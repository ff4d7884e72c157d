"""The forecast heads by name: the name `trainer.train` writes into a
checkpoint's metadata is the head's own `NAME`, and the served process
(api/handlers/model.py) builds the checkpoint's template from the head that
name finds here. Adding a head is one line of `_modules`."""
from __future__ import annotations

from typing import Optional


def _modules():
    from kmamiz_tpu.models import gat, gatv2, graphsage, pna
    from kmamiz_tpu.models.stlgt import model as stlgt

    return (graphsage, gat, gatv2, pna, stlgt)


def by_name(name: Optional[str]):
    """The head module a checkpoint names. No name is GraphSAGE, the one head
    whose checkpoints were written before the metadata named a head; a name
    this process does not know is an error that says so."""
    heads = {module.NAME: module for module in _modules()}
    if name is None:
        return heads["graphsage"]
    if name not in heads:
        raise ValueError(f"the checkpoint names the head {name!r}, which this process does not know ({sorted(heads)})")
    return heads[name]
