"""The benchmark's own tests: tiny sizes, on the CPU. Run from the root of
the checkout: `python -m pytest benchmarks/tests -q`. They live outside
`tests/`, so they move no tier-1 count."""
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = Path(__file__).resolve().parent.parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

#: the cell's distributions at a size a CPU trains in a second
TINY = {
    "name": "tiny",
    "family": "graphsage",
    "model_module": "kmamiz_tpu.models.graphsage",
    "generator": "mesh_history",
    "endpoints": 256,
    "node_bucket": 256,
    "edges": 1200,
    "edge_bucket": 2048,
    "num_features": 18,
    "hidden": 64,
    "node_embeddings": False,
    "slots": 8,
    "batch_slots": 1,
    "lr": 0.01,
    "assumed": {
        "in_degree": {"exponent": 1.0, "offset": 10},
        "out_degree": {"sigma": 1.0},
        "active_share": 0.95,
        "anomaly_base_rate": 0.10,
    },
}


@pytest.fixture
def tiny_config():
    import copy

    return copy.deepcopy(TINY)
