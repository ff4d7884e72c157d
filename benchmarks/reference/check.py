"""The comparison that decides `correct`.

Outside the timed window, at the cell's full width, the plain reference
(`reference/train.py`) and the program (`trainer.train`) run the first slots
of the seeded history for one epoch from the same seeded init, and their
losses and final parameters must agree. Each comparison is made at two
precisions of the reference: "highest", the ground truth of the mathematics,
and "default", the program's own (it sets none, so on the chip its float32
products are ONE bfloat16 pass). Against "highest" the tolerance pays for that
rounding; against "default" both sides round the same products the same way
and only the order of the sums is left.

- SCHEDULE, over `check_slots` (3) slots: one update per slot, in order, with
  the optimizer `models/common.py` names. It fails a skipped, merged or
  reordered slot, another optimizer or learning rate, wrong mathematics. It
  cannot be tight: adamw moves an element whose gradient is within rounding of
  0 by a full +-lr, either way in two correct implementations, and the next
  slots' losses feel it, more for some seeds than for others.
- FORWARD, over `forward_check_slots` (1) slot: the loss of the first slot is
  the forward pass alone, before any update, so at the program's own precision
  it agrees to the last few bits and the bound is tight. For GraphSAGE this is
  the comparison that sees a history stored in bfloat16 (the features rounded
  before they are aggregated, not only before they are multiplied). How many
  such one-slot histories are read, and the bounds, are the family's
  (`reference/<family>.py`; the defaults and the rule stand beside
  FORWARD_READINGS below). A family states its own only with its readings:
  the largest that the sound program gave over a dozen seeds or more, the
  smallest that its control gave (the reference in the program's place, one
  precision lower where the family is tempted), at the cell's width on the
  chip, and a bound between the two with room on both sides.

Read on the v5e at the cell's width over 28 seeds (PR 24, PERF.md section 6;
largest relative difference of the three losses; parameters as the bounds'
comment below defines):

                        highest                   default
    SCHEDULE  losses 6.1e-5 .. 6.5e-3      5.8e-8 .. 1.8e-5
              params 2.0e-2 .. 1.1e-1      5.4e-5 .. 1.4e-2
    FORWARD   losses 1.2e-5 .. 8.0e-4      0      .. 2.2e-7
              params 1.9e-2 .. 1.5e-1      9.7e-7 .. 1.9e-2
    history stored in bfloat16, FORWARD default losses: 1.1e-7 .. 2.8e-5,
    over the bound of 1e-6 for 17 of the 18 seeds it was read on

The chaotic readings (everything but FORWARD's default losses) spread over two
decades from seed to seed, so their bounds stand 4 to 50 times over the
largest reading; FORWARD's default losses are a few units in the last place
(float32's is 1.2e-7), and their bound is 1e-6.

Read again for GraphSAGE in PR 32 (v5e, the cell's width, 20 seeds x slots 0,
1, 2 each alone): FORWARD default losses 0 .. 3.2e-7 over 60 readings (and 0 ..
2.0e-7 over 12 more); with the history rounded to bfloat16 2.2e-6 .. 4.1e-5
over 54 readings, on slot 0 4.6e-6 .. 4.1e-5: over 1e-6 on 18 seeds of 18. So
one reading under 1e-6 stands for GraphSAGE: 3 times over the largest sound
reading, 2.2 times under the smallest of the control. GAT's readings do not
fit it, and a bfloat16 history is none of FORWARD's to see there:
`reference/gat.py` says why and states its own.

Inside the window: every call re-inits from the seed, so every call is the
same computation on the same device as the warm call of set-up, and its three
losses must equal that call's.
"""
from __future__ import annotations

import dataclasses
import importlib
import math
from dataclasses import dataclass
from typing import Callable, Dict, List

import numpy as np

from benchmarks.reference import train as ref_train

# Parameters are compared as a whole: the length of the difference over the
# length of the reference's own movement from the init. Not element by
# element, because adamw's first steps move every element by about lr
# whatever its gradient's size: an element whose gradient is within rounding
# of 0 can go either way by 2 * lr in two correct implementations (seen on the
# CPU at float32: 1 element of 10,790 off by 8e-5, the rest by 1e-7; on the
# chip against "highest": a few elements of each matrix off by 2e-2 = 2 * lr).
# Each bound: precision -> (largest relative difference of the three losses,
# length of the parameter difference over length of the movement).
SCHEDULE = {"highest": (5e-2, 5e-1), "default": (1e-3, 2.5e-1)}
FORWARD = {"highest": (1e-2, 5e-1), "default": (1e-6, 2.5e-1)}

# How FORWARD is read is the family's (`reference/<family>.py` may state
# FORWARD and FORWARD_READINGS of its own; these are the defaults, and
# GraphSAGE's). With n readings, each the first-slot comparison of a one-slot
# history (slots 0 .. n-1 of the run, from the same seeded init; the further
# ones at "default" alone), FORWARD passes where more than half of them are
# within the "default" loss bound (n = 1: the one; n = 3: the middle one of
# three), every parameter difference is within its bound, and slot 0 passes
# at "highest". A single reading far off is SCHEDULE's to see (1e-3 over the
# same slots), so FORWARD holds no ceiling of its own.
FORWARD_READINGS = 1

# Two runs of one program on one device from one seed: the same bits. The
# slack is for nothing but a compiler that reorders a reduction between two
# compilations of the same program.
SAME_RTOL = 1e-6


@dataclass
class Verdict:
    ok: bool
    detail: Dict[str, object]

    def compared(self) -> Dict[str, Dict[str, float]]:
        """Each number compared beside its limit, by a short plain name."""
        out = {
            f"{part}.{precision}.{number}": {"value": self.detail[part][precision][f"{number}_rel"],
                                             "limit": self.detail[part][precision][limit]}
            for part in ("schedule", "forward")
            for precision in ("highest", "default")
            for number, limit in (("loss", "loss_rtol"), ("param", "param_tol"))
        }
        for number, (value, limit) in forward_numbers(self.detail["forward"]).items():
            out[f"forward.default.{number}"] = {"value": value, "limit": limit}
        return out


def _number(v: float) -> float:
    """A nan as what it means for a comparison: over every limit."""
    return math.inf if math.isnan(v) else v


def to_host(params) -> Dict[str, np.ndarray]:
    """A head's NamedTuple of parameters as name -> host array (the absent
    node embedding dropped)."""
    return {k: np.asarray(v) for k, v in params._asdict().items() if v is not None}


def triple(result) -> List[float]:
    """The three losses `trainer.train` reports for its last epoch."""
    return [result.losses[-1], result.latency_losses[-1], result.anomaly_losses[-1]]


def _compare(config: dict, dataset, seed: int, model, call: Callable, tolerances) -> Dict[str, object]:
    """The program on `dataset` (through `call`, the driver's own way of
    calling it, so the check runs what the window runs) against the plain
    reference at each precision of `tolerances`."""
    import jax

    init = to_host(
        model.init_params(
            jax.random.PRNGKey(seed),
            hidden=int(config["hidden"]),
            num_features=int(config["num_features"]),
            num_nodes=0,
        )
    )
    result = call(dataset)
    got = np.array(triple(result))
    got_params = to_host(result.params)
    detail: Dict[str, object] = {"losses": got.tolist(), "ok": bool(np.all(np.isfinite(got)))}
    for precision, (loss_rtol, param_tol) in tolerances.items():
        want_params, per_slot = ref_train.train(
            config["family"], init, dataset, float(config["lr"]), precision=precision
        )
        want = np.mean(np.asarray(per_slot, dtype=np.float64), axis=0)
        loss_rel = float(np.max(np.abs(got - want) / np.abs(want)))
        param_rel = float("inf")
        if set(got_params) == set(want_params):
            diff = np.concatenate([(got_params[k] - want_params[k]).ravel() for k in want_params])
            moved = np.concatenate([(want_params[k] - init[k]).ravel() for k in want_params])
            param_rel = float(np.linalg.norm(diff) / np.linalg.norm(moved))
        passed = loss_rel <= loss_rtol and param_rel <= param_tol  # False on nan
        detail["ok"] = detail["ok"] and passed
        detail[precision] = {
            "ok": passed,
            "reference_losses": want.tolist(),
            "loss_rel": loss_rel,
            "loss_rtol": loss_rtol,
            "param_rel": param_rel,
            "param_tol": param_tol,
        }
    return detail


def _slots(dataset, lo: int, hi: int):
    """Slots lo..hi-1 of `dataset` alone, over the same graph (views)."""
    per_slot = ("features", "target_latency", "target_anomaly", "node_mask", "slot_keys")
    return dataclasses.replace(dataset, **{k: getattr(dataset, k)[lo:hi] for k in per_slot})


def forward_numbers(detail: Dict[str, object]) -> Dict[str, tuple]:
    """What FORWARD's rule (beside FORWARD_READINGS) compares at the program's
    own precision over the family's readings, each as (value, limit): the
    reading that more than half are within (the middle one of three; a
    reading that is no number is no flip, and puts it over every bound) and
    the largest parameter difference."""
    own = detail["readings"]
    losses = sorted((_number(r["loss_rel"]) for r in own), reverse=True)
    return {
        "loss": (losses[(len(own) - 1) // 2 if math.isfinite(losses[0]) else 0], detail["default"]["loss_rtol"]),
        "param": (max(_number(r["param_rel"]) for r in own), detail["default"]["param_tol"]),
    }


def forward_ok(detail: Dict[str, object]) -> bool:
    return bool(
        np.all(np.isfinite(detail["losses"]))
        and detail["highest"]["ok"]
        and all(value <= limit for value, limit in forward_numbers(detail).values())
    )


def against_reference(config: dict, head: Callable, mix: dict, seed: int, model, call: Callable) -> Verdict:
    """`head(n)` is the first n slots of the run's history; `mix` names how
    many SCHEDULE takes (`check_slots`) and how long one FORWARD history is
    (`forward_check_slots`); the family, how FORWARD is read."""
    family = importlib.import_module(f"benchmarks.reference.{config['family']}")
    bounds = getattr(family, "FORWARD", FORWARD)
    first = head(int(mix["check_slots"]))
    span = int(mix["forward_check_slots"])
    readings = min(int(getattr(family, "FORWARD_READINGS", FORWARD_READINGS)), len(first.features) // span)
    own = {"default": bounds["default"]}  # the further readings are taken at the program's own precision alone
    try:
        schedule = _compare(config, first, seed, model, call, SCHEDULE)
        forward = _compare(config, _slots(first, 0, span), seed, model, call, bounds)
        forward["readings"] = [forward["default"]] + [
            _compare(config, _slots(first, s * span, (s + 1) * span), seed, model, call, own)["default"]
            for s in range(1, readings)
        ]
    finally:
        ref_train.compiled.cache_clear()  # nothing of the reference's lives through the window
    forward["ok"] = forward_ok(forward)
    ok = bool(schedule["ok"] and forward["ok"])
    return Verdict(ok, {"schedule": schedule, "forward": forward, "ok": ok})


def same_computation(got: List[float], first: List[float]) -> bool:
    return all(
        math.isfinite(g) and abs(g - f) <= SAME_RTOL * abs(f)
        for g, f in zip(got, first)
    )
