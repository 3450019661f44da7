"""The output check of a live cell: the program's answers against the
plain reference (:mod:`perfbench.harness.reference`), tick by tick.

The answers compared are what the timed path itself produced: every tick's
selection ``[M, N]``, epsilon spent per analyst row, pipelines expired,
every block's capacity after the tick's debits, and, once the window has
closed, the owner of every analyst row.  The reference replays the same
arrivals from tick 0; at each tick it takes the ledger the program left
after the previous tick, decides on its own, then applies the program's
selection, so that every tick is judged from the same history.

Numbers (each has its limit in ``limits/<workload>.json``):

* ``mismatch_rounds_pct``: share of ticks whose selection differs from the
  reference's own decision;
* ``spend_gap``: worst relative gap of a row's epsilon spend against the
  reference's spend for the same selection (floor 1e-3 epsilon);
* ``capacity_gap``: worst gap of a block's epsilon after a tick's debits
  against the reference's debits from the same ledger;
* ``slot_faults``: selections of slots that were not pending, expiries the
  reference does not make, and rows owned by another analyst (exact: 0).

The control (:func:`control_raw`) is the same reference at a lower
precision, deciding on its own, in the program's place.
"""
from __future__ import annotations

import sys
from typing import Dict

import numpy as np

from .reference import ReferenceService

SPEND_FLOOR = 1e-3


def replay(answers: Dict, deployment: dict, sched: dict, scheduler: str,
           arrivals, dtype=None) -> Dict[str, float]:
    """The numbers compared, for the program's ``answers``:
    ``selected [T, M, N]``, ``spend [T, M]``, ``expired [T, M, N]``,
    ``capacity [T, B]`` after each tick and ``owner [M]`` after the
    last."""
    kw = {} if dtype is None else {"dtype": dtype}
    ref = ReferenceService(deployment, sched, scheduler, arrivals, **kw)
    sel = np.asarray(answers["selected"], bool)
    caps = np.asarray(answers["capacity"], np.float32)
    mism, spend_gap, cap_gap, faults = 0, 0.0, 0.0, 0
    for t in range(sel.shape[0]):
        before = caps[t - 1] if t else np.zeros_like(caps[0])
        own, expired, spend, invalid, cap = ref.step(forced=sel[t],
                                                     capacity=before)
        mism += int((own != sel[t]).any())
        cap_gap = max(cap_gap, float(np.max(np.abs(
            caps[t].astype(np.float64) - cap))))
        gap = np.abs(np.asarray(answers["spend"][t], np.float64) - spend)
        spend_gap = max(spend_gap, float(np.max(
            gap / np.maximum(np.abs(spend), SPEND_FLOOR))))
        faults += invalid + int((np.asarray(answers["expired"][t], bool)
                                 != expired).sum())
    faults += int((np.asarray(answers["owner"]) != ref.owner).sum())
    return {"mismatch_rounds_pct": 100.0 * mism / max(sel.shape[0], 1),
            "spend_gap": spend_gap, "capacity_gap": cap_gap,
            "slot_faults": float(faults)}


def numbers(cell: dict, raw: Dict, dtype=None) -> Dict[str, float]:
    """The numbers compared for one run's ``raw`` readings."""
    return replay(raw["answers"], cell["config"]["deployment"],
                  cell["config"]["scheduler"], cell["traffic"]["scheduler"],
                  raw["arrivals"], dtype)


def control_raw(cell: dict, seed: int, n_ticks: int, dtype) -> Dict:
    """Readings of the control in the program's place, shaped as a run's:
    the answers of the reference at ``dtype``, deciding on its own over
    ``n_ticks`` ticks, and the arrivals it replayed."""
    from .generator import Arrivals
    d = cell["config"]["deployment"]
    arrivals = Arrivals(d, seed)
    arrivals.ticks(n_ticks)
    ref = ReferenceService(d, cell["config"]["scheduler"],
                           cell["traffic"]["scheduler"], arrivals,
                           dtype=dtype)
    sel, spend, exp, cap = [], [], [], []
    for _ in range(n_ticks):
        own, expired, sp, _, c = ref.step()
        sel.append(own)
        spend.append(sp)
        exp.append(expired)
        cap.append(c)
    return {"answers": {"selected": np.stack(sel), "spend": np.stack(spend),
                        "expired": np.stack(exp), "capacity": np.stack(cap),
                        "owner": ref.owner.copy()},
            "arrivals": arrivals}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, ``{name: {"value", "limit"}}``).  The cell's limits name
    the numbers compared; a number above its limit, or a limit without its
    number, is not correct.  A number the cell does not compare is printed
    with the limit None."""
    if not limits:
        return False, {k: {"value": v, "limit": None}
                       for k, v in numbers.items()}
    checks = {k: {"value": numbers.get(k, float("nan")), "limit": lim}
              for k, lim in limits.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    checks.update({k: {"value": v, "limit": None}
                   for k, v in numbers.items() if k not in limits})
    return ok, checks


def print_checks(checks: Dict) -> None:
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
