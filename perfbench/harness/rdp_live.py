"""The ``rdp_live`` entry: ``FlaasService`` over a Rényi-DP block ledger
(``ServiceConfig.rdp``), driven tick by tick as the ``live`` entry drives
the scalar service, and its output check.

Set-up and window are ``live.py``'s (its ``Trace`` and ``recording`` are
reused): the warm-up arrivals, the service, the ring past its first wrap,
the window's arrivals from the warm-up's round rate, the admission
program compiled for every padded size (here with the curves' table),
the collector frozen.  The arrivals are the paper's §VI stream
(``generator.Arrivals``) with each pipeline's demand curve attached
(``generator_rdp.Curves``).

The output check replays :mod:`perfbench.harness.reference_rdp` tick by
tick from the program's per-order ledger; the numbers (each with its
limit in ``limits/<workload>.json``):

* ``mismatch_rounds_pct``: share of ticks whose selection differs from
  the reference's own decision;
* ``capacity_gap``: worst gap, at any order of any block, of the ledger
  after a tick's debits against the reference's debits from the same
  ledger (epsilon);
* ``spend_gap``: worst relative gap of a row's spend at an order against
  the reference's for the same selection (floor ``SPEND_FLOOR``);
* ``slot_faults``: selections of slots that were not pending, expiries
  the reference does not make, rows owned by another analyst (exact: 0);
* ``order_faults``: (tick, block) pairs of the program's ledger whose
  every order is overdrawn by more than ``ORDER_TOL`` (exact: 0): the
  guarantee that some order of every block stays within capacity.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, Optional

import numpy as np

# imported at start-up: a program without Rényi-DP ledgers stops here
from repro.privacy.rdp import RdpSpec

from .generator import Arrivals
from .generator_rdp import Curves
from .live import TRACE_SECONDS, Trace, recording

REFERENCE = "reference_rdp"
SPEND_FLOOR = 1e-3
# A grant fits while its normalized demand is within 1e-6 of the remaining
# share at some order, so an order the grant fits at can end 1e-6 x
# eps_G(a) <= 1e-5 epsilon below zero; float32 debits of ~10 epsilon add
# ~1e-6.  An overdraw of every order beyond 1e-4 is a broken guarantee.
ORDER_TOL = 1e-4


class RdpTrace(Trace):
    """``live.Trace`` whose submissions carry their demand curves."""

    def __init__(self, arrivals: Arrivals, curves: Curves, deployment: dict,
                 seed: int):
        super().__init__(arrivals, deployment, seed)
        self.curves = curves

    def extend(self, n_ticks: int) -> None:
        from repro.service import Submission
        events = self.arrivals.ticks(n_ticks)
        self.curves.attach(events)
        for t in range(len(self._subs), n_ticks):
            self._subs.append([Submission(analyst=b.analyst, submit_tick=t,
                                          bids=b.bids, eps=b.eps,
                                          loss=b.loss, curves=b.curves)
                               for b in events[t]])


def _warm_admission(svc, max_entries: int) -> None:
    """``live._warm_admission`` with the curves' table: the admission
    program for every padded COO size up to ``max_entries``, and none."""
    import jax
    from repro.service import admit_batch
    M, N = svc.cfg.analyst_slots, svc.cfg.pipeline_slots
    A = len(svc.cfg.rdp.orders)
    z = np.zeros((M, N))
    curve = np.zeros((M, N, A), np.float32)
    size = 0
    while True:
        idx = np.zeros(size, np.int64)
        jax.block_until_ready(admit_batch(
            svc.state, z.astype(bool), z, z, z.astype(np.int32), idx, idx,
            idx, idx.astype(np.float32), weight=np.ones(M, np.float32),
            curve=curve).demand)
        if size >= max_entries:
            break
        size = max(1, 2 * size)


def inputs(spec: dict, seed: int):
    """(arrivals, curves) of a run: the §VI stream and its curves."""
    d = spec["config"]["deployment"]
    return Arrivals(d, seed), Curves(d, spec["config"]["rdp"], seed)


def run(spec: dict, seed: int, seconds: float, traced: bool, t_start: float,
        trace_dir: Optional[str] = None, service_cls=None,
        compiles=None) -> Dict:
    """Set up, measure, and collect the program's answers (module
    docstring).  Returns the raw readings for the caller to check."""
    import jax
    from repro.core import SchedulerConfig
    from repro.service import FlaasService, ServiceConfig

    d = spec["config"]["deployment"]
    s = spec["config"]["scheduler"]
    rdp = spec["config"]["rdp"]
    mix = spec["traffic"]
    if traced:
        seconds = min(seconds, TRACE_SECONDS)
    if mix["chunk_ticks"] != 1:
        raise ValueError("the reference decides and admits tick by tick: "
                         "a live mix runs one tick per chunk")
    arrivals, curves = inputs(spec, seed)
    trace = RdpTrace(arrivals, curves, d, seed)
    warm = int(spec["config"]["warmup_ticks"])
    trace.extend(warm)
    cfg = ServiceConfig(
        scheduler=mix["scheduler"],
        sched=SchedulerConfig(beta=s["beta"], tau=s["tau"],
                              kappa_max=s["kappa_max"],
                              solver_iters=s["solver_iters"],
                              solver_tol=s["solver_tol"]),
        analyst_slots=d["analyst_slots"], pipeline_slots=d["pipeline_slots"],
        block_slots=d["block_slots"], chunk_ticks=mix["chunk_ticks"],
        admit_batch=d["admit_batch"], max_pending=d["max_pending"],
        profile_annotations=traced,
        rdp=RdpSpec(orders=tuple(rdp["orders"]), eps_g=rdp["eps_g"],
                    delta_g=rdp["delta_g"]))
    svc = recording(service_cls or FlaasService)(cfg, trace)
    warm_times = []
    while int(svc.state.tick) < warm:
        t0 = time.perf_counter()
        svc.run_chunk(1)
        warm_times.append(time.perf_counter() - t0)
    per_tick = min(float(np.median(warm_times[-8:])), 10.0)
    n_window = int(2.0 * seconds / max(per_tick, 1e-4)) + 64
    trace.extend(warm + n_window)
    biggest = max((sum(b.size for b in sub.bids)
                   for tick in trace._subs for sub in tick), default=1)
    _warm_admission(svc, d["admit_batch"] * biggest)
    gc.collect()
    gc.freeze()

    # ------------------------------------------------------------ window
    failed, error = 0, None
    ends = []
    if traced:
        jax.profiler.start_trace(trace_dir)
    phases0 = {k: v["seconds"] for k, v in svc.profiler.summary().items()}
    setup_s = time.perf_counter() - t_start
    if compiles is not None:
        compiles.start()
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("window"):
        while True:
            try:
                svc.run_chunk(1)
            except Exception as e:        # a round that raises is failed
                failed += 1
                error = f"{type(e).__name__}: {e}"
                break
            ends.append(time.perf_counter())
            if ends[-1] - w0 >= seconds:
                break
    if compiles is not None:
        compiles.stop()
    gc.unfreeze()
    if traced:
        jax.profiler.stop_trace()
    wall = (ends[-1] if ends else time.perf_counter()) - w0
    phases = {k: v["seconds"] - phases0.get(k, 0.0)
              for k, v in svc.profiler.summary().items()}
    round_s = np.diff(np.asarray([w0] + ends))

    stats = jax.devices()[0].memory_stats() or {}
    answers = _answers(svc)
    n_ticks = int(svc.state.tick)
    del svc
    gc.collect()
    return {"setup_s": setup_s, "wall_s": wall, "rounds": len(ends),
            "round_s": round_s, "failed": failed, "error": error,
            "phases": phases, "answers": answers, "arrivals": arrivals,
            "ticks": n_ticks,
            "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}


def _answers(svc) -> Dict:
    M, N = svc.cfg.analyst_slots, svc.cfg.pipeline_slots
    sel, spend, exp, cap = [], [], [], []
    for ys in svc.answers:
        if "capacity" not in ys:        # the round that raised
            continue
        cap.append(np.asarray(ys["capacity"], np.float32))
        s = np.asarray(ys["selected"], bool).reshape(-1, M, N)
        sel.append(s)
        spend.append(np.asarray(ys["analyst_spend"], np.float32)
                     .reshape(s.shape[0], M, -1))
        exp.append(np.asarray(ys["expired"], bool).reshape(-1, M, N)
                   if "expired" in ys else np.zeros_like(s))
    return {"selected": np.concatenate(sel), "spend": np.concatenate(spend),
            "expired": np.concatenate(exp), "capacity": np.stack(cap),
            "owner": svc.table.row_owner.copy()}


# ------------------------------------------------------------ the check
def replay(answers: Dict, deployment: dict, rdp: dict, arrivals,
           dtype=None) -> Dict[str, float]:
    """The numbers compared, for the program's ``answers``: ``selected
    [T, M, N]``, ``spend [T, M, A]``, ``expired [T, M, N]``, ``capacity
    [T, A, B]`` after each tick and ``owner [M]`` after the last."""
    from .reference_rdp import ReferenceRdp
    kw = {} if dtype is None else {"dtype": dtype}
    ref = ReferenceRdp(deployment, rdp, arrivals, **kw)
    sel = np.asarray(answers["selected"], bool)
    caps = np.asarray(answers["capacity"], np.float32)
    mism, spend_gap, cap_gap, faults, order_faults = 0, 0.0, 0.0, 0, 0
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
        order_faults += int(np.sum(np.all(caps[t] < -ORDER_TOL, axis=0)))
    faults += int((np.asarray(answers["owner"]) != ref.owner).sum())
    return {"mismatch_rounds_pct": 100.0 * mism / max(sel.shape[0], 1),
            "capacity_gap": cap_gap, "spend_gap": spend_gap,
            "slot_faults": float(faults), "order_faults": float(order_faults)}


def numbers(cell: dict, raw: Dict, dtype=None) -> Dict[str, float]:
    """The numbers compared for one run's ``raw`` readings."""
    return replay(raw["answers"], cell["config"]["deployment"],
                  cell["config"]["rdp"], raw["arrivals"], dtype)


def control_raw(cell: dict, seed: int, n_ticks: int, dtype) -> Dict:
    """Readings of the control in the program's place, shaped as a run's:
    the reference at ``dtype`` deciding on its own over ``n_ticks`` ticks,
    and the arrivals it replayed."""
    from .reference_rdp import ReferenceRdp
    arrivals, curves = inputs(cell, seed)
    curves.attach(arrivals.ticks(n_ticks))
    ref = ReferenceRdp(cell["config"]["deployment"], cell["config"]["rdp"],
                       arrivals, dtype=dtype)
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
