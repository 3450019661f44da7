"""The ``live`` entry: ``FlaasService.run_chunk`` driven tick by tick.

Set-up (counted in ``setup_s``): generate the warm-up arrivals, build the
service, run the ring past its first wrap (the wrap-free and the paged
chunk programs compile there), generate the window's arrivals from the
warm-up's round rate, compile the admission programs for every padded
size they can ask for, and freeze what set-up made out of the garbage
collector's passes.  The window runs ticks until
``seconds`` have passed (``TRACE_SECONDS`` at most in a traced run, whose
trace would otherwise outgrow the run's time); each tick is one round,
timed from boundary to boundary (``run_chunk`` ends in the host sync,
which waits for the chip).

The program sees only :class:`Trace`, which replays the pre-generated
arrivals as the program's own ``Submission`` objects.
"""
from __future__ import annotations

import gc
import time
import types
from typing import Dict, Optional

import numpy as np

from .check import control_raw, numbers                      # noqa: F401
from .generator import Arrivals

REFERENCE = "reference"     # the output check replays harness/reference.py
ROUND_SECONDS = 10.0
TRACE_SECONDS = 5.0     # a traced run measures at most this long
ANSWERS = ("selected", "analyst_spend", "expired")


class Trace:
    """The service's arrival-trace protocol over pre-generated arrivals.
    Stepping past the generated prefix is an error, never a wrap-around."""

    pattern = "poisson"
    tiers = None

    def __init__(self, arrivals: Arrivals, deployment: dict, seed: int):
        self.arrivals = arrivals
        self.sim = types.SimpleNamespace(
            pipelines_per_analyst=deployment["pipelines_per_analyst"])
        self.seed = seed
        self.device_budget = arrivals.device_budget
        self.blocks_per_device = arrivals.bpd
        self.blocks_per_tick = arrivals.bpr
        self._subs = []
        self._next = 0

    def extend(self, n_ticks: int) -> None:
        from repro.service import Submission
        events = self.arrivals.ticks(n_ticks)
        for t in range(len(self._subs), n_ticks):
            self._subs.append([Submission(analyst=b.analyst, submit_tick=t,
                                          bids=b.bids, eps=b.eps,
                                          loss=b.loss) for b in events[t]])

    def step(self, tick: int):
        if tick != self._next:
            raise ValueError(f"ticks must be consecutive: expected "
                             f"{self._next}, got {tick}")
        if tick >= len(self._subs):
            raise RuntimeError(f"tick {tick} is past the {len(self._subs)} "
                               f"pre-generated ticks")
        self._next += 1
        return self._subs[tick]

    def arrival_seconds(self, tick: int) -> float:
        return tick * ROUND_SECONDS


def recording(base):
    """``base`` (a ``FlaasService`` class) keeping every tick's answers as
    the compiled chunk step returns them, and the ledger after every tick
    (the state's ``block_capacity``; a chunk is one tick)."""

    class Recording(base):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.answers = []

        def _compiled_step(self, n_ticks, mode):
            step = super()._compiled_step(n_ticks, mode)

            def run(state, ops):
                final, ys = step(state, ops)
                self.answers.append({k: ys[k] for k in ANSWERS if k in ys})
                return final, ys
            return run

        def run_chunk(self, n_ticks=None):
            ys = super().run_chunk(n_ticks)
            # the host copies that the program's sync already made: no
            # device buffer of the outputs outlives the tick
            rec = {k: np.asarray(v) for k, v in self.answers[-1].items()}
            rec["capacity"] = self.state.block_capacity
            self.answers[-1] = rec
            return ys

    return Recording


def _warm_admission(svc, max_entries: int) -> None:
    """Compile the admission write for every padded COO size up to
    ``max_entries`` (the program pads to powers of two), and for none (a
    batch whose every entry was retired while it queued)."""
    import jax
    from repro.service import admit_batch
    M, N = svc.cfg.analyst_slots, svc.cfg.pipeline_slots
    z = np.zeros((M, N))
    size = 0
    while True:
        idx = np.zeros(size, np.int64)
        jax.block_until_ready(admit_batch(
            svc.state, z.astype(bool), z, z, z.astype(np.int32), idx, idx,
            idx, idx.astype(np.float32)).demand)
        if size >= max_entries:
            break
        size = max(1, 2 * size)


def run(spec: dict, seed: int, seconds: float, traced: bool, t_start: float,
        trace_dir: Optional[str] = None, service_cls=None,
        compiles=None) -> Dict:
    """Set up, measure, and collect the program's answers (see module
    docstring).  Returns the raw readings for the caller to check."""
    import jax
    from repro.core import SchedulerConfig
    from repro.service import FlaasService, ServiceConfig

    d = spec["config"]["deployment"]
    s = spec["config"]["scheduler"]
    mix = spec["traffic"]
    if traced:
        seconds = min(seconds, TRACE_SECONDS)
    if mix["chunk_ticks"] != 1:
        raise ValueError("the reference decides and admits tick by tick: "
                         "a live mix runs one tick per chunk")
    arrivals = Arrivals(d, seed)
    trace = Trace(arrivals, d, seed)
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
        profile_annotations=traced)
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
    # The pre-generated arrivals are millions of long-lived objects that a
    # served load would not keep in the process: keep them out of the
    # collector's full passes during the window.
    gc.collect()
    gc.freeze()

    # ------------------------------------------------------------ window
    failed, error = 0, None
    ends = []
    full_gc = []                    # seconds of each full collection
    gc_start = [0.0]

    def on_gc(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                gc_start[0] = time.perf_counter()
            else:
                full_gc.append(time.perf_counter() - gc_start[0])
    if traced:
        jax.profiler.start_trace(trace_dir)
    phases0 = {k: v["seconds"] for k, v in svc.profiler.summary().items()}
    setup_s = time.perf_counter() - t_start
    if compiles is not None:
        compiles.start()
    gc.callbacks.append(on_gc)
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
    gc.callbacks.remove(on_gc)
    gc.unfreeze()
    if traced:
        jax.profiler.stop_trace()
    wall = (ends[-1] if ends else time.perf_counter()) - w0
    phases = {k: v["seconds"] - phases0.get(k, 0.0)
              for k, v in svc.profiler.summary().items()}
    round_s = np.diff(np.asarray([w0] + ends))

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    answers = _answers(svc)
    n_ticks = int(svc.state.tick)
    del svc
    gc.collect()
    return {"setup_s": setup_s, "wall_s": wall, "rounds": len(ends),
            "round_s": round_s, "failed": failed, "error": error,
            "phases": phases, "answers": answers, "arrivals": arrivals,
            "ticks": n_ticks, "full_gc_s": full_gc,
            "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}


def _answers(svc) -> Dict:
    M, N = svc.cfg.analyst_slots, svc.cfg.pipeline_slots
    sel, spend, exp, cap = [], [], [], []
    for ys in svc.answers:
        cap.append(np.asarray(ys["capacity"], np.float32))
        s = np.asarray(ys["selected"], bool)
        sel.append(s)
        spend.append(np.asarray(ys["analyst_spend"], np.float32))
        exp.append(np.asarray(ys["expired"], bool) if "expired" in ys
                   else np.zeros_like(s))
    return {"selected": np.concatenate(sel).reshape(-1, M, N),
            "spend": np.concatenate(spend).reshape(-1, M),
            "expired": np.concatenate(exp).reshape(-1, M, N),
            "capacity": np.stack(cap), "owner": svc.table.row_owner.copy()}
