"""The ``sweep`` entry: ``repro.core.engine.run_fleet`` over fleets of §VI
episodes, call after call, and its output check.

Set-up (counted in ``setup_s``): draw ``fleets`` fleets of ``episodes``
episodes each from the mix's ``pool_seed`` with the harness's own §VI
draws (:mod:`perfbench.harness.generator`, not the program's
``generate_episode``), order them by the run's seed (:func:`ordered`),
put them on the device as the engine's batched ``Episode``, and make one
call on the last fleet (it compiles, or loads from the cache).  Every
seed so gets the same work in another order: an episode's solve time
follows its draws, so fleets drawn per seed would let the seed, not the
program, set the spread of the cell's metrics.
The window cycles the fleets call by call in a closed loop, so identical
inputs never come back to back: each call is ``run_fleet(fleet, cfg,
scheduler, mode=..., validate=True, diagnostics=True)`` as users make it,
and it returns once its conservation check has read the device.
``diagnostics`` is on because only it returns each round's selection and
its ledger at the round's start, which the check replays.  A call
advances every episode of the fleet by ``rounds`` rounds, so it counts
``episodes x rounds`` rounds, and its wall time over ``rounds`` is one
``round_s`` sample: the time to advance the whole fleet one round.

The check (:func:`numbers`; each number has its limit in
``limits/<workload>.json``) replays the first call of each fleet through
:mod:`perfbench.harness.reference_episode`, every round of every episode
judged from the program's own state at that round:

* ``mismatch_rounds_pct``: share of episode-rounds whose selection
  differs from the reference's own decision;
* ``capacity_gap``: worst gap of a block's epsilon after a round's debits
  (the program's next ledger, or its final capacity after the last round)
  against the reference's debit of the program's selection;
* ``efficiency_gap``, ``fairness_gap``, ``leftover_gap``: worst relative
  gap of a round's Eq-8 efficiency, Eq-9 fairness and epsilon left over
  all blocks against the reference's, for the same selection (floor
  ``GAP_FLOOR``);
* ``slot_faults``: selections of pipelines that were not pending,
  ``n_allocated`` other than the selection's size, and ``final_done``
  other than the union of the selections (exact: 0);
* ``repeat_faults``: calls whose answers differ, bit for bit, from the
  first call of the same fleet (exact: 0).

The control (:func:`control_raw`) is the reference run on its own
decisions and ledger at a lower precision, in the program's place.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List, Optional

import numpy as np

from . import reference_episode
from .generator import Arrivals

REFERENCE = "reference_episode"
ROUND_SECONDS = 10.0
TRACE_SECONDS = 5.0     # a traced run measures at most this long
GAP_FLOOR = 1e-3
ANSWERS = ("selected", "cap_frac", "n_allocated", "round_efficiency",
           "round_fairness", "leftover", "final_capacity", "final_done")


def episode(deployment: dict, seed: int, n_rounds: int) -> Dict:
    """One episode's inputs (``reference_episode.INPUTS``, numpy): the
    first ``analyst_slots`` analyst batches of ``n_rounds`` ticks of §VI
    arrivals, each pipeline demanding its blocks for the whole episode."""
    d = deployment
    arr = Arrivals(d, seed)
    M, N = d["analyst_slots"], d["pipeline_slots"]
    K = arr.bpr * n_rounds
    demand = np.zeros((M, N, K), np.float32)
    loss = np.ones((M, N), np.float32)
    arrival = np.zeros((M, N), np.float32)
    spawn = np.full(M, n_rounds, np.int32)          # n_rounds: never
    batches = [b for tick in arr.ticks(n_rounds) for b in tick][:M]
    for i, b in enumerate(batches):
        if len(b.bids) > N:
            raise ValueError(f"{len(b.bids)} pipelines, {N} slots")
        spawn[i] = b.tick
        arrival[i] = b.tick * ROUND_SECONDS
        loss[i, :len(b.loss)] = b.loss
        for j, (bids, eps) in enumerate(zip(b.bids, b.eps)):
            demand[i, j, bids] = eps
    budget = np.tile(np.repeat(arr.device_budget.astype(np.float32),
                               arr.bpd), n_rounds)
    born = np.repeat(np.arange(n_rounds, dtype=np.int32), arr.bpr)
    return {"demand": demand, "loss": loss, "arrival": arrival,
            "spawn": spawn, "budget": budget, "born": born}


def fleets(deployment: dict, seed: int, n_fleets: int, n_episodes: int,
           n_rounds: int) -> List[Dict]:
    """``n_fleets`` fleets of ``n_episodes`` distinct episodes each, every
    episode drawn from its own seed, a function of ``seed``."""
    out = []
    for f in range(n_fleets):
        eps = [episode(deployment, (seed * n_fleets + f) * n_episodes + e,
                       n_rounds) for e in range(n_episodes)]
        out.append({k: np.stack([ep[k] for ep in eps]) for k in eps[0]})
    return out


def ordered(pool: List[Dict], seed: int) -> List[Dict]:
    """The fleets of ``pool`` in an order drawn from ``seed``, and the
    episodes of each fleet too: the same fleets, so the same work a call,
    in another order."""
    rng = np.random.default_rng(seed)
    out = []
    for f in rng.permutation(len(pool)):
        perm = rng.permutation(len(pool[f]["demand"]))
        out.append({k: v[perm] for k, v in pool[f].items()})
    return out


def inputs(spec: dict, seed: int) -> List[Dict]:
    """A run's fleets: the mix's pool, ordered by ``seed``."""
    mix = spec["traffic"]
    return ordered(fleets(spec["config"]["deployment"], int(mix["pool_seed"]),
                          int(mix["fleets"]), int(mix["episodes"]),
                          int(mix["rounds"])), seed)


def _engine_fleet(fleet: Dict, n_rounds: int):
    """The program's batched ``Episode`` of ``fleet``, on the device."""
    import jax
    from repro.core.engine import Episode
    return jax.device_put(Episode(
        demand=fleet["demand"], loss=fleet["loss"],
        arrival=fleet["arrival"], spawn_round=fleet["spawn"],
        block_budget=fleet["budget"], block_round=fleet["born"],
        n_rounds=n_rounds))


def run(spec: dict, seed: int, seconds: float, traced: bool, t_start: float,
        trace_dir: Optional[str] = None, compiles=None) -> Dict:
    """Set up, measure, and collect the program's answers (see module
    docstring).  Returns the raw readings for the caller to check."""
    import jax
    from repro.core import SchedulerConfig, engine

    s = spec["config"]["scheduler"]
    mix = spec["traffic"]
    R, E = int(mix["rounds"]), int(mix["episodes"])
    if traced:
        seconds = min(seconds, TRACE_SECONDS)
    cfg = SchedulerConfig(beta=s["beta"], tau=s["tau"],
                          kappa_max=s["kappa_max"],
                          solver_iters=s["solver_iters"],
                          solver_tol=s["solver_tol"])
    fleet_inputs = inputs(spec, seed)
    device = [_engine_fleet(f, R) for f in fleet_inputs]

    def call(i):
        out = engine.run_fleet(device[i % len(device)], cfg,
                               mix["scheduler"], diagnostics=True,
                               validate=True, mode=mix["mode"])
        return jax.block_until_ready({k: out[k] for k in ANSWERS})

    call(len(device) - 1)       # the window starts on another fleet
    gc.collect()

    # ------------------------------------------------------------ window
    failed, error = 0, None
    ends, answers = [], []
    if traced:
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t_start
    if compiles is not None:
        compiles.start()
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("window"):
        while True:
            i = len(ends)
            try:
                answers.append((i % len(device), call(i)))
            except Exception as e:          # a call that raises is failed
                failed += 1
                error = f"{type(e).__name__}: {e}"
                break
            ends.append(time.perf_counter())
            if ends[-1] - w0 >= seconds:
                break
    if compiles is not None:
        compiles.stop()
    if traced:
        jax.profiler.stop_trace()
    wall = (ends[-1] if ends else time.perf_counter()) - w0
    round_s = np.diff(np.asarray([w0] + ends)) / R

    stats = jax.devices()[0].memory_stats() or {}
    answers = [(f, jax.device_get(a)) for f, a in answers]
    del device
    gc.collect()
    return {"setup_s": setup_s, "wall_s": wall, "rounds": len(ends) * E * R,
            "round_s": round_s, "failed": failed, "error": error,
            "phases": {}, "answers": answers, "fleets": fleet_inputs,
            "calls": len(ends),
            "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}


def _same(a: Dict, b: Dict) -> bool:
    """Bit for bit."""
    return all(np.asarray(a[k]).shape == np.asarray(b[k]).shape and
               np.asarray(a[k]).tobytes() == np.asarray(b[k]).tobytes()
               for k in ANSWERS)


def _gap(prog, ref) -> float:
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    if not prog.size:
        return 0.0
    return float(np.max(np.abs(prog - ref) / np.maximum(np.abs(ref),
                                                        GAP_FLOOR)))


def compare(fleet: Dict, ans: Dict, ref: Dict) -> Dict[str, float]:
    """One fleet's numbers (module docstring) from the program's answers
    ``ans`` and the reference's replay ``ref`` of them; ``mismatch``
    counts episode-rounds."""
    sel = np.asarray(ans["selected"], bool)
    E, R = sel.shape[:2]
    budget = np.asarray(fleet["budget"], np.float64)[:, None, :]
    born = np.asarray(fleet["born"])[:, None, :]
    # the program's ledger after each round's debits, on the blocks minted
    # by then: its next round's ledger, then its final capacity
    after = np.concatenate([np.asarray(ans["cap_frac"], np.float64)[:, 1:]
                            * budget, np.asarray(ans["final_capacity"],
                                                 np.float64)[:, None]], 1)
    minted = born <= np.arange(R)[None, :, None]
    cap_gap = np.abs(after - np.asarray(ref["left"], np.float64))
    done = np.zeros_like(sel)
    done[:, 1:] = np.logical_or.accumulate(sel, axis=1)[:, :-1]
    spawned = np.asarray(fleet["spawn"])[:, None, :, None] <= \
        np.arange(R)[None, :, None, None]
    faults = int((sel & ~(spawned & ~done)).sum())
    faults += int((np.asarray(ans["n_allocated"]) != sel.sum((2, 3))).sum())
    faults += int((np.asarray(ans["final_done"], bool)
                   != sel.any(axis=1)).sum())
    return {"mismatch": int((np.asarray(ref["own"], bool) != sel)
                            .any((2, 3)).sum()),
            "episode_rounds": E * R,
            "capacity_gap": float(np.max(np.where(minted, cap_gap, 0.0))),
            "efficiency_gap": _gap(ans["round_efficiency"],
                                   ref["efficiency"]),
            "fairness_gap": _gap(ans["round_fairness"], ref["fairness"]),
            "leftover_gap": _gap(ans["leftover"], ref["leftover"]),
            "slot_faults": faults}


def numbers(cell: dict, raw: Dict, dtype=None) -> Dict[str, float]:
    """The numbers compared for one run's ``raw`` readings, with the
    reference at ``dtype`` (float32 unless given)."""
    kw = {} if dtype is None else {"dtype": dtype}
    first: Dict[int, Dict] = {}
    repeat = 0
    for f, ans in raw["answers"]:
        if f not in first:
            first[f] = ans
        elif not _same(ans, first[f]):
            repeat += 1
    if not first:
        return {"mismatch_rounds_pct": float("nan")}
    per = []
    for f, ans in sorted(first.items()):
        ref = reference_episode.replay(
            raw["fleets"][f], np.asarray(ans["selected"], bool),
            np.asarray(ans["cap_frac"], np.float32),
            cell["config"]["scheduler"], cell["traffic"]["scheduler"], **kw)
        per.append(compare(raw["fleets"][f], ans, ref))
    out = {"mismatch_rounds_pct": 100.0 * sum(p["mismatch"] for p in per)
           / sum(p["episode_rounds"] for p in per)}
    for k in ("capacity_gap", "efficiency_gap", "fairness_gap",
              "leftover_gap"):
        out[k] = max(p[k] for p in per)
    out["slot_faults"] = float(sum(p["slot_faults"] for p in per))
    out["repeat_faults"] = float(repeat)
    return out


def control_raw(cell: dict, seed: int, n_calls: int, dtype) -> Dict:
    """Readings of the control in the program's place, shaped as a run's:
    the reference at ``dtype`` on its own decisions and ledger, over the
    run's fleets, called ``n_calls`` times in the window's order."""
    mix = cell["traffic"]
    fleet_inputs = inputs(cell, seed)
    own = [reference_episode.own(f, int(mix["rounds"]),
                                 cell["config"]["scheduler"],
                                 mix["scheduler"], dtype)
           for f in fleet_inputs]
    return {"answers": [(i % len(own), own[i % len(own)])
                        for i in range(max(n_calls, len(own)))],
            "fleets": fleet_inputs}
