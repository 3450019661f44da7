"""The output check at a size a CPU holds: sound runs pass; the control
(the reference in bfloat16) and every fault planted under the timed path
make ``correct`` false.  Each check is the one its entry owns, reached
through the seam (``runner.entry_module``) as a run reaches it.

A run here skips the harness's look for a chip and drives the rest of a
run: set-up, the window, the answers, the reference replay and the
verdict under the cell's own limits.
"""
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import check, live, runner, spec
from repro.core import engine
from repro.service import FlaasService

ROOT = Path(__file__).resolve().parents[2]
SCHEDULERS = ("dpbalance", "dpf")
SWEEP = "dpbalance.sweep_map"
SEED = 3 * 2 ** 31 + 11


def tiny_cell(scheduler):
    """A cell's mix and limits over a small deployment: the live cell of
    ``scheduler``, or with ``SWEEP`` the sweep cell at 2 fleets of 4
    episodes of 4 analysts x 5 pipelines over 200 blocks."""
    if scheduler == SWEEP:
        c = spec.cell(f"paper_vi.{SWEEP}", spec.benchmark(ROOT))
        cfg = json.loads(json.dumps(c["config"]))
        cfg["deployment"].update(n_devices=10, analyst_slots=4,
                                 pipeline_slots=5, pipelines_per_analyst=5)
        return dict(c, config=cfg,
                    traffic=dict(c["traffic"], episodes=4, fleets=2))
    c = spec.cell(f"paper_vi.{scheduler}.live", spec.benchmark(ROOT))
    mix = json.loads((ROOT / "perfbench" / "traffic" /
                      f"{scheduler}.live.json").read_text())
    c = dict(c, traffic=mix)
    cfg = json.loads(json.dumps(c["config"]))
    cfg["deployment"].update(n_devices=10, analyst_slots=4, block_slots=200,
                             admit_batch=4, max_pending=32, arrival_rate=1.0)
    cfg["warmup_ticks"] = 12
    return dict(c, config=cfg)


def measure(cell, service_cls=None):
    return runner.measure(cell, SEED, 0.3, False, time.perf_counter(),
                          service_cls=service_cls)


class StateUnchanged(FlaasService):
    """The chunk step hands back the state it was given."""

    def _compiled_step(self, n_ticks, mode):
        step = super()._compiled_step(n_ticks, mode)

        def run(state, ops):
            final, ys = step(state, ops)
            keep = (state.done, state.block_capacity)
            if len(final) == 3:
                keep = (state.demand,) + keep
            return keep, ys
        return run


class HalfBatch(FlaasService):
    """The round sees only the first half of the analyst rows."""

    def _compiled_step(self, n_ticks, mode):
        step = super()._compiled_step(n_ticks, mode)

        def run(state, ops):
            half = state.spawn_tick.shape[0] // 2
            spawn = state.spawn_tick.at[half:].set(np.iinfo(np.int32).max)
            return step(dataclasses.replace(state, spawn_tick=spawn), ops)
        return run


class AlteredAnswer(FlaasService):
    """One selection of the window's ticks flipped where it is produced."""

    def _compiled_step(self, n_ticks, mode):
        step = super()._compiled_step(n_ticks, mode)

        def run(state, ops):
            final, ys = step(state, ops)
            if int(state.tick) == 14:
                sel = ys["selected"]
                ys = dict(ys, selected=sel.at[0, 0, 0].set(~sel[0, 0, 0]))
            return final, ys
        return run


class WarmStart(FlaasService):
    """The program with SP1's warm-started duals on: the chunk carry then
    ends in the duals, not in the ledger."""

    def __init__(self, cfg, trace, **kw):
        sched = dataclasses.replace(cfg.sched, sp1_warm_start=True)
        super().__init__(dataclasses.replace(cfg, sched=sched), trace, **kw)


@pytest.mark.parametrize("scheduler", SCHEDULERS + (SWEEP,))
def test_sound_run_is_correct(scheduler):
    cell = tiny_cell(scheduler)
    out = measure(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert {k for k, c in out["checks"].items()
            if c["limit"] is not None} == set(cell["limits"])


@pytest.mark.parametrize("scheduler", SCHEDULERS)
@pytest.mark.parametrize("fault", [StateUnchanged, HalfBatch, AlteredAnswer],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(scheduler, fault):
    out = measure(tiny_cell(scheduler), service_cls=fault)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("scheduler", SCHEDULERS + (SWEEP,))
def test_control_in_bfloat16_fails_the_limits(scheduler):
    cell = tiny_cell(scheduler)
    entry = runner.entry_module(cell["traffic"]["entry"])
    raw = entry.control_raw(cell, SEED, 40, jnp.bfloat16)
    ok, checks = check.verdict(entry.numbers(cell, raw), cell["limits"])
    assert not ok, checks


@pytest.mark.parametrize("scheduler", SCHEDULERS)
def test_live_numbers_through_the_seam_are_the_check_s(scheduler):
    """The live entry's numbers are ``check.replay`` of the run's answers
    over its arrivals, as before the seam, for the program and for the
    control alike."""
    cell = tiny_cell(scheduler)
    d, sched = cell["config"]["deployment"], cell["config"]["scheduler"]
    entry = runner.entry_module("live")
    assert entry.REFERENCE == "reference"
    raw = live.run(cell, SEED, 0.3, False, time.perf_counter())
    ctl = entry.control_raw(cell, SEED, 30, jnp.bfloat16)
    for r in (raw, ctl):
        assert entry.numbers(cell, r) == check.replay(
            r["answers"], d, sched, scheduler, r["arrivals"])


def test_sweep_seeds_run_the_same_fleets_in_another_order():
    """Every seed gets the mix's pool of fleets, each fleet's episodes as
    a set unchanged (the same work a call), in an order of its own."""
    from perfbench.harness import sweep
    cell = tiny_cell(SWEEP)
    a, b = sweep.inputs(cell, SEED), sweep.inputs(cell, SEED + 1)
    assert [f["demand"].shape for f in a] == [f["demand"].shape for f in b]

    def fleet_sets(runs):
        return sorted(sorted(e.tobytes() for e in f["demand"]) for f in runs)
    assert fleet_sets(a) == fleet_sets(b)
    assert any(not np.array_equal(x["demand"], y["demand"])
               for x, y in zip(a, b))
    again = sweep.inputs(cell, SEED)
    assert all(np.array_equal(x[k], y[k]) for x, y in zip(a, again)
               for k in x)


# --------------------------------------------------- faults of a sweep
def drop_grants(fleet, out):
    """Episode 1's first round that grants anything grants nothing."""
    n = np.asarray(out["n_allocated"])
    r = int(np.flatnonzero(n[1] > 0)[0])
    return dict(out, selected=out["selected"].at[1, r].set(False),
                n_allocated=out["n_allocated"].at[1, r].set(0))


def swap_episodes(fleet, out):
    """Episodes 0 and 1 of the fleet swapped in every answer."""
    return {k: v.at[jnp.array([0, 1])].set(v[jnp.array([1, 0])])
            for k, v in out.items()}


def move_capacity(fleet, out):
    """One block's final capacity moved by 1e-3 epsilon."""
    k = int(np.argmax(np.asarray(out["final_capacity"][0])))
    return dict(out, final_capacity=out["final_capacity"].at[0, k]
                .add(1e-3))


def state_unchanged(fleet, out):
    """Every round hands back the ledger and the done set it was given:
    each block keeps its whole budget from its mint on."""
    R = out["selected"].shape[1]
    born = fleet.block_round[:, None, :]
    share = (born <= jnp.arange(R)[None, :, None]).astype(jnp.float32)
    return dict(out, cap_frac=share, final_capacity=fleet.block_budget,
                final_done=jnp.zeros_like(out["final_done"]))


FLEET_FAULTS = [drop_grants, swap_episodes, move_capacity, state_unchanged]


@pytest.mark.parametrize("fault", FLEET_FAULTS + ["half_batch",
                                                  "repeat_differs"],
                         ids=lambda f: getattr(f, "__name__", f))
def test_fleet_fault_is_not_correct(fault, monkeypatch):
    """A fault planted under the sweep's timed path, in what
    ``engine.run_fleet`` returns: ``correct`` comes out false."""
    real = engine.run_fleet
    calls = []

    def run_fleet(fleet, *a, **kw):
        calls.append(1)
        if fault == "half_batch":
            # the program runs the first half of the fleet; the second
            # half's answers are copies of the first's
            half = fleet.demand.shape[0] // 2
            out = real(jax.tree.map(lambda x: x[:half], fleet), *a, **kw)
            return {k: jnp.concatenate([v, v]) for k, v in out.items()}
        out = real(fleet, *a, **kw)
        if fault == "repeat_differs":
            if len(calls) > 3:      # set-up, then one call of each fleet
                e = out["round_efficiency"]
                return dict(out, round_efficiency=e.at[0, 0].set(
                    jnp.nextafter(e[0, 0], jnp.inf)))
            return out
        return fault(fleet, out)

    monkeypatch.setattr(engine, "run_fleet", run_fleet)
    out = runner.measure(tiny_cell(SWEEP), SEED, 0.5, False,
                         time.perf_counter())
    assert out["attempted"] >= 3 * 4 * 10          # calls x episodes x R
    assert not out["correct"], out["checks"]


def test_ledger_is_read_by_name_with_warm_start():
    """The answers' ledger is the state's ``block_capacity`` whatever the
    chunk carry holds: with warm-started SP1 the ledger still agrees."""
    cell = tiny_cell("dpbalance")
    raw = live.run(cell, SEED, 0.3, False, time.perf_counter(),
                   service_cls=WarmStart)
    nums = check.numbers(cell, raw)
    assert nums["capacity_gap"] <= cell["limits"]["capacity_gap"], nums
    assert nums["slot_faults"] == 0, nums


def test_mix_of_longer_chunks_is_refused():
    cell = tiny_cell("dpbalance")
    cell = dict(cell, traffic=dict(cell["traffic"], chunk_ticks=4))
    with pytest.raises(ValueError, match="one tick per chunk"):
        live.run(cell, SEED, 0.3, False, time.perf_counter())


def test_running_past_the_arrivals_is_an_error():
    from perfbench.harness.generator import Arrivals
    d = tiny_cell("dpbalance")["config"]["deployment"]
    trace = live.Trace(Arrivals(d, SEED), d, SEED)
    trace.extend(2)
    assert trace.step(0) is not None and trace.step(1) is not None
    with pytest.raises(RuntimeError, match="pre-generated"):
        trace.step(2)
