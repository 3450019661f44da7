"""The output check at a size a CPU holds: sound runs pass; the control
(the reference in bfloat16) and every fault planted under the timed path
make ``correct`` false.

A run here skips the harness's look for a chip and drives the rest of a
run: set-up, the window, the answers, the reference replay and the
verdict under the cell's own limits.
"""
import dataclasses
import json
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import check, control, live, runner, spec
from repro.service import FlaasService

ROOT = Path(__file__).resolve().parents[2]
SCHEDULERS = ("dpbalance", "dpf")
SEED = 3 * 2 ** 31 + 11


def tiny_cell(scheduler):
    """A live cell's mix and limits over a small deployment."""
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


@pytest.mark.parametrize("scheduler", SCHEDULERS)
def test_sound_run_is_correct(scheduler):
    out = measure(tiny_cell(scheduler))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("scheduler", SCHEDULERS)
@pytest.mark.parametrize("fault", [StateUnchanged, HalfBatch, AlteredAnswer],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(scheduler, fault):
    out = measure(tiny_cell(scheduler), service_cls=fault)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("scheduler", SCHEDULERS)
def test_control_in_bfloat16_fails_the_limits(scheduler):
    cell = tiny_cell(scheduler)
    d = cell["config"]["deployment"]
    ans, arrivals = control.control_answers(cell, SEED, 40, jnp.bfloat16)
    nums = check.replay(ans, d, cell["config"]["scheduler"], scheduler,
                        arrivals)
    ok, checks = check.verdict(nums, cell["limits"])
    assert not ok, checks


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
