"""The chunk program's outputs leave the device as one packed buffer
(``repro.service.outputs``).

* packing then unpacking gives back every key, dtype, shape and bit,
  NaN payloads and -0.0 included, on the host and on the device, and a
  host-packed buffer is the device-packed one, read back on the device;
* the packed program's outputs equal the unpacked program's, bit for
  bit, in every mint mode, under every scheduler and with every plane
  that adds outputs (decision trace, audit, diagnostics, warm SP1, the
  swap beam), on the plain and the sharded service;
* a round copies the outputs to the host once, and ``packed_outputs``
  counts them; ``np.asarray`` of a view after the round copies nothing;
* a step wrapper that hands back a plain dict with a value of its own
  still runs, and that value is what the round works with.
"""
import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SCHEDULER_NAMES, SchedulerConfig
from repro.core.registry import get_round_fn
from repro.obs import PhaseProfiler
from repro.service import FlaasService, ServiceConfig, make_trace
from repro.service.outputs import (ChunkOutputs, OutputView, pack,
                                   pack_host, unpack)
from repro.service.server import _chunk_metrics

# 8 blocks a tick into a 56-slot ring: ticks 0-6 run the wrap-free
# program, later ticks the paged one (or carry, with paging off)
SIZE = dict(n_devices=4, pipelines_per_analyst=6)
RING, TICKS = 56, 10


def _config(scheduler="dpbalance", sched=None, **over):
    return ServiceConfig(scheduler=scheduler,
                         sched=sched or SchedulerConfig(beta=2.2),
                         analyst_slots=3, pipeline_slots=6,
                         block_slots=RING, chunk_ticks=1, admit_batch=8,
                         max_pending=64, **over)


def _trace(ticks=TICKS):
    return make_trace("paper_default", "poisson", seed=3,
                      **SIZE).precompute(ticks + 2).reset()


def assert_same_bits(got, want):
    """Same keys, dtypes, shapes and bytes."""
    want = {k: np.asarray(v) for k, v in want.items()}
    assert sorted(got) == sorted(want)
    for k, w in want.items():
        g = np.asarray(got[k])
        assert g.dtype == w.dtype and g.shape == w.shape, k
        assert g.tobytes() == w.tobytes(), k


def checked(base, plain_step):
    """``base`` (a service class) running, beside every packed chunk, the
    unpacked program ``plain_step(service, n_ticks, mode)`` on the same
    inputs, and comparing the two after the round's host copy."""

    class Checked(base):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.pairs = []
            self.modes = set()
            self.plain = {}

        def _compiled_step(self, n_ticks, mode):
            step = super()._compiled_step(n_ticks, mode)
            key = (n_ticks, mode)
            if key not in self.plain:
                self.plain[key] = plain_step(self, n_ticks, mode)
            plain = self.plain[key]

            def run(state, ops):
                final, ys = step(state, ops)
                self.pairs.append((ys, plain(state, ops)[1]))
                self.modes.add(mode)
                return final, ys
            return run

        def run_chunk(self, n_ticks=None):
            out = super().run_chunk(n_ticks)
            ys, want = self.pairs.pop()
            assert isinstance(ys, ChunkOutputs)
            assert_same_bits(ys.to_host(), want)
            return out

    return Checked


def plain_chunk(svc, n_ticks, mode):
    cfg = svc.cfg
    return jax.jit(functools.partial(
        _chunk_metrics, cfg=cfg.sched, round_fn=get_round_fn(cfg.scheduler),
        n_ticks=n_ticks, mode=mode, diagnostics=cfg.diagnostics,
        trace_level=cfg.trace_level, audit=cfg.audit_path is not None))


def plain_sharded_chunk(svc, n_ticks, mode):
    from repro.shard.service import _op_specs, _sharded_body
    from jax.sharding import NamedSharding
    cfg = svc.cfg
    step = jax.jit(_sharded_body(
        cfg.scheduler, cfg.sched, n_ticks, mode, cfg.diagnostics, svc.mesh,
        cfg.trace_level, cfg.audit_path is not None))
    shardings = [NamedSharding(svc.mesh, s)
                 for s in _op_specs(mode, cfg.sched.sp1_warm_start)]

    def run(state, ops):
        return step(state, tuple(jax.device_put(op, s)
                                 for op, s in zip(ops, shardings)))
    return run


def run_checked(base, plain_step, cfg, ticks=TICKS, **kw):
    svc = checked(base, plain_step)(cfg, _trace(ticks), **kw)
    svc.run(ticks)
    modes = svc.modes
    svc.close()
    return modes


# ============================================================ round trip
def _odd_values():
    f = np.array([np.nan, -0.0, 0.0, np.inf, -np.inf, 1e-45, -3.5,
                  np.float32(3.4e38)], np.float32)
    # a NaN with a payload of its own, which a conversion would drop
    f[0] = np.array(0x7FC01234, np.uint32).view(np.float32)
    return {
        "f32": f.reshape(2, 4),
        "i32": np.array([np.iinfo(np.int32).min, -1, 0, 7,
                         np.iinfo(np.int32).max], np.int32),
        "u32": np.array([0, 1, 0xFFFFFFFF], np.uint32),
        "mask": np.array([[True, False, True, True, False]] * 3),
        "one_bool": np.array(True),
        "i8": np.array([-128, -1, 0, 5, 127], np.int8),
        "bf16": jnp.asarray([np.nan, -0.0, 1.5], jnp.bfloat16),
        "scalar": np.float32(-0.0),
        "empty": np.zeros((0, 3), np.float32),
    }


@pytest.mark.parametrize("side", ["host", "device", "upload"])
def test_pack_round_trip_keeps_every_bit(side):
    ys = _odd_values()
    out = jax.jit(pack)(ys)
    assert isinstance(out, ChunkOutputs)
    assert out.buf.dtype == jnp.uint32 and out.buf.ndim == 1
    assert all(s.offset % 4 == 0 for s in out.layout)
    assert list(out) == sorted(ys) and len(out) == len(ys)
    if side == "host":
        got = out.to_host()
    elif side == "device":
        got = {k: jnp.asarray(out[k]) for k in out}
    else:       # the host lays out the same buffer; the device reads it
        buf = pack_host(out.layout, ys)
        assert buf.tobytes() == np.asarray(out.buf).tobytes()
        got = jax.jit(unpack, static_argnums=1)(buf, out.layout)
    assert_same_bits(got, ys)


def test_views_behave_as_arrays():
    ys = _odd_values()
    out = jax.jit(pack)(ys)
    view = out["mask"]
    assert isinstance(view, OutputView) and "mask" in out
    assert view.shape == (3, 5) and view.dtype == np.bool_
    # JAX code: indexing and .at slice the device buffer
    flipped = view.at[0, 1].set(~view[0, 1])
    assert isinstance(flipped, jax.Array)
    assert bool(flipped[0, 1]) and np.array_equal(
        np.asarray(flipped)[1:], ys["mask"][1:])
    # a plain dict built from the mapping keeps its views
    d = dict(out, mask=flipped)
    assert d["mask"] is flipped and isinstance(d["i32"], OutputView)
    # a tree map reaches the one buffer, and the views read through it
    on_host = jax.tree.map(np.asarray, out)
    assert isinstance(on_host.buf, np.ndarray)
    assert np.array_equal(np.asarray(on_host["i32"]), ys["i32"])


def test_packing_is_one_program_of_the_chunk():
    """The pack rides the chunk's own jit, in its ``round_metrics``
    scope, and its outputs are one buffer."""
    svc = FlaasService(_config(), _trace())
    svc.run(RING // 8 + 1)                          # past the wrap: paged
    _, mode, ops, step = svc._plan_chunk(int(svc.state.tick), 1)
    assert mode == "paged"
    _, ys = step(svc.state, ops)
    assert jax.tree.leaves(ys) == [ys.buf]
    hlo = step.lower(svc.state, ops).compile().as_text()
    assert "jit_flaas_chunk" in hlo and "round_metrics" in hlo
    svc.close()


# ====================================================== program parity
@pytest.mark.parametrize("paged", [True, False], ids=["paged", "carry"])
@pytest.mark.parametrize("scheduler", SCHEDULER_NAMES)
def test_packed_equals_unpacked_program(scheduler, paged):
    modes = run_checked(FlaasService, plain_chunk,
                        _config(scheduler, paged=paged))
    assert modes == {"wrapfree", "paged" if paged else "carry"}


PLANES = {
    "trace_level_2": dict(trace_level=2),
    "audit": dict(audit_path="audit"),
    "diagnostics": dict(diagnostics=True),
    "sp1_warm_start": dict(sched=SchedulerConfig(beta=2.2,
                                                 sp1_warm_start=True)),
    "swap_beam": dict(sched=SchedulerConfig(beta=2.2, swap_beam=2)),
}


@pytest.mark.parametrize("plane", sorted(PLANES))
def test_packed_equals_unpacked_with_plane(plane, tmp_path):
    over = dict(PLANES[plane])
    if "audit_path" in over:
        over["audit_path"] = str(tmp_path / "audit.jsonl")
    modes = run_checked(FlaasService, plain_chunk, _config(**over))
    assert modes == {"wrapfree", "paged"}


def test_sharded_one_shard_packed_equals_unpacked():
    from repro.shard import ShardedFlaasService
    modes = run_checked(ShardedFlaasService, plain_sharded_chunk,
                        _config(), n_shards=1)
    assert modes == {"wrapfree", "paged"}


def test_sharded_eight_shards_packed_equals_unpacked():
    """On the 8-device CPU mesh (a fresh process: this one keeps its
    one device), with and without the block-sharded diagnostics."""
    script = textwrap.dedent("""
        import sys
        sys.path.insert(0, sys.argv[1])
        import test_packed_outputs as t
        from repro.shard import ShardedFlaasService
        for sched in ("dpbalance", "dpf"):
            for paged in (True, False):
                modes = t.run_checked(ShardedFlaasService,
                                      t.plain_sharded_chunk,
                                      t._config(sched, paged=paged),
                                      n_shards=8)
                assert modes == {"wrapfree", "paged" if paged else "carry"}
        modes = t.run_checked(ShardedFlaasService, t.plain_sharded_chunk,
                              t._config(diagnostics=True, trace_level=2),
                              n_shards=8)
        assert modes == {"wrapfree", "paged"}, modes
        print("OK")
    """)
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(here, "..", "src"))
    r = subprocess.run([sys.executable, "-c", script, here],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().endswith("OK")


# ========================================================= the host copy
class Observed(FlaasService):
    """Keeps each step's outputs and what ``_recycle`` was handed."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.steps, self.synced = [], []

    def _compiled_step(self, n_ticks, mode):
        step = super()._compiled_step(n_ticks, mode)

        def run(state, ops):
            final, ys = self.wrap(state, *step(state, ops))
            self.steps.append(ys)
            return final, ys
        return run

    def wrap(self, state, final, ys):
        return final, ys

    def _recycle(self, ys, *a, **kw):
        self.synced.append(dict(ys))
        return super()._recycle(ys, *a, **kw)


def test_one_copy_per_round_counts_every_output():
    svc = Observed(_config(), _trace())
    svc.run(TICKS)
    rows = svc.profiler.rounds()
    n = np.array([len(ys) for ys in svc.steps])
    assert set(n) == {10, 13}             # wrap-free and paged rounds
    assert np.array_equal(rows["packed_outputs"], n)
    assert np.all(rows["d2h"] == 2)       # the tick read + the packed copy
    assert np.all(rows["d2h_bytes"] == 4 + 4 * np.array(
        [sum(s.words for s in ys.layout) for ys in svc.steps]))
    assert svc.profiler.packed_outputs == n.sum()
    for ys, host in zip(svc.steps, svc.synced):
        assert sorted(host) == sorted(ys)
    svc.close()


def test_view_after_the_round_copies_nothing():
    svc = Observed(_config(), _trace())
    svc.run(TICKS - 1)
    views = {k: svc.steps[-1][k] for k in ("selected", "expired")}
    ys = svc.steps[-1]
    d2h = list(svc.profiler.transfers["d2h"])
    host = ys.to_host()
    for k, v in views.items():
        a = np.asarray(v)
        assert np.shares_memory(a, host[k])
        assert np.shares_memory(a, svc.synced[-1][k])
    assert svc.profiler.transfers["d2h"] == d2h
    svc.close()


def test_plain_dict_from_a_wrapper_runs_with_its_own_value():
    class Altered(Observed):
        def wrap(self, state, final, ys):
            if int(state.tick) == 8:
                sel = ys["selected"]
                ys = dict(ys, selected=sel.at[0, 0, 0].set(~sel[0, 0, 0]))
                self.altered = ys["selected"]
            return final, ys

    svc = Altered(_config(), _trace())
    svc.run(TICKS)
    i = 8
    got = svc.synced[i]["selected"]
    assert isinstance(got, np.ndarray)
    assert np.array_equal(got, np.asarray(svc.altered))
    base = svc.steps[i]["expired"].outputs.to_host()
    assert got[0, 0, 0] != base["selected"][0, 0, 0]
    row = svc.profiler.rounds()[i]
    assert row["packed_outputs"] == 0
    # the tick read, the views' one shared buffer copy, the new value
    assert row["d2h"] == 3
    svc.close()


def test_profiler_state_without_packed_outputs_restores_as_zero():
    prof = PhaseProfiler()
    prof.packed(13)
    state = prof.state_dict()
    assert state["packed_outputs"] == 13
    older = {k: v for k, v in state.items() if k != "packed_outputs"}
    clone = PhaseProfiler()
    clone.load_state_dict(older)
    assert clone.packed_outputs == 0


def test_profiler_state_without_packed_inputs_restores_as_zero():
    prof = PhaseProfiler()
    prof.packed_in(9)
    state = prof.state_dict()
    assert state["packed_inputs"] == 9
    older = {k: v for k, v in state.items() if k != "packed_inputs"}
    clone = PhaseProfiler()
    clone.load_state_dict(older)
    assert clone.packed_inputs == 0 and clone.packed_outputs == 0


def test_state_graft_is_unchanged_by_packing():
    """The chunk's carries are untouched by the pack: the final state of
    the packed service equals the unpacked program's final carries."""
    svc = FlaasService(_config(), _trace())
    svc.run(RING // 8 + 1)
    _, mode, ops, step = svc._plan_chunk(int(svc.state.tick), 1)
    final, _ = step(svc.state, ops)
    want, _ = plain_chunk(svc, 1, mode)(svc.state, ops)
    for g, w in zip(jax.tree.leaves(final), jax.tree.leaves(want)):
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()
    svc.close()
