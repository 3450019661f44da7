"""An admission batch crosses to the device as one packed buffer
(``repro.service.state.admit_batch``).

* the packed write leaves every ``ServiceState`` leaf bit-identical to
  the same write computed in numpy: scalar and Rényi-DP ledgers, a given
  or kept weight vector, every padded COO size, f64 and f32 tables, NaN
  payloads, -0.0 and denormals;
* one padded size is one program, whatever the callers' dtypes and
  whether they pass ``weight``;
* operands of the wrong shape, and curves for a scalar ledger, are
  refused on the host, and a buffer of a length no layout has by the
  admission program;
* a tiered trace, whose admissions change the weight vector, admits the
  same on one and on four shards as on the plain service.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SchedulerConfig
from repro.service import FlaasService, ServiceConfig, make_trace
from repro.service import state as state_mod
from repro.service.state import ServiceState, admit_batch

M, N, B, A = 3, 5, 16, 4
NAN_PAYLOAD = np.array(0x7FC01234, np.uint32).view(np.float32)


def odd(rng, shape, dtype):
    """Random values of ``dtype`` salted with NaN payloads, -0.0 and
    denormals (whatever of them ``dtype`` holds after a cast to f32)."""
    x = rng.uniform(-2.0, 2.0, shape).astype(dtype)
    flat = x.reshape(-1)
    salt = np.array([NAN_PAYLOAD, -0.0, 1e-45, -3e-39], np.float32)
    if dtype == np.float64:
        salt = salt.astype(np.float64)
        salt[0] = np.array(0x7FF8000012345678, np.uint64).view(np.float64)
    k = min(flat.size, salt.size)
    flat[rng.permutation(flat.size)[:k]] = salt[:k]
    return x


def start_state(rng, orders):
    s = ServiceState.create(M, N, B, orders=orders)
    f = lambda *shape: jnp.asarray(odd(rng, shape, np.float32))
    return dataclasses.replace(
        s, demand=f(M, N, B), loss=f(M, N), arrival=f(M, N),
        spawn_tick=jnp.asarray(rng.integers(0, 99, (M, N)), jnp.int32),
        done=jnp.asarray(rng.random((M, N)) < 0.5), weight=f(M),
        block_capacity=f(*s.block_capacity.shape),
        curve=f(M, N, orders) if orders else None)


def batch(rng, n, dtype, orders):
    """One admission's operands, the COO triples distinct and in range."""
    mask = rng.random((M, N)) < 0.5
    flat = rng.choice(M * N * B, n, replace=False)
    rows, cols, bids = np.unravel_index(flat, (M, N, B))
    ops = dict(mask=mask, loss=odd(rng, (M, N), dtype),
               arrival_seconds=odd(rng, (M, N), dtype),
               spawn_ticks=rng.integers(0, 99, (M, N)),
               rows=rows.astype(np.int64), cols=cols.astype(np.int64),
               bids=bids.astype(np.int64), eps=odd(rng, (n,), dtype))
    if orders:
        ops["curve"] = odd(rng, (M, N, orders), dtype)
    return ops


def numpy_admit(state, ops, weight):
    """The admission write in numpy, from the same host operands."""
    s = {f.name: None if getattr(state, f.name) is None
         else np.asarray(getattr(state, f.name)).copy()
         for f in dataclasses.fields(state)}
    mask = ops["mask"]
    s["demand"][mask] = 0.0
    s["demand"][ops["rows"], ops["cols"], ops["bids"]] = \
        ops["eps"].astype(np.float32)
    for key, arg in (("loss", "loss"), ("arrival", "arrival_seconds")):
        s[key] = np.where(mask, ops[arg].astype(np.float32), s[key])
    s["spawn_tick"] = np.where(mask, ops["spawn_ticks"].astype(np.int32),
                               s["spawn_tick"])
    s["done"] = s["done"] & ~mask
    if weight is not None:
        s["weight"] = weight.astype(np.float32)
    if "curve" in ops:
        s["curve"] = np.where(mask[..., None],
                              ops["curve"].astype(np.float32), s["curve"])
    return s


@pytest.mark.parametrize("dtype", [np.float64, np.float32],
                         ids=["f64", "f32"])
@pytest.mark.parametrize("n", [0, 1, 3, 4, 17])
@pytest.mark.parametrize("given_weight", [False, True],
                         ids=["kept_weight", "given_weight"])
@pytest.mark.parametrize("orders", [0, A], ids=["scalar", "rdp"])
def test_packed_write_is_bit_identical_to_numpy(orders, given_weight, n,
                                                dtype):
    rng = np.random.default_rng([orders, given_weight, n,
                                 np.dtype(dtype).itemsize])
    state = start_state(rng, orders)
    ops = batch(rng, n, dtype, orders)
    weight = odd(rng, (M,), dtype) if given_weight else None
    want = numpy_admit(state, ops, weight)
    got = admit_batch(state, **ops, weight=weight)
    for f in dataclasses.fields(got):
        g, w = getattr(got, f.name), want[f.name]
        if w is None:
            assert g is None, f.name
            continue
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape, f.name
        assert g.tobytes() == w.tobytes(), f.name


def test_one_program_per_padded_size():
    """The harness warms each padded size with f64 tables and no
    ``weight``; the server sends f32 tables and its weight vector.  Both
    land on one compiled program, so no compile reaches a timed window."""
    rng = np.random.default_rng(7)
    state = start_state(rng, 0)
    before = state_mod._admit_apply._cache_size()
    for dtype in (np.float64, np.float32):
        for weight in (None, np.ones(M, np.float32)):
            ops = batch(rng, 5, dtype, 0)
            jax.block_until_ready(admit_batch(state, **ops, weight=weight))
    assert state_mod._admit_apply._cache_size() == before + 1
    ops = batch(rng, 9, np.float32, 0)        # a new padded size: 16
    admit_batch(state, **ops)
    assert state_mod._admit_apply._cache_size() == before + 2


def test_malformed_operands_are_refused():
    rng = np.random.default_rng(3)
    state = start_state(rng, 0)
    ops = batch(rng, 2, np.float32, 0)
    with pytest.raises(ValueError, match="mask"):
        admit_batch(state, **dict(ops, mask=ops["mask"][:, :-1]))
    with pytest.raises(ValueError, match="scalar ledger"):
        admit_batch(state, **ops, curve=np.zeros((M, N, A), np.float32))
    words = state_mod.layout_words(state_mod.admission_layout(M, N, 0, 2))
    with pytest.raises(ValueError, match="fits no COO size"):
        state_mod._admit_apply(state, jnp.zeros(words + 1, jnp.uint32))


def tiered_matches_plain(n_shards):
    """A tiered trace changes the weight vector as analysts are admitted;
    the sharded service, which re-commits each admitted state to its
    layout, ends every chunk with the plain service's state (on several
    shards, each slot's blocks in stripe order)."""
    from repro.shard import ShardedFlaasService

    def trace():
        return make_trace("paper_default", "poisson", seed=4, n_devices=4,
                          pipelines_per_analyst=6,
                          tiers="free_pro_enterprise")

    cfg = ServiceConfig(scheduler="dpbalance",
                        sched=SchedulerConfig(beta=2.2), analyst_slots=3,
                        pipeline_slots=6, block_slots=80, chunk_ticks=4,
                        admit_batch=8, max_pending=64)
    plain = FlaasService(cfg, trace())
    sharded = ShardedFlaasService(cfg, trace(), n_shards=n_shards)
    weights = set()
    for _ in range(4):
        plain.run_chunk(4)
        sharded.run_chunk(4)
        for f in dataclasses.fields(plain.state):
            a = getattr(plain.state, f.name)
            b = getattr(sharded.state, f.name)
            assert (a is None) == (b is None), f.name
            if a is None:
                continue
            a, b = np.asarray(a), np.asarray(b)
            if n_shards > 1 and a.shape[-1:] == (cfg.block_slots,):
                # the striped ring orders the block axis by shard
                a, b = np.sort(a, axis=-1), np.sort(b, axis=-1)
            np.testing.assert_array_equal(a, b, err_msg=f.name)
        weights.add(np.asarray(plain.state.weight).tobytes())
    assert len(weights) > 1                 # the weights did change
    plain.close()
    sharded.close()


@pytest.mark.parametrize("n_shards", [1, 4])
def test_sharded_tiered_admission_matches_plain(n_shards):
    """One shard here; four on the CPU mesh of a fresh process (this one
    keeps its one device)."""
    if n_shards <= len(jax.devices()):
        tiered_matches_plain(n_shards)
        return
    here = os.path.dirname(os.path.abspath(__file__))
    script = ("import sys; sys.path.insert(0, sys.argv[1]); "
              "import test_admit_pack as t; "
              f"t.tiered_matches_plain({n_shards}); print('OK')")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(here, "..", "src"))
    r = subprocess.run([sys.executable, "-c", script, here],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().endswith("OK")
