"""Ahead-of-time compiles of the budget kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: blocks off the (8, 128) tiling, dynamic lane slices,
primitives Mosaic does not lower, or more VMEM than a kernel may hold.
These tests compile each kernel for one chip of a described ``v5e:2x2``
at the paper's §VI size (M = 6 analysts, N = 25 pipelines, K = 2000
blocks) and at the service default K = 4096, then the whole paper-size
dpbalance round with every kernel on.  The fleet shape (N = 1000 x
K = 100k) must be refused loudly by the kernels that keep ``g_ord``
resident.  Nothing runs: a compile that passes says nothing about results
or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import budget_alloc

PAPER = dict(M=6, N=25)
WIDTHS = (2000, 4096)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(kernel, sharding, M, N, K):
    """(callable, abstract arguments) for one budget kernel."""
    s = lambda *shape, dtype=jnp.float32: _spec(sharding, *shape,
                                               dtype=dtype)
    if kernel == "rowmax":
        return budget_alloc.rowmax, (s(M, K),)
    if kernel == "matvec":
        return budget_alloc.matvec, (s(M, K), s(K))
    if kernel == "matvec_t":
        return budget_alloc.matvec_t, (s(M, K), s(M))
    if kernel == "dual_step":
        return (lambda *a: budget_alloc.dual_step(*a, beta=2.2),
                (s(M, K), s(K), s(M), s(M), s(M, dtype=jnp.bool_), s(K),
                 s(K)))
    if kernel == "boost_scan":
        # batched over analysts, as pack_all runs it
        fn = jax.vmap(lambda g, sel, left: budget_alloc.boost_scan(
            g, sel, left, kappa_max=2.0))
        return fn, (s(M, N, K), s(M, N, dtype=jnp.bool_), s(M, K))
    if kernel == "swap_eval":
        C = N * N // 4                   # the compacted candidate cap
        return (lambda g, sel, left: budget_alloc.swap_eval(
            g, sel, left, kappa_max=2.0),
                (s(N, K), s(C, N, dtype=jnp.bool_), s(C, K)))
    raise KeyError(kernel)


@pytest.mark.parametrize("K", WIDTHS)
@pytest.mark.parametrize("kernel", ["rowmax", "matvec", "matvec_t",
                                    "dual_step", "boost_scan", "swap_eval"])
def test_kernel_compiles_for_v5e(one_chip, kernel, K):
    fn, args = _kernel_args(kernel, one_chip, K=K, **PAPER)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_wide_analyst_axis_compiles_for_v5e(one_chip):
    # M = 1000 analysts: row tiles of 256 with a ragged last tile
    for kernel in ("rowmax", "matvec", "matvec_t", "dual_step"):
        fn, args = _kernel_args(kernel, one_chip, M=1000, N=25, K=4096)
        assert "tpu_custom_call" in \
            jax.jit(fn).lower(*args).compile().as_text()


def test_paper_round_holds_every_kernel(one_chip, monkeypatch):
    from repro.core import SchedulerConfig, hotpath, schedule_round
    from repro.core.demand import RoundInputs

    # the dispatch reads the platform, which here is the CPU
    monkeypatch.setattr(hotpath, "_interpret", lambda: False)
    M, N, K = 6, 25, 2000
    s = lambda *shape, dtype=jnp.float32: _spec(one_chip, *shape,
                                               dtype=dtype)
    rnd = RoundInputs(demand=s(M, N, K), active=s(M, N, dtype=jnp.bool_),
                      arrival=s(M, N), loss=s(M, N), capacity=s(K),
                      budget_total=s(K), now=s())
    cfg = SchedulerConfig(beta=2.2, use_pallas=True, sp1_warm_start=True,
                          swap_beam=8)
    hlo = jax.jit(lambda r: schedule_round(r, cfg)).lower(rnd) \
        .compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') >= 5


@pytest.mark.parametrize("kernel", ["boost_scan", "swap_eval"])
def test_fleet_shape_is_refused(one_chip, kernel):
    from repro.core import hotpath
    N, K = 1000, 100_000
    s = lambda *shape, dtype=jnp.float32: _spec(one_chip, *shape,
                                               dtype=dtype)
    if kernel == "boost_scan":
        fn = lambda g, sel, left: hotpath.boost_scan(g, sel, left, 2.0,
                                                     use_pallas=True)
        args = (s(N, K), s(N, dtype=jnp.bool_), s(K))
    else:
        fn = lambda g, sel, left: hotpath.swap_eval(g, sel, left, 2.0,
                                                    use_pallas=True)
        args = (s(N, K), s(8, N, dtype=jnp.bool_), s(8, K))
    with pytest.raises(ValueError, match=f"{kernel}: g_ord .* VMEM"):
        jax.jit(fn).lower(*args)
