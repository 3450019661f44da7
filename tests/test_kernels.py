"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import budget_alloc, ops, ref

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,H,KH,S,dh", [
    (1, 2, 1, 64, 32), (2, 4, 2, 128, 64), (1, 8, 8, 64, 16),
    (2, 6, 2, 96, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 32)])
def test_flash_attention(B, H, KH, S, dh, dtype, causal, window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, dh), dtype)
    k = jax.random.normal(ks[1], (B, KH, S, dh), dtype)
    v = jax.random.normal(ks[2], (B, KH, S, dh), dtype)
    out = ops.flash_attention_op(q, k, v, causal=causal, window=window,
                                 block_q=32, block_k=32)
    expect = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,H,KH,L,dh", [
    (1, 2, 1, 128, 32), (2, 4, 2, 256, 64), (2, 8, 8, 64, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("frac", [0.25, 1.0])
def test_decode_attention(B, H, KH, L, dh, dtype, frac):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, dh), dtype)
    k = jax.random.normal(ks[1], (B, KH, L, dh), dtype)
    v = jax.random.normal(ks[2], (B, KH, L, dh), dtype)
    clen = max(1, int(L * frac))
    out = ops.decode_attention_op(q, k, v, jnp.asarray(clen), block_k=32)
    expect = ref.decode_attention_ref(q, k, v, clen)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,D,bs,bd", [
    (1, 32, 16, 8, 16), (2, 64, 32, 16, 16), (2, 128, 64, 32, 32),
])
@pytest.mark.parametrize("with_h0", [False, True])
def test_rglru_scan(B, S, D, bs, bd, with_h0):
    ks = jax.random.split(KEY, 3)
    a = jax.random.uniform(ks[0], (B, S, D), jnp.float32, 0.5, 0.999)
    b = jax.random.normal(ks[1], (B, S, D), jnp.float32)
    h0 = jax.random.normal(ks[2], (B, D), jnp.float32) if with_h0 else None
    out = ops.rglru_scan_op(a, b, h0, block_s=bs, block_d=bd)
    expect = ref.rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,P,bp", [(4, 256, 64), (8, 1024, 256),
                                    (16, 512, 512)])
@pytest.mark.parametrize("clip", [0.5, 3.0])
def test_dp_clip_accumulate(B, P, bp, clip):
    g = jax.random.normal(KEY, (B, P), jnp.float32) * 2.0
    out, norms = ops.dp_clip_accumulate_op(g, clip, block_p=bp)
    true_norms = jnp.sqrt(ref.rownorms_ref(g))
    scales = jnp.minimum(1.0, clip / true_norms)
    expect = ref.clip_accumulate_ref(g, scales)
    np.testing.assert_allclose(np.asarray(norms), np.asarray(true_norms),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-5)
    # clipped rows really are clipped
    clipped_norm = float(jnp.linalg.norm(g[0] * scales[0]))
    assert clipped_norm <= clip * (1 + 1e-5)


@pytest.mark.parametrize("M,K,bm,bk", [(64, 256, 32, 64), (256, 1024, 256, 256)])
def test_budget_kernels(M, K, bm, bk):
    ks = jax.random.split(KEY, 2)
    gamma = jax.random.uniform(ks[0], (M, K), jnp.float32)
    lam = jax.random.uniform(ks[1], (K,), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.rowmax_op(gamma, block_m=bm, block_k=bk)),
        np.asarray(ref.rowmax_ref(gamma)), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ops.matvec_op(gamma, lam, block_m=bm, block_k=bk)),
        np.asarray(ref.matvec_ref(gamma, lam)), rtol=1e-4)


class TestHotpathDispatch:
    """The scheduler's hot-path dispatch (core.hotpath) must match the
    kernels.ref oracles on arbitrary, non-tiling shapes — this is the
    interpret-mode parity gate for wiring the budget kernels into
    AnalystView / the waterfill sweeps behind ``use_pallas``."""

    @pytest.mark.parametrize("M,K", [(6, 2000), (5, 123), (64, 1024)])
    def test_rowmax_matches_ref(self, M, K):
        from repro.core import hotpath
        g = jax.random.uniform(KEY, (M, K), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(hotpath.rowmax(g, use_pallas=True)),
            np.asarray(ref.rowmax_ref(g)), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(hotpath.rowmax(g, use_pallas=False)),
            np.asarray(ref.rowmax_ref(g)), rtol=1e-6)

    @pytest.mark.parametrize("M,K", [(6, 2000), (5, 123)])
    def test_matvec_forms_match_ref(self, M, K):
        from repro.core import hotpath
        ks = jax.random.split(KEY, 3)
        c = jax.random.uniform(ks[0], (M, K), jnp.float32)
        lam = jax.random.uniform(ks[1], (K,), jnp.float32)
        x = jax.random.uniform(ks[2], (M,), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(hotpath.matvec(c, lam, use_pallas=True)),
            np.asarray(ref.matvec_ref(c, lam)), rtol=1e-4)
        np.testing.assert_allclose(
            np.asarray(hotpath.matvec_t(c, x, use_pallas=True)),
            np.asarray(ref.matvec_ref(c.T, x)), rtol=1e-4)

    @pytest.mark.parametrize("M,K", [(13, 300), (6, 2000), (17, 129)])
    def test_ragged_tiles_match_ref(self, M, K):
        # chip-legal (8, 128) tiles that divide neither dimension: the
        # overhang of the last tiles is masked (-inf for max, 0 for sums)
        ks = jax.random.split(KEY, 3)
        c = jax.random.uniform(ks[0], (M, K), jnp.float32) - 0.5
        v = jax.random.uniform(ks[1], (K,), jnp.float32)
        x = jax.random.uniform(ks[2], (M,), jnp.float32)
        kw = dict(block_m=8, block_k=128, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(budget_alloc.rowmax(c, **kw)),
            np.asarray(ref.rowmax_ref(c)))
        np.testing.assert_allclose(
            np.asarray(budget_alloc.matvec(c, v, **kw)),
            np.asarray(ref.matvec_ref(c, v)), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(budget_alloc.matvec_t(c, x, **kw)),
            np.asarray(ref.matvec_ref(c.T, x)), rtol=1e-4, atol=1e-5)


class TestBoostScanKernel:
    """SP2's fused boost sweep (one VMEM-resident divide/min/update pass
    over K per visited pipeline) must be BITWISE-identical to the jnp
    ``lax.scan`` reference — it replaces the scan inside
    ``proportional_boost``, whose outputs feed argmax tie resolution in
    the swap engine, so allclose is not enough."""

    def _instance(self, key, N, K, kappa=2.0):
        ks = jax.random.split(key, 3)
        g = jax.random.uniform(ks[0], (N, K), jnp.float32) * \
            (jax.random.uniform(ks[1], (N, K)) > 0.5)
        sel = jax.random.uniform(ks[2], (N,)) > 0.4
        left = jax.random.uniform(ks[0], (K,), jnp.float32) * 2.0
        return g, sel, left

    @pytest.mark.parametrize("N,K", [(4, 16), (7, 33), (25, 200), (1, 1)])
    @pytest.mark.parametrize("kappa", [1.0, 2.0, 8.0])
    def test_bitwise_vs_ref(self, N, K, kappa):
        g, sel, left = self._instance(KEY, N, K)
        extras, lout = ops.boost_scan_op(g, sel, left, kappa_max=kappa)
        e_ref, l_ref = ref.boost_scan_ref(g, sel, left, kappa)
        np.testing.assert_array_equal(np.asarray(extras), np.asarray(e_ref))
        np.testing.assert_array_equal(np.asarray(lout), np.asarray(l_ref))

    def test_degenerate_rows(self):
        # zero-demand rows (infinite water level -> kappa cap) and
        # nothing-selected both take the documented closed forms
        g = jnp.zeros((3, 8), jnp.float32).at[1].set(0.5)
        sel = jnp.asarray([True, True, False])
        left = jnp.ones((8,), jnp.float32)
        extras, lout = ops.boost_scan_op(g, sel, left, kappa_max=2.0)
        e_ref, l_ref = ref.boost_scan_ref(g, sel, left, 2.0)
        np.testing.assert_array_equal(np.asarray(extras), np.asarray(e_ref))
        np.testing.assert_array_equal(np.asarray(lout), np.asarray(l_ref))
        extras0, _ = ops.boost_scan_op(g, jnp.zeros(3, bool), left,
                                       kappa_max=2.0)
        assert (np.asarray(extras0) == 0).all()

    def test_vmapped_over_analysts_and_candidates(self):
        # pack_all vmaps the sweep over analysts; the swap engine adds a
        # second candidate axis — both must batch through pallas_call
        ks = jax.random.split(KEY, 3)
        g = jax.random.uniform(ks[0], (3, 4, 6, 32), jnp.float32)
        sel = jax.random.uniform(ks[1], (3, 4, 6)) > 0.4
        left = jax.random.uniform(ks[2], (3, 4, 32), jnp.float32)
        fn = lambda g_, s_, l_: ops.boost_scan_op(g_, s_, l_, kappa_max=2.0)
        e, l = jax.vmap(jax.vmap(fn))(g, sel, left)
        er, lr = jax.vmap(jax.vmap(
            lambda g_, s_, l_: ref.boost_scan_ref(g_, s_, l_, 2.0)))(
                g, sel, left)
        np.testing.assert_array_equal(np.asarray(e), np.asarray(er))
        np.testing.assert_array_equal(np.asarray(l), np.asarray(lr))

    def test_hotpath_dispatch_matches_jnp_path(self):
        # the two sides of the SchedulerConfig(use_pallas) switch
        from repro.core import hotpath
        g, sel, left = self._instance(KEY, 9, 41)
        l_jnp, e_jnp = hotpath.boost_scan(g, sel, left, 2.0,
                                          use_pallas=False)
        l_pal, e_pal = hotpath.boost_scan(g, sel, left, 2.0,
                                          use_pallas=True)
        np.testing.assert_array_equal(np.asarray(e_jnp), np.asarray(e_pal))
        np.testing.assert_array_equal(np.asarray(l_jnp), np.asarray(l_pal))

    def test_full_round_parity_with_pallas_boost(self):
        # a whole dpbalance round with use_pallas on: selections match the
        # jnp path exactly (the boost kernel is bitwise; SP1's matvec
        # kernel reassociates sums, so continuous outputs are allclose)
        import dataclasses as dc

        from repro.core import SchedulerConfig, schedule_round
        from repro.core.demand import RoundInputs
        rng = np.random.default_rng(5)
        M, N, K = 2, 5, 12
        rnd = RoundInputs(
            demand=jnp.asarray(rng.uniform(0, 0.2, (M, N, K)) *
                               (rng.random((M, N, K)) > 0.5), jnp.float32),
            active=jnp.ones((M, N), bool),
            arrival=jnp.zeros((M, N), jnp.float32),
            loss=jnp.asarray(rng.uniform(0.5, 1, (M, N)), jnp.float32),
            capacity=jnp.asarray(rng.uniform(0.5, 1.5, K), jnp.float32),
            budget_total=jnp.ones((K,), jnp.float32),
            now=jnp.asarray(0.0, jnp.float32))
        cfg = SchedulerConfig(beta=2.2)
        a = schedule_round(rnd, cfg)
        b = schedule_round(rnd, dc.replace(cfg, use_pallas=True))
        np.testing.assert_array_equal(np.asarray(a.selected),
                                      np.asarray(b.selected))
        np.testing.assert_allclose(np.asarray(a.x_pipeline),
                                   np.asarray(b.x_pipeline),
                                   rtol=1e-5, atol=1e-6)


class TestDualStepKernel:
    """SP1's fused dual-ascent sweep (x(lambda), the block loads, and the
    per-block residual in one [M, K]-tiled pass) must be BITWISE-identical
    to the jnp reference at every tile shape — the residual drives the
    while_loop exit test, so a last-ulp difference would change iteration
    counts and break warm-off parity.

    The reference is compared UNDER JIT: the kernel's row-reduce matches
    the XLA-compiled reduction order, while eager op-by-op dispatch can
    associate the same sum differently in the last ulp."""

    REF = staticmethod(jax.jit(ref.dual_step_ref, static_argnums=(7,)))

    def _instance(self, key, M, K, beta=2.2):
        ks = jax.random.split(key, 5)
        c = jax.random.uniform(ks[0], (M, K), jnp.float32) * \
            (jax.random.uniform(ks[1], (M, K)) > 0.3)
        lam = jnp.exp(jax.random.normal(ks[2], (K,)) * 3.0)
        w_pow = jax.random.uniform(ks[3], (M,), jnp.float32) ** (1.0 - beta)
        xcap = jax.random.uniform(ks[4], (M,), jnp.float32) * 10.0
        mask = jax.random.uniform(ks[0], (M,)) > 0.2
        cap = jax.random.uniform(ks[1], (K,), jnp.float32) + 0.1
        cap_safe = jnp.maximum(cap, 1e-12)
        return c, lam, w_pow, xcap, mask, cap, cap_safe

    @pytest.mark.parametrize("M,K,bm", [
        (5, 123, 4),        # non-divisor tile: padded tail rows
        (7, 33, 3),
        (8, 64, 8),         # exact tiling
        (64, 256, 256),     # single tile covering all rows
        (1, 1, 1),          # degenerate
        (6, 2000, 256),
    ])
    def test_bitwise_vs_ref(self, M, K, bm):
        args = self._instance(KEY, M, K)
        x, g = ops.dual_step_op(*args, beta=2.2, block_m=bm)
        x_ref, g_ref = self.REF(*args, 2.2)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(x_ref))
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g_ref))

    def test_bitwise_across_tile_shapes(self):
        # the same instance through every tile shape: one canonical answer
        args = self._instance(jax.random.PRNGKey(11), 13, 77)
        outs = [ops.dual_step_op(*args, beta=2.2, block_m=bm)
                for bm in (1, 2, 4, 5, 13, 64)]
        for x, g in outs[1:]:
            np.testing.assert_array_equal(np.asarray(x),
                                          np.asarray(outs[0][0]))
            np.testing.assert_array_equal(np.asarray(g),
                                          np.asarray(outs[0][1]))

    def test_vmapped(self):
        # the waterfill runs under the engine's scan/vmap machinery; the
        # kernel must batch through pallas_call bitwise
        ks = jax.random.split(KEY, 4)
        B, M, K = 3, 6, 40
        c = jax.random.uniform(ks[0], (B, M, K), jnp.float32)
        lam = jnp.exp(jax.random.normal(ks[1], (B, K)))
        w_pow = jax.random.uniform(ks[2], (B, M), jnp.float32)
        xcap = jax.random.uniform(ks[3], (B, M), jnp.float32) * 5.0
        mask = jnp.ones((B, M), bool)
        cap = jax.random.uniform(ks[0], (B, K), jnp.float32) + 0.1
        cs = jnp.maximum(cap, 1e-12)
        fn = lambda *a: ops.dual_step_op(*a, beta=2.2, block_m=4)
        rfn = jax.jit(jax.vmap(lambda *a: ref.dual_step_ref(*a, 2.2)))
        x, g = jax.vmap(fn)(c, lam, w_pow, xcap, mask, cap, cs)
        x_ref, g_ref = rfn(c, lam, w_pow, xcap, mask, cap, cs)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(x_ref))
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g_ref))

    def test_masked_rows_are_inert(self):
        # a masked-out analyst contributes exactly zero to every load
        args = list(self._instance(KEY, 9, 31))
        args[4] = jnp.zeros((9,), bool)
        x, g = ops.dual_step_op(*args, beta=2.2, block_m=4)
        assert (np.asarray(x) == 0.0).all()
        x_ref, g_ref = self.REF(*args, 2.2)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g_ref))

    def test_hotpath_dispatch(self):
        # hotpath.dual_step with use_pallas routes through the fused
        # kernel unsharded, and matches the two-matvec fallback to rtol
        from repro.core import hotpath
        c, lam, w_pow, xcap, mask, cap, cap_safe = self._instance(KEY, 5, 123)
        xp, gp = jax.jit(
            lambda c_, l_, w_, *a: hotpath.dual_step(c_, l_, w_, 2.2, *a,
                                                     use_pallas=True))(
            c, lam, w_pow, xcap, mask, cap, cap_safe)
        xj, gj = jax.jit(
            lambda c_, l_, w_, *a: hotpath.dual_step(c_, l_, w_, 2.2, *a,
                                                     use_pallas=False))(
            c, lam, w_pow, xcap, mask, cap, cap_safe)
        np.testing.assert_allclose(np.asarray(xp), np.asarray(xj),
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gj),
                                   rtol=1e-4, atol=1e-6)
