"""Training substrate: optimizers converge, DP grads behave, compression."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.training import (TrainConfig, DPConfig, adafactor, adamw,
                            clip_by_global_norm, compress_tree,
                            compressed_mean, decompress_tree, dp_gradients,
                            global_norm, quantize_int8, dequantize_int8, sgd)


def _quad_loss(params, batch):
    # simple convex problem: ||W x - y||^2
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2)


def _problem(key, n=64, d=8):
    k1, k2, k3 = jax.random.split(key, 3)
    w_true = jax.random.normal(k1, (d, 1))
    x = jax.random.normal(k2, (n, d))
    y = x @ w_true + 0.01 * jax.random.normal(k3, (n, 1))
    return {"x": x, "y": y}, {"w": jnp.zeros((d, 1))}


@pytest.mark.parametrize("make_opt,iters", [(lambda: adamw(lr=5e-2), 60),
                                            (lambda: adafactor(lr=1e-1), 300),
                                            (lambda: sgd(lr=5e-2), 60)])
def test_optimizers_converge(make_opt, iters):
    batch, params = _problem(jax.random.PRNGKey(0))
    opt = make_opt()
    st = opt.init(params)
    loss0 = float(_quad_loss(params, batch))
    upd = jax.jit(opt.update)
    for _ in range(iters):
        g = jax.grad(_quad_loss)(params, batch)
        params, st = upd(g, st, params)
    assert float(_quad_loss(params, batch)) < 0.1 * loss0


def test_mixed_precision_master():
    batch, params = _problem(jax.random.PRNGKey(1))
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    opt = adamw(lr=5e-2, keep_master=True)
    st = opt.init(params)
    assert st["master"]["w"].dtype == jnp.float32
    g = jax.grad(_quad_loss)(params, batch)
    params2, st2 = opt.update(g, st, params)
    assert params2["w"].dtype == jnp.bfloat16


class TestDpGradients:
    def test_modes_agree_without_clipping(self):
        batch, params = _problem(jax.random.PRNGKey(2), n=16)
        key = jax.random.PRNGKey(0)
        g_ex, _ = dp_gradients(_quad_loss, params, batch, key, clip=1e9,
                               noise_multiplier=0.0, mode="example")
        g_mb, _ = dp_gradients(_quad_loss, params, batch, key, clip=1e9,
                               noise_multiplier=0.0, mode="microbatch",
                               n_micro=4)
        g_ref = jax.grad(_quad_loss)(params, batch)
        # per-example mean-of-grads == grad-of-mean for mean losses
        np.testing.assert_allclose(np.asarray(g_ex["w"]),
                                   np.asarray(g_ref["w"]), rtol=1e-4)
        np.testing.assert_allclose(np.asarray(g_mb["w"]),
                                   np.asarray(g_ref["w"]), rtol=1e-4)

    def test_clipping_bounds_units(self):
        batch, params = _problem(jax.random.PRNGKey(3), n=16)
        clip = 0.05
        g, metrics = dp_gradients(_quad_loss, params, batch,
                                  jax.random.PRNGKey(0), clip=clip,
                                  noise_multiplier=0.0, mode="example")
        # mean of clipped unit grads has norm <= clip
        assert float(global_norm(g)) <= clip * (1 + 1e-5)
        assert float(metrics["clip_frac"]) > 0

    def test_noise_is_deterministic_in_key(self):
        batch, params = _problem(jax.random.PRNGKey(4), n=8)
        k = jax.random.PRNGKey(5)
        g1, _ = dp_gradients(_quad_loss, params, batch, k, clip=1.0,
                             noise_multiplier=1.0, mode="microbatch",
                             n_micro=2)
        g2, _ = dp_gradients(_quad_loss, params, batch, k, clip=1.0,
                             noise_multiplier=1.0, mode="microbatch",
                             n_micro=2)
        np.testing.assert_array_equal(np.asarray(g1["w"]), np.asarray(g2["w"]))

    def test_noise_changes_grads(self):
        batch, params = _problem(jax.random.PRNGKey(4), n=8)
        g0, _ = dp_gradients(_quad_loss, params, batch, jax.random.PRNGKey(5),
                             clip=1.0, noise_multiplier=0.0,
                             mode="microbatch", n_micro=2)
        g1, _ = dp_gradients(_quad_loss, params, batch, jax.random.PRNGKey(5),
                             clip=1.0, noise_multiplier=1.0,
                             mode="microbatch", n_micro=2)
        assert float(jnp.max(jnp.abs(g0["w"] - g1["w"]))) > 0


class TestCompression:
    def test_int8_roundtrip_error_bounded(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (256,)) * 5
        q, s = quantize_int8(x)
        err = jnp.max(jnp.abs(dequantize_int8(q, s) - x))
        assert float(err) <= float(s) * 0.5 + 1e-6

    def test_error_feedback_reduces_bias(self):
        """EF-compressed SGD converges on the quadratic (bias vanishes)."""
        batch, params = _problem(jax.random.PRNGKey(6))
        residual = None
        lr = 5e-2
        for _ in range(80):
            g = jax.grad(_quad_loss)(params, batch)
            (q, s), residual = compress_tree(g, residual)
            g_hat = decompress_tree(q, s)
            params = jax.tree.map(lambda p, gg: p - lr * gg, params, g_hat)
        assert float(_quad_loss(params, batch)) < 0.05

    def test_compressed_mean_close_to_mean(self):
        trees = [{"w": jax.random.normal(jax.random.PRNGKey(i), (64,))}
                 for i in range(4)]
        cm = compressed_mean(trees)
        true = jax.tree.map(lambda *xs: sum(xs) / 4.0, *trees)
        np.testing.assert_allclose(np.asarray(cm["w"]),
                                   np.asarray(true["w"]), atol=0.05)


def test_compressed_psum_shard_map():
    """int8 all-reduce under shard_map on a 1-device mesh (semantics check;
    multi-device path exercised in test_distributed.py subprocess)."""
    from repro.training import compressed_psum
    from repro.distributed.compat import make_mesh
    mesh = make_mesh((1,), ("pod",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 8)) * 3
    f = jax.shard_map(lambda t: compressed_psum(t, "pod"), mesh=mesh,
                      in_specs=jax.sharding.PartitionSpec(),
                      out_specs=jax.sharding.PartitionSpec())
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=0.1)
