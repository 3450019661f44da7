"""Multi-device distribution tests (subprocess with fake devices — the main
test process must keep the default 1-device backend)."""
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(script: str, devices: int = 8, timeout=560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


@pytest.mark.slow
def test_sharded_train_step_numerics_match_single_device():
    """The pjit train step on a 4x2 mesh must produce the same loss as the
    single-device run (same seeds, same batch)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.configs import get_arch, reduced
        from repro.training import TrainConfig, DPConfig, make_state, train_step
        from repro.distributed.sharding import state_pspecs, batch_pspecs

        r = reduced(get_arch("flaas-100m"))
        tcfg = TrainConfig(optimizer="adamw", lr=1e-3, param_dtype="float32",
                           dp=DPConfig(clip=1.0, noise_multiplier=0.0, n_micro=2))
        state = make_state(jax.random.PRNGKey(0), r, tcfg)
        rng = np.random.default_rng(0)
        t = rng.integers(0, r.vocab, (8, 17))
        batch = {"tokens": jnp.asarray(t[:, :-1]), "labels": jnp.asarray(t[:, 1:])}

        step = jax.jit(functools.partial(train_step, cfg=r, tcfg=tcfg))
        _, m1 = step(state, batch)

        from repro.distributed.compat import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        named = lambda tree: jax.tree.map(
            lambda s: NamedSharding(mesh, s), tree,
            is_leaf=lambda s: isinstance(s, P))
        with jax.set_mesh(mesh):
            st_specs = named(state_pspecs(state, r, mesh))
            b_specs = named(batch_pspecs(batch, mesh))
            stepd = jax.jit(functools.partial(train_step, cfg=r, tcfg=tcfg),
                            in_shardings=(st_specs, b_specs),
                            out_shardings=(st_specs, named(P())))
            _, m2 = stepd(state, batch)
        d = abs(float(m1["loss"]) - float(m2["loss"]))
        assert d < 1e-3, d
        print("OK", float(m1["loss"]), float(m2["loss"]))
    """)
    assert "OK" in out


@pytest.mark.slow
def test_compressed_psum_multidevice():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.training import compressed_psum
        from repro.distributed.compat import make_mesh
        mesh = make_mesh((4,), ("pod",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 16)) * 2
        f = jax.jit(jax.shard_map(lambda t: compressed_psum(t, "pod"),
                              mesh=mesh, in_specs=P("pod"),
                              out_specs=P("pod")))
        out = f(x)   # psum of per-shard slices, broadcast back
        # each shard's output = sum over shards of its own slice? No:
        # psum over pod of [2,16] shards -> every shard holds the sum.
        local = x.reshape(4, 2, 16).sum(0)
        got = np.asarray(out).reshape(4, 2, 16)
        for i in range(4):
            np.testing.assert_allclose(got[i], local, atol=0.15)
        print("OK")
    """, devices=4)
    assert "OK" in out


@pytest.mark.slow
def test_production_mesh_shapes():
    out = _run("""
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        assert dict(m1.shape) == {"data": 16, "model": 16}
        m2 = make_production_mesh(multi_pod=True)
        assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
        print("OK")
    """, devices=512)
    assert "OK" in out


@pytest.mark.slow
def test_pipeline_parallel_matches_sequential():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline_parallel import pipeline_apply
        from repro.distributed.compat import make_mesh
        mesh = make_mesh((4,), ("pod",))
        n_stages, n_micro, d = 4, 8, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        w = jax.random.normal(ks[0], (n_stages, d, d)) * 0.3
        x = jax.random.normal(ks[1], (n_micro, 4, d))

        def stage_fn(wi, h):
            return jnp.tanh(h @ wi)

        got = pipeline_apply(stage_fn, w, x, mesh, axis="pod")
        want = x
        for s in range(n_stages):
            want = jax.vmap(lambda h: stage_fn(w[s], h))(want)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
    """, devices=4)
    assert "OK" in out
