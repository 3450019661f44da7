"""Production training launcher.

On a real TPU slice this builds the production mesh, shards the state with
repro.distributed rules, and runs the DP-FedAvg train step with checkpoint /
restore; on this CPU container it runs the same code path on a 1x1 mesh with
a reduced config (--smoke) — the mesh/sharding logic is identical, only the
device list differs.  The 512-way lower/compile proof lives in dryrun.py.

    PYTHONPATH=src python -m repro.launch.train --arch flaas-100m --smoke \
        --steps 20 --ckpt /tmp/repro_train
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch, reduced
from repro.data.pipeline import synth_tokens
from repro.distributed.sharding import batch_pspecs, state_pspecs
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.training import DPConfig, TrainConfig, make_state, train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="flaas-100m")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--noise", type=float, default=0.2)
    ap.add_argument("--clip", type=float, default=1.0)
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    n_dev = len(jax.devices())
    mesh = make_production_mesh(multi_pod=args.multi_pod) if n_dev >= 256 \
        else make_host_mesh()
    print(f"arch={cfg.name} mesh={dict(mesh.shape)} devices={n_dev}")

    tcfg = TrainConfig(
        optimizer="adafactor" if cfg.name.startswith("kimi") else "adamw",
        param_dtype="float32" if n_dev == 1 else "bfloat16",
        dp=DPConfig(clip=args.clip, noise_multiplier=args.noise,
                    n_micro=2 if args.batch % 2 == 0 else 1))
    state = make_state(jax.random.PRNGKey(0), cfg, tcfg)
    mgr = CheckpointManager(args.ckpt, keep_n=3, async_save=True)
    restored, at = mgr.restore(jax.device_get(state))
    start = 0
    if restored is not None:
        state = jax.tree.map(jnp.asarray, restored)
        start = at
        print(f"resumed from step {at}")

    with jax.set_mesh(mesh):
        st_specs = state_pspecs(state, cfg, mesh)
        step = jax.jit(functools.partial(train_step, cfg=cfg, tcfg=tcfg),
                       in_shardings=(st_specs,
                                     batch_pspecs(
                                         synth_tokens(0, args.batch, args.seq,
                                                      cfg.vocab), mesh)),
                       out_shardings=(st_specs, P()),
                       donate_argnums=(0,))
        for i in range(start, start + args.steps):
            b = synth_tokens(i, args.batch, args.seq, cfg.vocab)
            b = {k: jnp.asarray(v) for k, v in b.items()}
            t0 = time.time()
            state, m = step(state, b)
            loss = float(m["loss"])
            print(f"step {i:5d}  loss={loss:.4f}  "
                  f"gnorm={float(m['grad_norm_mean']):.3f}  "
                  f"{time.time()-t0:.2f}s")
            if (i + 1) % args.ckpt_every == 0:
                mgr.save(i + 1, state)
    mgr.wait()
    print("final checkpoints:", mgr.all_steps())


if __name__ == "__main__":
    main()
