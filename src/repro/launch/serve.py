"""Production serving launcher: batched prefill + decode over the mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch flaas-100m --smoke \
        --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch, reduced
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import forward_with_cache, init_model
from repro.training import serve_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="flaas-100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    n_dev = len(jax.devices())
    mesh = make_production_mesh(multi_pod=args.multi_pod) if n_dev >= 256 \
        else make_host_mesh()
    print(f"arch={cfg.name} mesh={dict(mesh.shape)}")

    key = jax.random.PRNGKey(0)
    dtype = jnp.float32 if n_dev == 1 else jnp.bfloat16
    params = init_model(key, cfg, dtype=dtype)
    B, Pl = args.batch, args.prompt_len
    total = Pl + args.gen
    prompts = jax.random.randint(key, (B, Pl), 0, cfg.vocab)
    kwargs = {}
    if cfg.encoder is not None:
        kwargs["enc_frames"] = jnp.zeros((B, cfg.cross_memory_len,
                                          cfg.d_model), dtype)
    elif cfg.cross_memory_len:
        kwargs["memory"] = jnp.zeros((B, cfg.cross_memory_len, cfg.d_model),
                                     dtype)

    with jax.set_mesh(mesh):
        t0 = time.time()
        logits, cache = forward_with_cache(params, prompts, cfg,
                                           cache_len=total, **kwargs)
        print(f"prefill {B}x{Pl}: {time.time()-t0:.2f}s")
        step = jax.jit(functools.partial(serve_step, cfg=cfg,
                                         temperature=args.temperature))
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        t0 = time.time()
        for i in range(args.gen - 1):
            tok, _, cache = step(params, tok, cache, jnp.asarray(Pl + i),
                                 rng=jax.random.fold_in(key, i))
        dt = time.time() - t0
        print(f"decode {args.gen-1} steps: {dt:.2f}s "
              f"({B*(args.gen-1)/max(dt,1e-9):.1f} tok/s)")


if __name__ == "__main__":
    main()
