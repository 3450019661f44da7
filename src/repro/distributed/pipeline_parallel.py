"""GPipe-style pipeline parallelism over a mesh axis (default: 'pod').

For models whose per-chip weight footprint exceeds HBM even under TP+FSDP,
the multi-pod mesh's 'pod' axis can carry pipeline stages instead of data
parallelism: layers are split into `n_stages` contiguous stages, microbatches
stream through, and activations hop stages via `collective_permute`
(TPU-native point-to-point over ICI).

Implementation: shard_map over the stage axis; the classic GPipe schedule of
T = n_micro + n_stages - 1 ticks, each tick = receive(ppermute) -> compute.
Stage s is busy for ticks [s, s + n_micro); bubble fraction =
(n_stages-1)/T, amortized by more microbatches.

`pipeline_apply` is deliberately minimal — a building block wired for the
cells that need it (kimi-k2 at <512 chips), not the default path (DP over
'pod' measures better for everything that fits; see DESIGN.md §6).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x_micro, mesh,
                   axis: str = "pod"):
    """Run microbatches through pipeline stages laid out along `axis`.

    stage_fn(params_one_stage, x) -> y    (same shape as x)
    stage_params: pytree with leading dim n_stages (sharded over `axis`)
    x_micro: [n_micro, ...] microbatched input (replicated over `axis`)
    Returns [n_micro, ...] outputs of the LAST stage (replicated).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_stage(params_local, x_local):
        params_local = jax.tree.map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)
        mb_shape = x_local.shape[1:]

        def tick(t, carry):
            prev_out, outputs = carry
            # receive the previous stage's tick-(t-1) output
            received = jax.lax.ppermute(prev_out, axis, fwd)
            idx = jnp.clip(t, 0, n_micro - 1)
            my_in = jnp.where(stage == 0,
                              jax.lax.dynamic_index_in_dim(
                                  x_local, idx, keepdims=False),
                              received)
            out = stage_fn(params_local, my_in)
            # last stage banks its result for microbatch t-(n_stages-1)
            mb_done = t - (n_stages - 1)
            valid = (stage == n_stages - 1) & (mb_done >= 0)
            outputs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.maximum(mb_done, 0), 0),
                lambda o: o, outputs)
            return out, outputs

        zero = jnp.zeros(mb_shape, x_local.dtype)
        outs0 = jnp.zeros((n_micro,) + mb_shape, x_local.dtype)
        _, outputs = jax.lax.fori_loop(0, ticks, tick, (zero, outs0))
        # broadcast from the last stage: zero elsewhere, then sum-reduce
        outputs = jnp.where(stage == n_stages - 1, outputs, 0.0)
        return jax.lax.psum(outputs, axis)

    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P()), out_specs=P(),
        check_vma=False)
    return fn(stage_params, x_micro)
