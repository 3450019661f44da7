"""Kernel dispatch for the scheduler hot path.

The two dense ``[M, K]`` sweeps that dominate production-scale rounds —
the AnalystView dominant-share row-max and the waterfill dual-ascent
matvecs — have Pallas kernels in :mod:`repro.kernels.budget_alloc`.  This
module is the single switch between those kernels and the plain-jnp path:

* ``use_pallas=False`` (default): pure jnp — XLA fuses these fine at paper
  scale, and it is the fast path on CPU.
* ``use_pallas=True``: the Pallas kernels, compiled on TPU and interpreted
  elsewhere (interpret mode is slow but bit-faithful, which is what the
  parity tests pin against ``kernels.ref``).

The kernels tile any shape themselves (ceil-div grids with masked edges).
A kernel whose VMEM residency cannot hold the shape — ``boost_scan`` and
``swap_eval`` keep ``g_ord [N, K]`` whole — raises a ``ValueError`` at
dispatch naming the limit; the jnp path is never substituted for it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def rowmax(g: jax.Array, use_pallas: bool = False) -> jax.Array:
    """mu_i = max_k g_ik.  [M, K] -> [M]."""
    if not use_pallas:
        return jnp.max(g, axis=-1)
    from repro.kernels.budget_alloc import rowmax as rowmax_kernel
    return rowmax_kernel(g, interpret=_interpret())


def matvec(c: jax.Array, v: jax.Array, use_pallas: bool = False) -> jax.Array:
    """y_i = sum_k c_ik v_k.  [M, K] x [K] -> [M]."""
    if not use_pallas:
        return c @ v
    from repro.kernels.budget_alloc import matvec as matvec_kernel
    return matvec_kernel(c, v, interpret=_interpret())


def matvec_t(c: jax.Array, x: jax.Array, use_pallas: bool = False) -> jax.Array:
    """load_k = sum_i c_ik x_i  (transpose sweep).  [M, K] x [M] -> [K]."""
    if not use_pallas:
        return x @ c
    return matvec(c.T, x, use_pallas=True)


def dual_step(c: jax.Array, lam: jax.Array, w_pow: jax.Array, beta: float,
              xcap: jax.Array, mask: jax.Array, cap: jax.Array,
              cap_safe: jax.Array, use_pallas: bool = False,
              block_axis=None):
    """One SP1 dual-ascent sweep: ``x(lambda)`` and the block residual.

    Computes the KKT closed form ``x_i = clip((w_pow_i / sum_k c_ik
    lam_k)^(1/beta), xcap_i)`` masked to participating analysts, then the
    load residual ``g_k = (sum_i c_ik x_i - cap_k) / cap_safe_k``.
    Returns ``(x [M], g [K])``.

    ``use_pallas`` fuses both ``[M, K]`` sweeps into one tiled kernel
    (:func:`repro.kernels.budget_alloc.dual_step`) with the K-sized load
    accumulator in VMEM scratch, replacing the two separate matvec
    round-trips the solver otherwise pays per iteration.  The kernel path
    requires a local block axis: on a sharded mesh the denominator is a
    cross-shard psum that cannot live inside a per-device kernel, so
    sharded callers keep the two-matvec path (kernels still serve the
    local partial sums when ``use_pallas`` is set).
    """
    _EPS = 1e-12
    if use_pallas and (block_axis is None or not block_axis.sharded):
        from repro.kernels.budget_alloc import dual_step as dual_kernel
        return dual_kernel(c, lam, w_pow, xcap, mask, cap, cap_safe,
                           beta=beta, interpret=_interpret())
    denom = matvec(c, lam, use_pallas)
    if block_axis is not None:
        denom = block_axis.sum(denom)
    denom = jnp.maximum(denom, _EPS)
    x = (w_pow / denom) ** (1.0 / beta)
    x = jnp.minimum(x, xcap)
    x = jnp.where(mask, x, 0.0)
    g = (matvec_t(c, x, use_pallas) - cap) / cap_safe
    return x, g


def boost_scan(g_ord: jax.Array, sel_ord: jax.Array, leftover: jax.Array,
               kappa_max: float, use_pallas: bool = False,
               block_axis=None):
    """SP2's sequential proportional-boost sweep (packing Eq 20 heuristic).

    Visits the pre-permuted pipeline rows ``g_ord [N, K]`` in order; each
    selected pipeline receives ``extra = clip(min_k leftover_k / g_jk, 0,
    kappa_max - 1)`` additional allocation, debited from ``leftover``.
    Returns ``(leftover_after [K], extras [N])``.

    ``use_pallas`` fuses the whole sweep — N steps of divide / min-reduce /
    update over K — into one VMEM-resident Pallas kernel
    (:func:`repro.kernels.budget_alloc.boost_scan`), batched over analysts
    and swap candidates by the surrounding vmaps.  The kernel path requires
    a local block axis: on a sharded mesh each step's water level is a
    cross-shard ``pmin``, which cannot live inside a per-device kernel, so
    sharded callers keep the jnp scan (the dispatch below enforces this).
    """
    if use_pallas and (block_axis is None or not block_axis.sharded):
        from repro.kernels.budget_alloc import boost_scan as boost_kernel
        extras, left = boost_kernel(g_ord, sel_ord, leftover,
                                    kappa_max=kappa_max,
                                    interpret=_interpret())
        return left, extras

    _EPS = 1e-9

    def step(left, xs):
        dem, is_sel = xs
        ratio = jnp.where(dem > _EPS, left / jnp.maximum(dem, _EPS),
                          jnp.inf)
        # boost water level = min over ALL blocks the pipeline touches
        # (cross-shard min on a sharded ledger)
        mn = jnp.min(ratio)
        if block_axis is not None:
            mn = block_axis.min(mn)
        extra = jnp.clip(mn, 0.0, kappa_max - 1.0)
        extra = jnp.where(is_sel, extra, 0.0)
        return left - extra * dem, extra

    return jax.lax.scan(step, leftover, (g_ord, sel_ord))


def swap_eval(g_ord: jax.Array, sel_c: jax.Array, leftover_c: jax.Array,
              kappa_max: float, use_pallas: bool = False,
              block_axis=None, tile: int = 128):
    """Boost sweeps for a ``[C, N]`` stack of swap candidates at once.

    ``g_ord [N, K]`` are the shared visit-ordered demand rows, ``sel_c``
    the candidate selections in visit order, ``leftover_c [C, K]`` each
    candidate's initial leftover.  Returns ``extras [C, N]``.

    ``use_pallas`` streams the candidate axis through the tiled kernel
    (:func:`repro.kernels.budget_alloc.swap_eval`): each VMEM tile of
    candidates shares one load of every demand row instead of re-streaming
    ``g_ord`` per candidate as the vmapped single-candidate kernel does.
    Same local-block-axis restriction as :func:`boost_scan` — on a sharded
    mesh every visit step's water level is a cross-shard ``pmin``, so
    sharded callers keep the batched jnp scan."""
    if use_pallas and (block_axis is None or not block_axis.sharded):
        from repro.kernels.budget_alloc import swap_eval as swap_kernel
        return swap_kernel(g_ord, sel_c, leftover_c, kappa_max=kappa_max,
                           tile=tile, interpret=_interpret())

    def one(sel_row, left):
        _, extras = boost_scan(g_ord, sel_row, left, kappa_max, False,
                               block_axis)
        return extras

    return jax.vmap(one)(sel_c, leftover_c)
