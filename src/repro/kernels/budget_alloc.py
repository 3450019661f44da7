"""Scheduler inner-loop kernels — the paper's technique on the MXU/VPU.

At production scale (M ~ 10^3 analysts, K ~ 10^5 live blocks: 1000+ devices
each minting blocks) the DPBalance dual-ascent iteration is dominated by two
dense [M,K] sweeps per step plus a dominant-share reduction:

  rowmax(gamma)        mu_i  = max_k gamma_ik          (Defs 5-6)
  matvec(c, lam)       d_i   = sum_k c_ik lam_k        (Eq 39 denominator)
  matvec_t(c, x)       load_k = sum_i c_ik x_i          (Eq 14 LHS)

All three tile the K axis through VMEM with accumulators in scratch; the
waterfill solver calls them every iteration, so the whole scheduler runs
on-device next to the training step it feeds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# VMEM a budget kernel may keep resident (double-buffered blocks + scratch).
# The v5e compiler's default scoped-VMEM limit is 16 MiB; the margin covers
# Mosaic's own temporaries.  A shape that needs more is refused with a
# ValueError naming the kernel and the operand that does not fit — it is
# never handed to the jnp path in its place.
VMEM_BUDGET = 12 * 2**20


def _vmem_bytes(rows: int, cols: int) -> int:
    """f32 bytes of one ``[rows, cols]`` VMEM buffer under (8, 128) tiling."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * 4


def _check_vmem(kernel: str, need: int, what: str) -> None:
    if need > VMEM_BUDGET:
        raise ValueError(
            f"{kernel}: {what} needs {need / 2**20:.2f} MiB of VMEM, over "
            f"the {VMEM_BUDGET / 2**20:.0f} MiB budget of one kernel "
            f"instance (repro.kernels.budget_alloc.VMEM_BUDGET); this shape "
            f"needs a kernel that tiles the block axis")


def _row_tile(kernel: str, want: int, rows: int, row_bytes: int,
              fixed_bytes: int, what: str) -> int:
    """Row-tile height for a kernel whose VMEM use is ``fixed_bytes`` plus
    ``row_bytes`` per tile row: ``min(want, rows)`` when that fits, else
    the largest multiple of 8 that does (TPU blocks are whole sublane
    tiles or the full dimension)."""
    t = max(1, min(int(want), rows))
    fit = (VMEM_BUDGET - fixed_bytes) // max(row_bytes, 1)
    if t > fit:
        t = fit // 8 * 8
    _check_vmem(kernel, fixed_bytes + max(t, 8) * row_bytes, what)
    return t


def _lane_mask(shape, k_block, block_k: int, k_total: int):
    """True on the lanes of a ``[rows, block_k]`` tile that fall inside the
    array: the last tile of a K axis that ``block_k`` does not divide
    overhangs the edge, and its overhang holds unspecified values."""
    col = k_block * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return col < k_total


def _rowmax_kernel(g_ref, o_ref, acc_scr, *, block_k: int, k_total: int):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.full_like(acc_scr, NEG_INF)

    g = g_ref[...].astype(jnp.float32)
    if k_total % block_k:
        g = jnp.where(_lane_mask(g.shape, ki, block_k, k_total), g, NEG_INF)
    acc_scr[...] = jnp.maximum(acc_scr[...],
                               jnp.max(g, axis=1, keepdims=True))

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[...] = acc_scr[...]


def rowmax(gamma, *, block_m: int = 256, block_k: int = 1024,
           interpret: bool = False):
    """mu_i = max_k gamma_ik.  [M,K] -> [M] fp32.

    Any shape: a dimension larger than its block is tiled with a ceil-div
    grid, and the K overhang of the last tile is masked to -inf.  On the
    chip ``block_m`` must be a multiple of 8 and ``block_k`` of 128 (or the
    full dimension) — the defaults are."""
    M, K = gamma.shape
    bm, bk = min(block_m, M), min(block_k, K)
    out = pl.pallas_call(
        functools.partial(_rowmax_kernel, block_k=bk, k_total=K),
        grid=(pl.cdiv(M, bm), pl.cdiv(K, bk)),
        in_specs=[pl.BlockSpec((bm, bk), lambda m, k: (m, k))],
        out_specs=pl.BlockSpec((bm, 1), lambda m, k: (m, 0)),
        out_shape=jax.ShapeDtypeStruct((M, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, 1), jnp.float32)],
        interpret=interpret,
    )(gamma)
    return out[:, 0]


def _matvec_kernel(c_ref, v_ref, o_ref, acc_scr, *, block_k: int,
                   k_total: int):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cv = c_ref[...].astype(jnp.float32) * v_ref[...].astype(jnp.float32)
    if k_total % block_k:
        cv = jnp.where(_lane_mask(cv.shape, ki, block_k, k_total), cv, 0.0)
    acc_scr[...] += jnp.sum(cv, axis=1, keepdims=True)

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[...] = acc_scr[...]


def matvec(c, v, *, block_m: int = 256, block_k: int = 1024,
           interpret: bool = False):
    """y_i = sum_k c_ik v_k.  [M,K] x [K] -> [M] fp32.  Tiles any shape
    like :func:`rowmax`, with the K overhang masked to 0."""
    M, K = c.shape
    bm, bk = min(block_m, M), min(block_k, K)
    out = pl.pallas_call(
        functools.partial(_matvec_kernel, block_k=bk, k_total=K),
        grid=(pl.cdiv(M, bm), pl.cdiv(K, bk)),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, k: (m, k)),
            pl.BlockSpec((1, bk), lambda m, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda m, k: (m, 0)),
        out_shape=jax.ShapeDtypeStruct((M, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, 1), jnp.float32)],
        interpret=interpret,
    )(c, v[None, :])
    return out[:, 0]


def matvec_t(c, x, **kw):
    """load_k = sum_i c_ik x_i — transpose form, reuses `matvec` (K becomes
    the row axis, so its tile is a multiple of 8 and M rides the lanes)."""
    return matvec(c.T, x, **kw)


_DUAL_EPS = 1e-12


def _dual_step_kernel(c_ref, lam_ref, w_ref, xcap_ref, mask_ref, cap_ref,
                      capsafe_ref, x_ref, g_ref, load_scr, *, beta: float):
    """One fused SP1 dual-ascent sweep over a row tile.

    Per grid step: form x(lambda) for the tile's rows (denominator is the
    full-K row sum, so it is tile-shape invariant), then fold each row's
    contribution into the K-sized load accumulator that lives in VMEM
    scratch across the whole sequential grid.  The fold is strictly
    row-sequential — row j of tile m lands after every row of tiles
    < m — so the accumulation order is row 0..M-1 regardless of block_m,
    which is what keeps the output bitwise equal to the ``lax.scan``
    reference at every tile shape.  Row j's demand and ratio are read
    back from the refs (a sublane-indexed load), never sliced out of a
    value.  Zero-padded tail rows contribute an exact +0.0 (c = 0,
    w_pow = 0, mask = 0 -> x = 0)."""
    mi = pl.program_id(0)
    nm = pl.num_programs(0)

    @pl.when(mi == 0)
    def _init():
        load_scr[...] = jnp.zeros_like(load_scr)

    c = c_ref[...].astype(jnp.float32)                 # [bm, K]
    lam = lam_ref[...].astype(jnp.float32)             # [1, K]
    denom = jnp.maximum(jnp.sum(c * lam, axis=1, keepdims=True), _DUAL_EPS)
    x = (w_ref[...] / denom) ** (1.0 / beta)           # [bm, 1]
    x = jnp.minimum(x, xcap_ref[...])
    x_ref[...] = jnp.where(mask_ref[...] != 0, x, 0.0)

    def row(j, carry):
        cj = c_ref[pl.ds(j, 1), :].astype(jnp.float32)            # [1, K]
        load_scr[...] = load_scr[...] + cj * x_ref[pl.ds(j, 1), :]
        return carry

    jax.lax.fori_loop(0, c_ref.shape[0], row, 0)

    @pl.when(mi == nm - 1)
    def _emit():
        g_ref[...] = (load_scr[...] - cap_ref[...]) / capsafe_ref[...]


def dual_step(c, lam, w_pow, xcap, mask, cap, cap_safe, *, beta: float,
              block_m: int = 256, interpret: bool = False):
    """Fused SP1 dual step: ``x(lambda) [M]`` and residual ``g [K]`` in one
    [M,K]-tiled pass (replaces the solver's two separate matvecs per
    iteration).  Non-divisor row counts are zero-padded and the pad slid
    off; bit-identical to :func:`repro.kernels.ref.dual_step_ref` at every
    tile shape, padded tails included, and under vmap.

    A tile holds full ``[bm, K]`` rows, so ``bm`` shrinks below ``block_m``
    (to a multiple of 8) where K is wide; a K at which not even 8 rows
    fit in VMEM is refused."""
    M, K = c.shape
    # per tile row: c (double-buffered) and the w/xcap/mask in, x out
    # columns (double-buffered, one lane tile each); lam/cap/cap_safe in,
    # g out (double-buffered) and the load scratch are [1, K] rows
    bm = _row_tile("dual_step", block_m, M,
                   row_bytes=(2 * _vmem_bytes(8, K)
                              + 8 * _vmem_bytes(8, 1)) // 8,
                   fixed_bytes=9 * _vmem_bytes(1, K),
                   what=f"a [8, {K}] row tile")
    pad = (-M) % bm

    def col(v, dtype):          # [M] -> zero-padded [M + pad, 1] column
        return jnp.pad(v.astype(dtype), (0, pad))[:, None]

    def row(v):                 # [K] -> [1, K] row
        return v.astype(jnp.float32)[None, :]

    cf = jnp.pad(c.astype(jnp.float32), ((0, pad), (0, 0)))
    x, g = pl.pallas_call(
        functools.partial(_dual_step_kernel, beta=float(beta)),
        grid=((M + pad) // bm,),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i: (i, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
        ],
        out_specs=(pl.BlockSpec((bm, 1), lambda i: (i, 0)),
                   pl.BlockSpec((1, K), lambda i: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((M + pad, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, K), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((1, K), jnp.float32)],
        interpret=interpret,
    )(cf, row(lam), col(w_pow, jnp.float32), col(xcap, jnp.float32),
      col(mask, jnp.int32), row(cap), row(cap_safe))
    return x[:M, 0], g[0]


_BOOST_EPS = 1e-9


def _boost_scan_kernel(g_ref, sel_ref, left_ref, extras_ref, oleft_ref,
                       left_scr, *, kappa_max: float):
    """SP2 proportional-boost sweep, fully VMEM-resident.

    The leftover vector lives in scratch for the whole sweep; each of the
    N steps reads one demand row, forms the boost water level (min over K
    of leftover / demand), debits the boost, and records it — the
    divide / min / update chain the jnp path runs as N separate scan steps
    with HBM round-trips between them.  Batched over analysts and swap
    candidates by vmap (each batch element becomes a grid instance)."""
    left_scr[...] = left_ref[...]
    extras_ref[...] = jnp.zeros_like(extras_ref)
    sel = sel_ref[...]                                           # [1, N]
    lane = jax.lax.broadcasted_iota(jnp.int32, sel.shape, 1)

    def step(j, carry):
        dem = g_ref[pl.ds(j, 1), :]                              # [1, K]
        left = left_scr[...]                                     # [1, K]
        ratio = jnp.where(dem > _BOOST_EPS,
                          left / jnp.maximum(dem, _BOOST_EPS), jnp.inf)
        extra = jnp.clip(jnp.min(ratio), 0.0, kappa_max - 1.0)
        # step j's selection flag: lane j of the selection row, picked by
        # mask-and-reduce (no dynamic lane slice)
        is_sel = jnp.sum(jnp.where(lane == j, sel, 0)) != 0
        extra = jnp.where(is_sel, extra, 0.0)
        left_scr[...] = left - extra * dem
        # lane-select store (TPU-friendly: no scalar scatter)
        extras_ref[...] = jnp.where(lane == j, extra, extras_ref[...])
        return carry

    jax.lax.fori_loop(0, g_ref.shape[0], step, 0)
    oleft_ref[...] = left_scr[...]


def boost_scan(g_ord, sel_ord, leftover, *, kappa_max: float,
               interpret: bool = False):
    """Fused SP2 boost sweep.  ``g_ord [N, K]`` (visit-ordered demand
    rows), ``sel_ord [N]`` bool, ``leftover [K]`` -> ``(extras [N],
    leftover_after [K])``, bit-identical to the jnp ``lax.scan`` reference
    (:func:`repro.kernels.ref.boost_scan_ref`).

    The whole of ``g_ord`` is VMEM-resident, so ``[N, K]`` beyond the
    VMEM budget is refused."""
    N, K = g_ord.shape
    _check_vmem("boost_scan",
                2 * (_vmem_bytes(N, K) + 2 * _vmem_bytes(1, N)
                     + 2 * _vmem_bytes(1, K)) + _vmem_bytes(1, K),
                f"g_ord [{N}, {K}]")
    extras, left = pl.pallas_call(
        functools.partial(_boost_scan_kernel, kappa_max=float(kappa_max)),
        out_shape=(jax.ShapeDtypeStruct((1, N), jnp.float32),
                   jax.ShapeDtypeStruct((1, K), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((1, K), jnp.float32)],
        interpret=interpret,
    )(g_ord, sel_ord.astype(jnp.int32)[None, :], leftover[None, :])
    return extras[0], left[0]


def _swap_eval_kernel(g_ref, sel_ref, left_ref, extras_ref, left_scr, *,
                      kappa_max: float):
    """Boost sweeps for one VMEM tile of swap candidates.

    Each grid step owns ``tile`` candidates: their leftover vectors sit in
    a ``[tile, K]`` scratch block for the whole sweep, and every one of the
    N visit steps loads the shared demand row ONCE and applies it to the
    entire tile — the row reuse the per-candidate vmap of
    :func:`boost_scan` cannot express (there each batch element re-streams
    ``g_ord``).  Arithmetic per candidate is operation-for-operation the
    single-candidate kernel's: same masked divide, same min-reduce over K,
    same clip and debit, so extras are bit-identical to
    :func:`repro.kernels.ref.swap_eval_ref`."""
    left_scr[...] = left_ref[...]
    extras_ref[...] = jnp.zeros_like(extras_ref)
    sel = sel_ref[...]                                           # [tile, N]
    lane = jax.lax.broadcasted_iota(jnp.int32, sel.shape, 1)

    def step(j, carry):
        dem = g_ref[pl.ds(j, 1), :]                              # [1, K]
        left = left_scr[...]                                     # [tile, K]
        ratio = jnp.where(dem > _BOOST_EPS,
                          left / jnp.maximum(dem, _BOOST_EPS), jnp.inf)
        extra = jnp.clip(jnp.min(ratio, axis=1, keepdims=True),
                         0.0, kappa_max - 1.0)                   # [tile, 1]
        is_sel = jnp.sum(jnp.where(lane == j, sel, 0), axis=1,
                         keepdims=True)                          # [tile, 1]
        extra = jnp.where(is_sel != 0, extra, 0.0)
        left_scr[...] = left - extra * dem
        # lane-select store (TPU-friendly: no scalar scatter)
        extras_ref[...] = jnp.where(lane == j, extra, extras_ref[...])
        return carry

    jax.lax.fori_loop(0, g_ref.shape[0], step, 0)


def swap_eval(g_ord, sel_c, leftover_c, *, kappa_max: float, tile: int = 128,
              interpret: bool = False):
    """Tiled SP2 candidate evaluator: boost sweeps for a whole candidate
    stack.  ``g_ord [N, K]`` (visit-ordered demand rows, shared),
    ``sel_c [C, N]`` candidate selections (visit order), ``leftover_c
    [C, K]`` per-candidate initial leftovers -> ``extras [C, N]``.

    The candidate axis is streamed through the kernel in ``tile``-sized
    VMEM blocks (grid = ceil(C / tile); non-divisor tails are zero-padded
    and slid off afterwards — a padded candidate selects nothing, so its
    lane is all-zero by construction).  Where K is wide the tile shrinks
    (to a multiple of 8) until it fits in VMEM; ``g_ord`` itself stays
    resident, so an ``[N, K]`` beyond the budget is refused.  Objectives
    and the swap argmax are formed by the caller from the extras in the
    canonical pipeline-order arithmetic, which is what keeps tie
    resolution bit-identical to the unfused sweep."""
    C, N = sel_c.shape
    K = g_ord.shape[1]
    # per candidate row: leftover in (x2), scratch, selection in and
    # extras out (x2 each); g_ord double-buffered on top
    t = _row_tile("swap_eval", tile, C,
                  row_bytes=(3 * _vmem_bytes(8, K)
                             + 4 * _vmem_bytes(8, N)) // 8,
                  fixed_bytes=2 * _vmem_bytes(N, K),
                  what=f"g_ord [{N}, {K}]")
    pad = (-C) % t
    sel_i = sel_c.astype(jnp.int32)
    left = leftover_c.astype(jnp.float32)
    if pad:
        sel_i = jnp.concatenate(
            [sel_i, jnp.zeros((pad, N), jnp.int32)], axis=0)
        left = jnp.concatenate(
            [left, jnp.zeros((pad, K), jnp.float32)], axis=0)
    extras = pl.pallas_call(
        functools.partial(_swap_eval_kernel, kappa_max=float(kappa_max)),
        grid=((C + pad) // t,),
        in_specs=[
            pl.BlockSpec((N, K), lambda i: (0, 0)),
            pl.BlockSpec((t, N), lambda i: (i, 0)),
            pl.BlockSpec((t, K), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((t, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((C + pad, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((t, K), jnp.float32)],
        interpret=interpret,
    )(g_ord, sel_i, left)
    return extras[:C]
