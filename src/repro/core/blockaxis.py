"""Cross-shard reduction hooks for block-axis sweeps.

Every scheduler stage sweeps the block axis somewhere: the dominant-share
row-max (Eq 3/4), the waterfill dual-ascent matvecs, SP2 feasibility
checks, the kappa-boost water level.  On one device those are plain jnp
reductions; on a block-sharded mesh (``repro.shard``) each device holds
only its stripe of the ``[..., B]`` arrays and the *same* code must finish
each reduction with a collective over the mesh axis.

:class:`BlockAxis` is that seam.  The default :data:`LOCAL` (``name=None``)
makes every hook the identity, so the single-device path is untouched —
byte-for-byte the pre-sharding code.  Inside ``shard_map`` the caller
passes ``BlockAxis("shard")`` and each hook becomes the matching
``jax.lax`` collective.  The object is hashable (frozen dataclass) so it
can ride through ``jax.jit`` static arguments.

Convention: callers reduce their *local* block stripe with jnp first, then
hand the partial result to the hook — e.g. ``bx.max(jnp.max(g, axis=-1))``
— so the hook only ever sees block-free shapes and the collective payload
stays small (analyst- or pipeline-indexed, never block-indexed).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class BlockAxis:
    """Reduction hooks over the (possibly sharded) block axis.

    ``name`` is the mesh axis the block dimension is sharded over, or None
    for the single-device layout.  ``fits_segment`` sizes the visit
    segments of :func:`grant_fits_scan` — the sharded sequential-grant
    sweeps batch their cross-shard fits-checks into one collective per
    segment refinement instead of one per visited pipeline (ignored on the
    local layout, where the per-step check is free).
    """

    name: Optional[str] = None
    fits_segment: int = 8

    @property
    def sharded(self) -> bool:
        return self.name is not None

    # partial-result combiners: x is the local stripe's reduction
    def max(self, x):
        return jax.lax.pmax(x, self.name) if self.name else x

    def min(self, x):
        return jax.lax.pmin(x, self.name) if self.name else x

    def sum(self, x):
        return jax.lax.psum(x, self.name) if self.name else x

    # boolean combiners (pmax/pmin are not defined on bool everywhere, so
    # route through i32)
    def any(self, x):
        if not self.name:
            return x
        return jax.lax.pmax(x.astype(jnp.int32), self.name).astype(bool)

    def all(self, x):
        if not self.name:
            return x
        return jax.lax.pmin(x.astype(jnp.int32), self.name).astype(bool)


LOCAL = BlockAxis(None)


def grant_fits_scan(dems, act, remaining, feas,
                    block_axis: BlockAxis = LOCAL, curves=None, norm=None):
    """Sequential grant-if-fits sweep over pre-ordered visits.

    ``dems [V, K]`` are the visits' (local-stripe) demand rows, ``act [V]``
    their activity mask, ``remaining [K]`` the local remaining capacity.
    Returns ``(remaining_after, taken [V] bool)`` with, in visit order,

        taken_v = act_v  AND  all_k dem_vk <= remaining_k + feas   (global k)
        remaining -= dem_v                                     where taken_v.

    This is THE fits-check of every sequential-grant loop (the DPF/DPK/FCFS
    baselines and SP2's greedy cover).  On the local layout it is a plain
    ``lax.scan`` — one step per visit, byte-identical to the pre-seam code.

    On a sharded ``block_axis`` the naive scan costs **one cross-shard
    collective per visited pipeline** (the per-step AND).  Here visits are
    processed in segments of ``block_axis.fits_segment``: each refinement
    evaluates the whole segment's fits under a guessed in-segment decision
    vector with ONE batched ``pmin`` (payload = the segment), then adopts
    the result as the next guess.  Because a decision vector that is
    correct on its first ``p`` entries yields verdicts that are correct on
    ``p + 1`` entries (each verdict only depends on *earlier* decisions),
    every refinement extends the correct prefix — the loop converges to
    the unique self-consistent vector in at most G refinements, typically
    1-2 (log-ish depth in practice vs G sequential collectives).  The
    final remaining-capacity state is recomputed under the converged
    decisions with the same subtraction order as the naive scan, so
    decisions AND arithmetic are bit-identical to the per-step path on any
    shard count.

    Rényi-DP ledgers pass ``curves [V, A]`` (each visit's demand curve
    over the ledger's orders) and ``norm [A, K]`` (the blocks' per-order
    budgets); ``remaining`` is then ``[A, K]`` and ``dems`` the visits'
    per-block scales.  The visit's demand at order ``a`` is
    ``dem_vk * c_v(a) / norm_ak``, formed for the one visited row, and

        taken_v = act_v AND all_k (dem_vk = 0 OR any_a demand <= rem + feas)
        rem(a) -= demand(a)       at every order, where taken_v.

    The gate on ``dem_vk = 0`` keeps a block that no order can serve from
    refusing the pipelines that never demand it.  ``any_a`` is local to a
    block, so the sharded path keeps its one ``[G]``-payload collective.
    """
    if curves is None:
        def fits_at(d, r):
            return jnp.all(d <= r + feas)

        def debit_of(d):
            return d
        xs_dem = dems
    else:
        def fits_at(d, r):
            dem, _ = d
            return jnp.all((dem == 0.0)
                           | jnp.any(debit_of(d) <= r + feas, axis=0))

        def debit_of(d):
            dem, c = d
            return (dem[None, :] * c[:, None]) / norm
        xs_dem = (dems, curves)

    if not block_axis.sharded or block_axis.fits_segment <= 1:
        def step(rem, xs):
            dem, a = xs
            ok = a & block_axis.all(fits_at(dem, rem))
            return jnp.where(ok, rem - debit_of(dem), rem), ok

        return jax.lax.scan(step, remaining, (xs_dem, act))

    G = int(block_axis.fits_segment)
    V = act.shape[0]
    pad = (-V) % G

    def segments(x):
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:],
                                              x.dtype)])
        return x.reshape(((V + pad) // G, G) + x.shape[1:])

    dem_seg = jax.tree.map(segments, xs_dem)
    act_seg = segments(act)

    def seg_body(rem, xs):
        dem_g, act_g = xs

        def refine(dec):
            """Segment fits + end-state under in-segment decisions ``dec``
            (one local G-step scan, one [G]-payload collective)."""
            def step(r, xs2):
                d, a, dc = xs2
                fit = a & fits_at(d, r)
                return jnp.where(dc, r - debit_of(d), r), fit

            r_end, fits = jax.lax.scan(step, rem, (dem_g, act_g, dec))
            return r_end, block_axis.all(fits)

        dec0 = jnp.zeros((G,), bool)
        r0, f0 = refine(dec0)

        def cond(carry):
            dec, fits, _ = carry
            return jnp.any(dec != fits)

        def body(carry):
            _, fits, _ = carry
            r_end, new_fits = refine(fits)
            return fits, new_fits, r_end

        # at exit dec == fits: the evaluation that produced ``fits`` used
        # the very decisions it returned, so they are the (unique) correct
        # ones and ``r_end`` is the capacity state under them.
        _, taken_g, r_end = jax.lax.while_loop(cond, body, (dec0, f0, r0))
        return r_end, taken_g

    rem_out, taken = jax.lax.scan(seg_body, remaining, (dem_seg, act_seg))
    return rem_out, taken.reshape(-1)[:V]
