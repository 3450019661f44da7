"""SP2 — pipeline-level reallocation inside each analyst (paper Eqs 20-24).

Given the analyst's granted budget vector (from SP1), pick the pipeline set:

    (Eq 23)  maximize the NUMBER of covered pipelines, then
    (Eq 20)  maximize sum_j mu_ij x_ij a_ij over the chosen set, x_ij >= 1
             (one-or-more property, Eq 5), returning unused budget.

The paper uses a greedy heuristic for Eq 23 and Gurobi for Eq 20.  We use:

* greedy cover by ascending mu_ij (classic max-count packing heuristic),
* a single-swap refinement pass that keeps the count but may improve the
  boosted Eq-20 objective (this is what picks Bob's P3 over P4 in Fig 2) —
  by default through the incremental engine in :mod:`repro.core.swap`
  (exact candidate compaction, bit-identical to the O(N^3 K) reference
  path kept here as ``swap_refine_reference``),
* closed-form sequential proportional boost for Eq 20: each selected pipeline
  in descending mu_ij a_ij order receives kappa_j = min_k leftover_k /
  gamma_jk extra, capped at kappa_max.  With a single selected pipeline this
  is exactly the paper's kappa (Bob's P3: kappa = 1.25).

Everything is lax.scan / vmap based so the whole SP2 stage jit-compiles and
vmaps over analysts.  An exact exhaustive oracle (numpy) lives in
``exact_pack`` for tests on small N.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import hotpath
from . import swap as _swap
from .blockaxis import LOCAL, BlockAxis, grant_fits_scan

_EPS = 1e-9
_FEAS = 1e-6  # feasibility slack (float32 headroom on normalized shares)
_BIG = 1e30


class PackResult(NamedTuple):
    x_ij: jax.Array       # [N] per-pipeline allocation ratio (0 or >= 1)
    selected: jax.Array   # [N] bool
    used: jax.Array       # [K] budget consumed
    objective: jax.Array  # scalar Eq-20 value
    # Observability extras (trailing, defaulted — absent values are static
    # empty pytree nodes, so older constructors/unpackers keep working):
    swapped: jax.Array | None = None  # scalar bool: refinement changed greedy
    water: jax.Array | None = None    # scalar: post-boost min leftover share


def greedy_cover(gamma, mu, active, budget, block_axis: BlockAxis = LOCAL):
    """Select max-count pipeline set by ascending-mu greedy.  [N,K]->[N] bool.

    ``mu`` must be the *global* dominant share (already reduced across
    shards), so the visit order is identical on every shard; the
    grant-if-fits sweep goes through :func:`~repro.core.blockaxis.
    grant_fits_scan` — a plain per-visit scan locally, segment-batched
    cross-shard ANDs on a sharded mesh."""
    N = mu.shape[0]
    key = jnp.where(active, mu, _BIG)
    order = jnp.argsort(key)
    _, taken = grant_fits_scan(gamma[order], active[order], budget, _FEAS,
                               block_axis)
    sel = jnp.zeros((N,), bool).at[order].set(taken)
    return sel & active


def proportional_boost(gamma, mu, a, active, sel, budget, kappa_max: float,
                       block_axis: BlockAxis = LOCAL,
                       use_pallas: bool = False):
    """Eq 20 heuristic: x=1 for selected, then greedy kappa boosts in
    descending mu*a order.  Returns (x_ij, used, objective).

    The visit order is the FIXED descending-mu*a order over all pipelines
    (unselected ones are no-ops: extra = 0, leftover unchanged), which is
    step-for-step identical to sorting only the selected set but lets the
    scan carry pre-permuted gamma rows instead of dynamically gathering a
    row per step — under swap_refine's candidate vmap that removes one
    [n_candidates, K] gather per scan step (sel is the only batched input).

    The sweep itself dispatches through :func:`repro.core.hotpath.
    boost_scan`: ``use_pallas`` fuses the N-step divide/min/update chain
    into one VMEM-resident kernel on a local block axis (sharded meshes
    keep the jnp scan — the per-step water level is a cross-shard min)."""
    base_used = jnp.sum(gamma * sel[:, None], axis=0)
    leftover = budget - base_used

    order = jnp.argsort(-(mu * a))          # fixed: selection-independent
    g_ord = gamma[order]                     # [N, K], gathered once
    sel_ord = sel[order]

    leftover, extras = hotpath.boost_scan(g_ord, sel_ord, leftover,
                                          kappa_max, use_pallas, block_axis)
    x = jnp.zeros_like(mu).at[order].set(extras)
    x = jnp.where(sel, 1.0 + x, 0.0)
    used = jnp.sum(gamma * x[:, None], axis=0)
    obj = jnp.sum(mu * a * x * sel)
    return x, used, obj


def _boost_objective(gamma, mu, a, active, sel, budget, kappa_max,
                     block_axis: BlockAxis = LOCAL, use_pallas: bool = False):
    _, _, obj = proportional_boost(gamma, mu, a, active, sel, budget,
                                   kappa_max, block_axis, use_pallas)
    return obj


def swap_refine_reference(gamma, mu, a, active, sel, budget, kappa_max: float,
                          block_axis: BlockAxis = LOCAL,
                          use_pallas: bool = False):
    """Single-swap local search, reference path: for every (selected s,
    unselected u) try sel - {s} + {u}; keep the feasible candidate with the
    best boosted objective.  Count is preserved by construction.

    O(N^3 K) per pass — kept as the oracle for the incremental engine in
    :mod:`repro.core.swap`, which produces bit-identical selections at a
    quarter of the work (see ``tests/test_swap.py``)."""
    N = mu.shape[0]
    s_idx, u_idx = jnp.meshgrid(jnp.arange(N), jnp.arange(N), indexing="ij")
    s_flat, u_flat = s_idx.reshape(-1), u_idx.reshape(-1)

    def make_candidate(s, u):
        cand = sel.at[s].set(False).at[u].set(True)
        valid = sel[s] & (~sel[u]) & active[u] & (s != u)
        used = jnp.sum(gamma * cand[:, None], axis=0)
        feasible = block_axis.all(jnp.all(used <= budget + _FEAS))
        return cand, valid & feasible

    cands, valids = jax.vmap(make_candidate)(s_flat, u_flat)
    objs = jax.vmap(
        lambda c: _boost_objective(gamma, mu, a, active, c, budget, kappa_max,
                                   block_axis, use_pallas)
    )(cands)
    objs = jnp.where(valids, objs, -_BIG)
    base_obj = _boost_objective(gamma, mu, a, active, sel, budget, kappa_max,
                                block_axis, use_pallas)
    best = jnp.argmax(objs)
    improved = objs[best] > base_obj + 1e-12
    return jnp.where(improved, cands[best], sel)


def swap_refine(gamma, mu, a, active, sel, budget, kappa_max: float,
                block_axis: BlockAxis = LOCAL, incremental: bool = True,
                use_pallas: bool = False):
    """Single-swap refinement — dispatches to the incremental engine
    (:func:`repro.core.swap.swap_refine_incremental`, default) or the full
    O(N^3 K) reference path.  Both return the same selection bit-for-bit."""
    fn = _swap.swap_refine_incremental if incremental else \
        swap_refine_reference
    return fn(gamma, mu, a, active, sel, budget, kappa_max, block_axis,
              use_pallas)


def _finish_analyst(gamma, mu, a, active, sel0, sel, budget, kappa_max,
                    block_axis: BlockAxis = LOCAL,
                    use_pallas: bool = False) -> PackResult:
    """Shared SP2 tail: boost the final selection and assemble the
    PackResult.  Split out so the certified-pruning path can run it on a
    beam-refined (or fallback-refined) selection with operation-for-
    operation the arithmetic of :func:`pack_analyst`."""
    swapped = jnp.any(sel != sel0)
    x, used, obj = proportional_boost(gamma, mu, a, active, sel, budget,
                                      kappa_max, block_axis, use_pallas)
    # SP2 boost water level: the binding leftover share after the kappa
    # sweep (what the next boost step would have had to fit under).  Only
    # consumed by decision tracing; dead code (DCE'd) otherwise.
    water = block_axis.min(jnp.min(budget - used))
    return PackResult(x_ij=x, selected=sel, used=used, objective=obj,
                      swapped=swapped, water=water)


@functools.partial(jax.jit, static_argnames=("kappa_max", "refine",
                                             "incremental", "block_axis",
                                             "use_pallas"))
def pack_analyst(gamma, mu, a, active, budget, kappa_max: float = 8.0,
                 refine: bool = True, incremental: bool = True,
                 block_axis: BlockAxis = LOCAL,
                 use_pallas: bool = False) -> PackResult:
    """Full SP2 for one analyst.  vmap over analysts for the batched version."""
    sel0 = greedy_cover(gamma, mu, active, budget, block_axis)
    if refine:
        sel = swap_refine(gamma, mu, a, active, sel0, budget, kappa_max,
                          block_axis, incremental, use_pallas)
    else:
        sel = sel0
    return _finish_analyst(gamma, mu, a, active, sel0, sel, budget,
                           kappa_max, block_axis, use_pallas)


pack_all = jax.vmap(pack_analyst,
                    in_axes=(0, 0, 0, 0, 0, None, None, None, None, None),
                    out_axes=0)


@functools.partial(jax.jit, static_argnames=("kappa_max", "swap_beam",
                                             "block_axis", "use_pallas"))
def pack_all_pruned(gamma, mu, a, active, budget, kappa_max: float = 8.0,
                    swap_beam: int = 8, block_axis: BlockAxis = LOCAL,
                    use_pallas: bool = False):
    """Batched SP2 with the certified candidate-pruning beam.

    Runs the top-``swap_beam`` beam (:func:`repro.core.swap.
    swap_refine_beam`) for every analyst and checks the per-round
    exactness certificate.  The fallback is hoisted ABOVE the analyst
    vmap as a real ``lax.cond``: inside a vmapped body a data-dependent
    branch lowers to a select that executes both sides, which would spend
    the full O(N^2/4) sweep every round and defeat the pruning.  Out here
    the predicate is a replicated scalar (all per-analyst verdicts AND-ed;
    on a sharded mesh every quantity feeding it is post-collective), so
    certified rounds never touch the full grid and uncertified rounds
    rerun the whole round through the exact compacted sweep — all-or-
    nothing, bit-identical to :func:`pack_all` either way.

    Returns ``(PackResult [M, ...], cert_ok scalar bool, margin scalar)``
    — margin is the tightest per-analyst certificate margin (see
    ``swap_refine_beam``), the level-2 trace observable."""
    sel0 = jax.vmap(greedy_cover, in_axes=(0, 0, 0, 0, None))(
        gamma, mu, active, budget, block_axis)
    sel_beam, ok, margin = jax.vmap(
        lambda g, m, aa, ac, s0, b: _swap.swap_refine_beam(
            g, m, aa, ac, s0, b, kappa_max, swap_beam, block_axis,
            use_pallas))(gamma, mu, a, active, sel0, budget)
    cert_ok = jnp.all(ok)

    def fallback(_):
        return jax.vmap(
            lambda g, m, aa, ac, s0, b: _swap.swap_refine_incremental(
                g, m, aa, ac, s0, b, kappa_max, block_axis, use_pallas))(
            gamma, mu, a, active, sel0, budget)

    # Only the selection comes out of the branch: the boost tail runs once
    # below, in the same program position as in :func:`pack_all`, so XLA
    # compiles its reductions the same way on both paths.
    sel = jax.lax.cond(cert_ok, lambda _: sel_beam, fallback, None)
    pack = jax.vmap(
        lambda g, m, aa, ac, s0, s, b: _finish_analyst(
            g, m, aa, ac, s0, s, b, kappa_max, block_axis, use_pallas))(
        gamma, mu, a, active, sel0, sel, budget)
    return pack, cert_ok, jnp.min(margin)


@functools.partial(jax.jit, static_argnames=("kappa_max",))
def _batched_boost_objective(gamma, mu, a, active, sels, budget,
                             kappa_max: float):
    """[S, N] selection matrix -> [S] boosted objectives (one compile per
    shape — what makes the exhaustive oracle usable at N = 10 in tests)."""
    return jax.vmap(
        lambda s: proportional_boost(gamma, mu, a, active, s, budget,
                                     kappa_max)[2])(sels)


def exact_pack(gamma, mu, a, active, budget, kappa_max: float = 8.0):
    """Exhaustive oracle for tests (N <= 16): enumerate subsets, maximize
    count then boosted objective (boost via the same sequential heuristic).
    Ties resolve to the lowest subset bitmask, matching the original
    sequential enumeration."""
    gamma, mu, a = map(np.asarray, (gamma, mu, a))
    active, budget = np.asarray(active), np.asarray(budget)
    N = mu.shape[0]
    idxs = np.flatnonzero(active)
    n = idxs.size
    if n > 16:
        raise ValueError(f"exact_pack enumerates 2^{n} subsets; N_active "
                         "must be <= 16")
    bits = np.arange(1 << n)
    sels = np.zeros((1 << n, N), bool)
    sels[:, idxs] = (bits[:, None] >> np.arange(n)) & 1
    used = sels.astype(gamma.dtype) @ gamma                       # [S, K]
    feasible = (used <= budget + _FEAS).all(axis=1)
    objs = np.asarray(_batched_boost_objective(
        jnp.asarray(gamma), jnp.asarray(mu), jnp.asarray(a),
        jnp.asarray(active), jnp.asarray(sels), jnp.asarray(budget),
        kappa_max), np.float64)
    counts = sels.sum(axis=1)
    key = np.where(feasible, counts * 1.0, -1.0)
    best_count = int(key.max())
    if best_count < 0:                       # no feasible subset (can't
        return np.zeros(N, bool), 0, -np.inf  # happen: empty set is feasible)
    cand = feasible & (counts == best_count)
    best_obj = objs[cand].max()
    best = int(np.flatnonzero(cand & (objs >= best_obj))[0])
    return sels[best], best_count, float(objs[best])
