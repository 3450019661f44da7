"""Demand model for privacy-budget scheduling (paper §IV, Defs 5-6).

Shapes (padded, fixed per round):
    M — data analysts, N — pipelines per analyst (padded), K — data blocks.

`demand[M, N, K]` is the raw privacy demand (epsilon, RDP units) pipeline j of
analyst i places on block k; zero where the pipeline does not touch the block.
`capacity[K]` is the *remaining* privacy budget of each block.  Normalized
demand gamma = demand / capacity_total (the paper normalizes against the block's
total budget so shares are comparable across blocks).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from . import hotpath
from .blockaxis import LOCAL, BlockAxis

Array = jax.Array

_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class DemandView:
    """Two-ring residency view of the ``[M, N, B]`` demand tensor.

    The round functions never mutate demand; what varies tick-to-tick in a
    long-running service is only the *hot ring* — the small stripe of
    slots the current chunk's mints can touch, where retirement wipes
    stale demand columns.  Those wipes are monotone and time-indexed: the
    entry ``(m, n, b)`` is zero at tick ``t`` exactly when slot ``b`` was
    re-minted at some chunk tick ``mint_tick[b] <= t`` and the pipeline
    was submitted before it (``spawn_tick[m, n] < mint_tick[b]``).  So the
    hot ring needs no resident copy at all: ``base`` — the cold page
    store, the tensor as it stood at the chunk boundary — stays a scan
    *constant*, and :meth:`masked` reconstructs the tick's effective
    demand by fusing the wipe predicate into the activity-masking product
    the round performs anyway.  The wrapped tick body therefore carries
    O(1) demand state (down from the full O(M·N·B) carry), and every
    produced value is bit-identical to mutating the tensor in place:
    ``x * 1.0 == x`` and ``x * 0.0 == 0.0`` for the nonnegative finite
    demands.

    ``mint_tick=None`` is the monolithic view (engine episodes, wrap-free
    chunks, the full-tensor carry fallback): ``base`` is already current.
    """

    base: Array                         # [M, N, B]
    mint_tick: Optional[Array] = None   # [B] i32 chunk mint tick (NEVER if
                                        #   the slot is not minted)
    spawn_tick: Optional[Array] = None  # [M, N] i32 pipeline activation
    now_tick: Optional[Array] = None    # scalar i32 current tick

    def wiped(self) -> Array:
        """[M, N, B] bool — entries retired by this chunk's mints up to
        (and including) ``now_tick``."""
        mt = self.mint_tick[None, None, :]
        return (mt <= self.now_tick) & (self.spawn_tick[..., None] < mt)

    def masked(self, active: Array) -> Array:
        """The tick's effective demand: ``base`` with inactive pipelines
        and retired entries zeroed, in one fused elementwise pass.

        The paged result sits behind an ``optimization_barrier``: the
        fused wipe predicate must be evaluated once into a real buffer,
        not inlined into every downstream consumer of the demand tensor
        (XLA would otherwise re-derive the [M, N, B] compare per use)."""
        m = active[..., None]
        if self.mint_tick is None:
            return self.base * m.astype(self.base.dtype)
        m = m & ~self.wiped()
        return jax.lax.optimization_barrier(
            self.base * m.astype(self.base.dtype))


jax.tree_util.register_dataclass(
    DemandView, data_fields=["base", "mint_tick", "spawn_tick", "now_tick"],
    meta_fields=[])


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RoundInputs:
    """Everything the scheduler sees for one allocation round.

    ``weight`` is the optional per-analyst tier weight (service tenancy —
    :mod:`repro.service.tenancy`): it multiplies the utility coefficient
    ``a_i = T(t_i) l_i``, so SP1's alpha-fair water-filling and every
    Eq 8-10 metric see ``a_i * w_i``.  ``None`` (the engine path, and any
    pre-tenancy caller) is pytree-structural: the compiled round is
    op-for-op the unweighted program.  An all-ones weight compiles the
    multiply but is bitwise-identical to it (``x * 1.0 == x``), which is
    what keeps the default single-tier service exact."""

    demand: Array        # [M, N, K] raw epsilon demand
    active: Array        # [M, N] bool — pipeline exists and is pending
    arrival: Array       # [M, N] arrival time of each pipeline (seconds)
    loss: Array          # [M, N] matching degree l_ij in (0, 1]
    capacity: Array      # [K] remaining budget of each block (epsilon)
    budget_total: Array  # [K] the block's *total* budget (normalization base)
    now: Array           # scalar — current time (seconds)
    weight: Optional[Array] = None  # [M] per-analyst tier weight (or None)
    lam: Optional[Array] = None     # [K] previous round's SP1 duals (warm
                                    #   start; None = cold, also structural)
    curve: Optional[Array] = None   # [M, N, A] Rényi-DP demand curve over
                                    #   the ledger's orders (None = scalar
                                    #   epsilon; structural).  With it,
                                    #   ``demand`` is each pipeline's
                                    #   per-block scale, the demand at
                                    #   order a is ``demand * curve[..., a]``,
                                    #   and capacity / budget_total are
                                    #   ``[A, K]``

    @property
    def shape(self):
        return self.demand.shape


def normalized_demand(demand: Array, budget_total: Array) -> Array:
    """gamma_ij^<k> = demand / total block budget (Def 5).  [M, N, K]."""
    return demand / jnp.maximum(budget_total, _EPS)[None, None, :]


def pipeline_max_share(gamma: Array, block_axis: BlockAxis = LOCAL) -> Array:
    """mu_ij = max_k gamma_ij^<k>  (Eq. 3).  [M, N]."""
    return block_axis.max(jnp.max(gamma, axis=-1))


def infeasible_pipelines(gamma: Array, cap_frac: Array,
                         slack: float = 1e-6,
                         block_axis: BlockAxis = LOCAL) -> Array:
    """Pipelines whose demand exceeds remaining capacity on any block —
    they can never satisfy one-or-more this round and are masked out (they
    stay pending for the next).  [M, N] bool.  Single source of truth for
    the round-level feasibility rule (scheduler, baselines, engine
    diagnostics all use it)."""
    return block_axis.any(
        jnp.any(gamma > cap_frac[None, None, :] + slack, axis=-1))


def order_share(demand: Array, curve: Array, budget_total: Array) -> Array:
    """Rényi-DP normalized demand at its best order,
    ``min_a demand_ijk * curve_ij(a) / budget_total_k(a)``.  [M, N, K].

    One order within capacity is enough for a grant to fit, so the
    smallest share over the orders is the pipeline's share of the block
    (the DPF key over RDP budgets).  The ``[M, N, A, K]`` product lives
    only inside the reduction's fusion."""
    bt = jnp.maximum(budget_total, _EPS)                 # [A, K]
    per_order = (demand[..., None, :] * curve[..., :, None]) / bt
    return jnp.min(per_order, axis=-2)


def infeasible_pipelines_rdp(demand: Array, curve: Array,
                             budget_total: Array, cap_frac: Array,
                             slack: float = 1e-6,
                             block_axis: BlockAxis = LOCAL) -> Array:
    """The Rényi-DP form of :func:`infeasible_pipelines`: a pipeline is
    screened out when some block it demands has no order at which its
    normalized demand fits the remaining ``cap_frac [A, K]``.  Blocks it
    does not demand never screen it, whatever their orders hold.
    [M, N] bool."""
    bt = jnp.maximum(budget_total, _EPS)
    per_order = (demand[..., None, :] * curve[..., :, None]) / bt
    fits = jnp.any(per_order <= cap_frac + slack, axis=-2)   # [M, N, K]
    return block_axis.any(jnp.any((demand > 0.0) & ~fits, axis=-1))


def no_rdp_form(scheduler: str) -> ValueError:
    """The error of a scheduler that has no Rényi-DP form."""
    return ValueError(
        f"scheduler {scheduler!r} has no Rényi-DP form (ServiceConfig.rdp /"
        f" RoundInputs.curve): run 'dpf' or 'fcfs' over an RDP ledger")


def analyst_demand(gamma: Array, active: Array) -> Array:
    """Assembled analyst demand gamma_i^<k> = sum_j gamma_ij^<k> (Eq. 15 at
    x_ij = 1, over active pipelines).  [M, K]."""
    return jnp.sum(gamma * active[..., None], axis=1)


def analyst_max_share(gamma_i: Array, use_pallas: bool = False,
                      block_axis: BlockAxis = LOCAL) -> Array:
    """mu_i = max_k gamma_i^<k>  (Eq. 4).  [M].

    ``use_pallas`` routes the row-max through the Pallas budget kernel
    (production-scale [M, K] sweep; see :mod:`repro.core.hotpath`); on a
    block-sharded mesh the local row-max is finished with a ``pmax``."""
    return block_axis.max(hotpath.rowmax(gamma_i, use_pallas))


def waiting_coefficient(arrival: Array, now: Array, tau: float) -> Array:
    """T(t) — any monotone decreasing function of waiting time (Def 8).

    We use T(t) = exp(-t / tau); tau is a platform knob (seconds).
    """
    wait = jnp.maximum(now - arrival, 0.0)
    return jnp.exp(-wait / tau)


def analyst_waiting(arrival: Array, active: Array, now: Array) -> Array:
    """Average delay t_i over an analyst's pending pipelines (Def 10)."""
    wait = jnp.maximum(now - arrival, 0.0) * active
    denom = jnp.maximum(jnp.sum(active, axis=1), 1.0)
    return jnp.sum(wait, axis=1) / denom


def analyst_loss(loss: Array, mu_ij: Array, active: Array) -> Array:
    """l_i — mu-weighted average of the analyst's pipeline matching degrees
    (Eq. 6's functional form lifted to the analyst level)."""
    w = mu_ij * active
    denom = jnp.maximum(jnp.sum(w, axis=1), _EPS)
    return jnp.sum(w * loss, axis=1) / denom


@dataclasses.dataclass(frozen=True)
class AnalystView:
    """Per-analyst aggregates consumed by the SP1 water-filling solver."""

    gamma_i: Array   # [M, K] assembled normalized demand
    mu_i: Array      # [M]    analyst dominant-share coefficient
    a_i: Array       # [M]    T(t_i) * l_i weight
    mask: Array      # [M]    analyst has any active demand

    @classmethod
    def build(cls, rnd: RoundInputs, tau: float, use_pallas: bool = False,
              block_axis: BlockAxis = LOCAL) -> "AnalystView":
        gamma = normalized_demand(rnd.demand, rnd.budget_total)
        mu_ij = pipeline_max_share(gamma, block_axis)
        g_i = analyst_demand(gamma, rnd.active)
        mu_i = analyst_max_share(g_i, use_pallas, block_axis)
        t_i = analyst_waiting(rnd.arrival, rnd.active, rnd.now)
        T_i = jnp.exp(-t_i / tau)
        l_i = analyst_loss(rnd.loss, mu_ij, rnd.active)
        a_i = T_i * l_i
        if rnd.weight is not None:      # tier weight folds into a_i, so it
            a_i = a_i * rnd.weight      # reaches SP1 and the Eq 8-10 metrics
        mask = jnp.sum(rnd.active, axis=1) > 0
        return cls(gamma_i=g_i, mu_i=mu_i, a_i=a_i, mask=mask)
