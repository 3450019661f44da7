"""DPBalance sequential allocation (paper Algorithm 1).

Round flow:
  1. build per-analyst aggregates (gamma_i, mu_i, a_i)            [demand.py]
  2. SP1: alpha-fair analyst allocation via Lagrange dual ascent  [waterfill.py]
  3. SP2: per-analyst greedy cover + swap refine + kappa boost    [packing.py]
  4. return unused budget to the pool (one-or-more, Alg.1 l.4/7)
  5. emit metrics: dominant efficiency (Eq 8), dominant fairness (Eq 9),
     platform utility (Eq 10), #allocated pipelines, leftover.

`schedule_round` is a single jit-compiled program over padded [M, N, K]
arrays — the scheduler itself runs on device and scales with the mesh.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import demand as dm
from . import utility as ut
from .blockaxis import LOCAL, BlockAxis
from .packing import pack_all, pack_all_pruned
from .waterfill import alpha_fair_waterfill

_EPS = 1e-9


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    beta: float = 2.2               # fairness preference (paper Q2 knob)
    lam: float | None = None        # efficiency preference; default (beta-1)/beta
    tau: float = 100.0              # waiting-time decay T(t) = exp(-t/tau)
    kappa_max: float = 2.0          # cap on one-or-more boost (swept: 2.0 best
                                    # cross-round; large kappa starves later rounds)
    weighted_constraints: bool = False  # paper's literal Eq 14 (see DESIGN §8)
    refine: bool = True             # SP2 single-swap refinement
    incremental_swap: bool = True   # compacted swap engine (core/swap.py);
                                    # False = O(N^3 K) reference path,
                                    # bit-identical selections either way
    solver_iters: int = 4000
    solver_tol: float = 1e-6
    use_pallas: bool = False        # [M,K] hot-path sweeps via Pallas kernels
                                    # (compiled on TPU, interpret elsewhere)
    swap_beam: int = 0              # >0: certified top-k pruning of the SP2
                                    # swap sweep (core/swap.py) — evaluate
                                    # only the `swap_beam` best-bounded
                                    # candidates, fall back to the full
                                    # compacted sweep when the exactness
                                    # certificate fails.  0 (default) keeps
                                    # the full sweep, bitwise as before.
    sp1_warm_start: bool = False    # carry SP1 duals across rounds
                                    # (``rnd.lam`` in, ``sp1_lam`` out) and
                                    # use the adaptive ascent step.  The
                                    # fixed point is unique, so warm solves
                                    # agree with cold within 10*solver_tol;
                                    # off (default) is bitwise as before.

    def effective_lambda(self) -> float:
        return ut.default_lambda(self.beta) if self.lam is None else self.lam


class RoundResult(NamedTuple):
    x_analyst: jax.Array    # [M] SP1 ratios
    x_pipeline: jax.Array   # [M, N] final per-pipeline ratios (0 or >= 1)
    selected: jax.Array     # [M, N] bool
    grants: jax.Array       # [M, N, K] epsilon actually granted
    consumed: jax.Array     # [K] epsilon consumed from each block
    utility: jax.Array      # [M] analyst utilities U_i
    efficiency: jax.Array   # scalar Eq 8
    fairness: jax.Array     # scalar Eq 9
    platform: jax.Array     # scalar Eq 10
    jain: jax.Array         # scalar auxiliary Jain index
    n_allocated: jax.Array  # scalar pipelines granted
    leftover: jax.Array     # [K] remaining capacity after the round
    sp1_violation: jax.Array
    # --- observability extras (PR 8) -----------------------------------
    # Trailing fields with ``None`` defaults: every value below is an
    # intermediate the round already computes (zero extra device work);
    # ``None`` is a static empty pytree node, so results built without
    # them flow through jit/vmap/scan unchanged and old keyword
    # constructors keep working.  Consumed by ``repro.obs.tracing``.
    sp1_iters: jax.Array | None = None      # scalar i32 dual-ascent iters
    mu_real: jax.Array | None = None        # [M] realized dominant share
    sp2_objective: jax.Array | None = None  # [M] boosted Eq-20 objective
    sp2_water: jax.Array | None = None      # [M] post-boost min leftover
    swap_accepted: jax.Array | None = None  # [M] bool: swap refine fired
    grant_scale: jax.Array | None = None    # scalar overdraw-guard scale
    # --- certified swap pruning (PR 9) ---------------------------------
    swap_cert_ok: jax.Array | None = None      # scalar bool: beam certified
    swap_cert_margin: jax.Array | None = None  # scalar: tightest margin
    # --- warm-started SP1 (PR 10) --------------------------------------
    sp1_lam: jax.Array | None = None  # [K] final duals (only when
                                      # ``sp1_warm_start``; local stripe
                                      # on a sharded mesh)


def _schedule_round(rnd: dm.RoundInputs, cfg: SchedulerConfig,
                    block_axis: BlockAxis = LOCAL) -> RoundResult:
    """One DPBalance round.  With a sharded ``block_axis`` (see
    :mod:`repro.shard`) the demand/capacity operands are the caller's local
    block stripes and every per-block sweep stays shard-local; only the
    analyst-level aggregates cross the mesh.

    ``rnd.weight`` (optional [M] per-analyst tier weight, service tenancy)
    folds into ``a_i`` inside :meth:`AnalystView.build`, so SP1's
    water-filling and the Eq 8-10 metrics are tier-weighted.  SP2's
    per-pipeline ``a_ij`` stays unweighted on purpose: within one analyst
    a tier weight is a common factor, so it cannot change the packing."""
    if rnd.curve is not None:
        raise dm.no_rdp_form("dpbalance")
    gamma = dm.normalized_demand(rnd.demand, rnd.budget_total)
    mu_ij = dm.pipeline_max_share(gamma, block_axis)

    # Pipelines demanding exhausted blocks can never satisfy one-or-more:
    # mask them out of this round (they stay pending for the next).
    cap_frac = rnd.capacity / jnp.maximum(rnd.budget_total, _EPS)
    active = rnd.active & ~dm.infeasible_pipelines(gamma, cap_frac,
                                                   block_axis=block_axis)
    rnd = dataclasses.replace(rnd, active=active)

    view = dm.AnalystView.build(rnd, cfg.tau, cfg.use_pallas, block_axis)

    # SP1 — analyst-level alpha-fair allocation.
    c = view.gamma_i * (view.a_i[:, None] if cfg.weighted_constraints else 1.0)
    warm = cfg.sp1_warm_start
    with jax.named_scope("sp1"):
        sp1 = alpha_fair_waterfill(
            view.mu_i, view.a_i, c, view.mask, cap=cap_frac,
            beta=cfg.beta, max_iters=cfg.solver_iters, tol=cfg.solver_tol,
            use_pallas=cfg.use_pallas, block_axis=block_axis,
            lam0=rnd.lam if warm else None, adaptive=warm)
    budget_i = view.gamma_i * sp1.x[:, None]          # [M, K] granted vectors

    # SP2 — per-analyst packing (Alg.1 lines 3-7); per-pipeline weights
    # a_ij = T(t_ij) l_ij.
    T_ij = dm.waiting_coefficient(rnd.arrival, rnd.now, cfg.tau)
    a_ij = T_ij * rnd.loss
    with jax.named_scope("sp2"):
        if cfg.swap_beam > 0 and cfg.refine and cfg.incremental_swap:
            pack, cert_ok, cert_margin = pack_all_pruned(
                gamma, mu_ij, a_ij, active, budget_i, cfg.kappa_max,
                cfg.swap_beam, block_axis, cfg.use_pallas)
        else:
            pack = pack_all(gamma, mu_ij, a_ij, active, budget_i,
                            cfg.kappa_max, cfg.refine, cfg.incremental_swap,
                            block_axis, cfg.use_pallas)
            cert_ok = cert_margin = None

    x_ij = pack.x_ij
    grants = rnd.demand * x_ij[..., None]             # epsilon units
    consumed = jnp.sum(grants, axis=(0, 1))
    # Safety: never overdraw physical capacity (numerical guard).
    over = consumed > rnd.capacity * (1.0 + 1e-6) + 1e-7
    scale = jnp.where(over, rnd.capacity / jnp.maximum(consumed, _EPS), 1.0)
    grant_scale = block_axis.min(jnp.min(scale))
    grants = grants * grant_scale
    consumed = consumed * grant_scale
    leftover = jnp.maximum(rnd.capacity - consumed, 0.0)

    # Metrics — realized dominant share per analyst after SP2+returns.
    realized = jnp.sum(gamma * x_ij[..., None], axis=1)        # [M, K]
    mu_real = block_axis.max(jnp.max(realized, axis=-1))       # mu_i * x_i
    util = mu_real * view.a_i * view.mask
    eff = ut.dominant_efficiency(util, view.mask)
    fair = ut.dominant_fairness(util, cfg.beta, view.mask)
    plat = ut.platform_utility(util, cfg.beta, cfg.effective_lambda(), view.mask)
    return RoundResult(
        x_analyst=sp1.x, x_pipeline=x_ij, selected=pack.selected,
        grants=grants, consumed=consumed, utility=util, efficiency=eff,
        fairness=fair, platform=plat, jain=ut.jain_index(util, view.mask),
        n_allocated=jnp.sum(pack.selected), leftover=leftover,
        sp1_violation=sp1.violation,
        sp1_iters=sp1.iters, mu_real=mu_real, sp2_objective=pack.objective,
        sp2_water=pack.water, swap_accepted=pack.swapped,
        grant_scale=grant_scale,
        swap_cert_ok=cert_ok, swap_cert_margin=cert_margin,
        sp1_lam=sp1.lam if warm else None)


@functools.lru_cache(maxsize=32)
def _compiled(cfg: SchedulerConfig):
    return jax.jit(functools.partial(_schedule_round, cfg=cfg))


def schedule_round(rnd: dm.RoundInputs, cfg: SchedulerConfig) -> RoundResult:
    """Public entry — jit-cached per config."""
    return _compiled(cfg)(rnd)
