"""Baseline privacy-budget schedulers from the paper's evaluation (§VI):

* DPF  [Luo et al., OSDI'21]  — grant the pending pipeline with the smallest
  dominant share first (max-min fairness at the pipeline level).
* DPK  [Tholoniat et al., "Packing privacy budget"] — grant pipelines with the
  lowest weight-to-demand ratio first (efficiency/packing oriented; smallest
  total demand per unit weight gets in first).
* FCFS — grant in arrival order.

All three operate at the pipeline level with x_ij = 1 grants (no boost), which
is how the paper characterizes them in Fig. 2.  They share the same
RoundResult schema as DPBalance so every metric is directly comparable.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from . import demand as dm
from . import utility as ut
from .blockaxis import LOCAL, BlockAxis, grant_fits_scan
from .scheduler import RoundResult, SchedulerConfig

_EPS = 1e-9
_FEAS = 1e-6
_BIG = 1e30


def _sequential_grant(rnd: dm.RoundInputs, cfg: SchedulerConfig, key_fn,
                      block_axis: BlockAxis = LOCAL):
    """Flatten pipelines, sort by key_fn ascending, grant-if-fits scan.

    Sharded ``block_axis``: the sort key is reduced across shards first so
    the visit order is identical everywhere; the grant-if-fits sweep runs
    through :func:`~repro.core.blockaxis.grant_fits_scan`, which keeps
    per-block remaining capacity shard-local and batches the cross-shard
    fits-check ANDs into one segmented collective per refinement instead
    of one per visited pipeline."""
    if rnd.curve is not None:
        return _sequential_grant_rdp(rnd, cfg, key_fn, block_axis)
    M, N, K = rnd.demand.shape
    gamma = dm.normalized_demand(rnd.demand, rnd.budget_total)
    mu_ij = dm.pipeline_max_share(gamma, block_axis)
    cap_frac = rnd.capacity / jnp.maximum(rnd.budget_total, _EPS)

    active = rnd.active & ~dm.infeasible_pipelines(gamma, cap_frac, _FEAS,
                                                   block_axis)
    key = key_fn(rnd, gamma, mu_ij, block_axis)          # [M, N]
    key = jnp.where(active, key, _BIG).reshape(-1)
    order = jnp.argsort(key)
    # pre-permute into visit order so the scan streams rows instead of
    # dynamically gathering one per step
    g_ord = gamma.reshape(M * N, K)[order]
    a_ord = active.reshape(-1)[order]

    with jax.named_scope("grant_scan"):
        _, taken = grant_fits_scan(g_ord, a_ord, cap_frac, _FEAS,
                                   block_axis)
    sel = jnp.zeros((M * N,), bool).at[order].set(taken).reshape(M, N)
    x_ij = sel.astype(gamma.dtype)

    grants = rnd.demand * x_ij[..., None]
    consumed = jnp.sum(grants, axis=(0, 1))
    leftover = jnp.maximum(rnd.capacity - consumed, 0.0)

    # dataclasses.replace keeps the optional per-analyst tier weight, so
    # the baselines' Eq 8-10 metrics are weighted exactly like DPBalance's
    # (their grant *order* stays unweighted — they are the paper's
    # tier-blind baselines).
    view = dm.AnalystView.build(
        dataclasses.replace(rnd, active=active), cfg.tau,
        cfg.use_pallas, block_axis)
    realized = jnp.sum(gamma * x_ij[..., None], axis=1)
    mu_real = block_axis.max(jnp.max(realized, axis=-1))
    util = mu_real * view.a_i * view.mask
    eff = ut.dominant_efficiency(util, view.mask)
    fair = ut.dominant_fairness(util, cfg.beta, view.mask)
    plat = ut.platform_utility(util, cfg.beta, cfg.effective_lambda(), view.mask)
    zeros_m = jnp.zeros((M,), gamma.dtype)
    return RoundResult(
        x_analyst=zeros_m, x_pipeline=x_ij, selected=sel, grants=grants,
        consumed=consumed, utility=util, efficiency=eff, fairness=fair,
        platform=plat, jain=ut.jain_index(util, view.mask),
        n_allocated=jnp.sum(sel), leftover=leftover,
        sp1_violation=jnp.zeros(()),
        # observability extras: the baselines have no SP1/SP2 stages, so
        # only the realized dominant share is meaningful (rest stay None —
        # repro.obs.tracing substitutes static zeros / unit scale).
        mu_real=mu_real)


def _sequential_grant_rdp(rnd: dm.RoundInputs, cfg: SchedulerConfig,
                          key_fn, block_axis: BlockAxis = LOCAL):
    """:func:`_sequential_grant` over a Rényi-DP ledger (``rnd.curve``).

    A pipeline's share of a block is its normalized demand at its best
    order (:func:`~repro.core.demand.order_share`), so DPF's dominant
    share is ``max_k min_a``.  The grant-if-fits scan admits a visit
    when every block it demands fits at some order, and debits every
    order.  Capacity is not floored: an order that a grant overdraws is
    spent on that block while another order still holds.  The Eq 8-10
    metrics read the best-order shares in place of the scalar gamma."""
    M, N, K = rnd.demand.shape
    A = rnd.curve.shape[-1]
    with jax.named_scope("order_fits"):
        share = dm.order_share(rnd.demand, rnd.curve, rnd.budget_total)
        mu_ij = block_axis.max(jnp.max(share, axis=-1))
        cap_frac = rnd.capacity / jnp.maximum(rnd.budget_total, _EPS)
        active = rnd.active & ~dm.infeasible_pipelines_rdp(
            rnd.demand, rnd.curve, rnd.budget_total, cap_frac, _FEAS,
            block_axis)
        key = key_fn(rnd, share, mu_ij, block_axis)
        key = jnp.where(active, key, _BIG).reshape(-1)
        order = jnp.argsort(key)
        d_ord = rnd.demand.reshape(M * N, K)[order]
        c_ord = rnd.curve.reshape(M * N, A)[order]
        a_ord = active.reshape(-1)[order]
        with jax.named_scope("grant_scan"):
            _, taken = grant_fits_scan(
                d_ord, a_ord, cap_frac, _FEAS, block_axis, curves=c_ord,
                norm=jnp.maximum(rnd.budget_total, dm._EPS))
    sel = jnp.zeros((M * N,), bool).at[order].set(taken).reshape(M, N)
    x_ij = sel.astype(share.dtype)

    grants = rnd.demand * x_ij[..., None]                    # [M, N, K]
    consumed = jnp.sum(grants[:, :, None, :] * rnd.curve[..., None],
                       axis=(0, 1))                          # [A, K]
    leftover = rnd.capacity - consumed

    view = dm.AnalystView.build(
        dataclasses.replace(rnd, demand=share, active=active, curve=None,
                            budget_total=jnp.ones((K,), share.dtype)),
        cfg.tau, cfg.use_pallas, block_axis)
    realized = jnp.sum(share * x_ij[..., None], axis=1)
    mu_real = block_axis.max(jnp.max(realized, axis=-1))
    util = mu_real * view.a_i * view.mask
    zeros_m = jnp.zeros((M,), share.dtype)
    return RoundResult(
        x_analyst=zeros_m, x_pipeline=x_ij, selected=sel, grants=grants,
        consumed=consumed, utility=util,
        efficiency=ut.dominant_efficiency(util, view.mask),
        fairness=ut.dominant_fairness(util, cfg.beta, view.mask),
        platform=ut.platform_utility(util, cfg.beta,
                                     cfg.effective_lambda(), view.mask),
        jain=ut.jain_index(util, view.mask),
        n_allocated=jnp.sum(sel), leftover=leftover,
        sp1_violation=jnp.zeros(()), mu_real=mu_real)


def _dpf_key(rnd, gamma, mu_ij, block_axis=LOCAL):
    return mu_ij                                   # smallest dominant share


def _dpk_key(rnd, gamma, mu_ij, block_axis=LOCAL):
    if rnd.curve is not None:
        raise dm.no_rdp_form("dpk")
    total = block_axis.sum(jnp.sum(gamma, axis=-1))  # total normalized demand
    return total                                   # lowest demand packs first


def _fcfs_key(rnd, gamma, mu_ij, block_axis=LOCAL):
    return rnd.arrival                             # earliest arrival first


@functools.lru_cache(maxsize=32)
def _compiled(cfg: SchedulerConfig, name: str):
    key_fn = {"dpf": _dpf_key, "dpk": _dpk_key, "fcfs": _fcfs_key}[name]
    return jax.jit(functools.partial(_sequential_grant, cfg=cfg, key_fn=key_fn))


def dpf_round(rnd: dm.RoundInputs, cfg: SchedulerConfig) -> RoundResult:
    return _compiled(cfg, "dpf")(rnd)


def dpk_round(rnd: dm.RoundInputs, cfg: SchedulerConfig) -> RoundResult:
    return _compiled(cfg, "dpk")(rnd)


def fcfs_round(rnd: dm.RoundInputs, cfg: SchedulerConfig) -> RoundResult:
    return _compiled(cfg, "fcfs")(rnd)

