"""The service tick loop: chunks of T ticks as one compiled scan.

Layering on the PR-1 engine:

* the per-tick body is the *engine's* round body (mint blocks -> build
  ``RoundInputs`` -> dispatch through ``registry.get_round_fn`` -> debit
  capacity, mark grants) lifted onto persistent :class:`ServiceState`
  instead of a pre-generated ``Episode``;
* ``chunk_ticks`` consecutive ticks run as a single ``jax.lax.scan`` inside
  one jit program — the host touches device state **only at chunk
  boundaries**, where it drains the admission queue into recycled slots,
  plans the chunk's block mints, and folds telemetry;
* admissions are *prefetched*: the server polls the trace for the whole
  upcoming chunk at the boundary, and each admitted pipeline activates
  mid-chunk at its own ``spawn_tick`` — the same mechanism as the engine's
  ``spawn_round``, which is what makes a frozen trace replay bit-compatible
  with :func:`repro.core.engine.run_episode` (see
  :mod:`repro.service.replay`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import utility as ut
from repro.core.blockaxis import LOCAL, BlockAxis
from repro.core.demand import DemandView, RoundInputs, no_rdp_form
from repro.core.engine import round_diagnostics
from repro.core.registry import get_round_fn
from repro.core.scheduler import SchedulerConfig
from repro.core.simulation import ROUND_SECONDS
from repro.obs.audit import AuditWriter
from repro.obs.exporter import JsonlSink, MetricsServer
from repro.obs.profiler import PhaseProfiler
from repro.obs.registry import MetricsRegistry, absorb_summary
from repro.obs.tracing import DecisionTrace, split_trace_ys, \
    trace_round_outputs
from repro.privacy.rdp import RdpSpec

from .outputs import copy_to_host, packed
from .queue import AdmissionQueue
from .state import NEVER, ServiceState, SlotTable, admit_batch, plan_mints
from .telemetry import StreamingTelemetry
from .tenancy import policy_key, resolve_policy
from .traces import ArrivalTrace, demand_window_ticks

# Bump when checkpoint_host_state()'s schema changes incompatibly.
# Version 2 (tenancy): adds the per-row tier/weight mirrors, the
# ServiceState.weight device leaf, per-tier telemetry, and the versioned
# per-class admission queue.  Version 3 (observability): adds the metrics
# registry / phase profiler snapshots and the audit slot mirrors — all
# optional, so v1/v2 checkpoints restore with those planes empty.
# Version 4 (warm SP1): adds the ServiceState.lam device leaf (per-block
# SP1 duals carried across ticks); older checkpoints restore with a fresh
# cold dual (all ones), which only costs a one-chunk re-warm.
# Version 5 (Rényi-DP ledgers): records the ledger's RdpSpec ("rdp",
# None for scalar epsilon); an RDP state adds the ServiceState.curve
# leaf and an [A, B] block_capacity.  Older checkpoints are scalar.
_CHECKPOINT_VERSION = 5
_COMPAT_VERSIONS = (1, 2, 3, 4, 5)

# The profiler spans of one run_chunk round, in order (parents before their
# children); the per-round ring keeps each one's self seconds.
ROUND_SPANS = (
    "admit_drain", "admit_drain/poll", "admit_drain/queue",
    "admit_drain/place", "admit_drain/write",
    "plan_mints", "plan_mints/plan", "plan_mints/upload",
    "chunk_execute", "chunk_compile_execute", "state_graft",
    "host_sync", "host_sync/device_wait", "host_sync/copy_out",
    "recycle", "telemetry_fold")


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    scheduler: str = "dpbalance"
    sched: SchedulerConfig = SchedulerConfig()
    analyst_slots: int = 8         # M rows in the slot table
    pipeline_slots: int = 32       # N columns per row
    block_slots: int = 4096        # B ledger ring slots
    chunk_ticks: int = 8           # T — scan length per host round-trip
    admit_batch: int = 32          # max submissions admitted per boundary
    max_pending: int = 1024        # queue bound (backpressure beyond this)
    validate: bool = True          # host-checks conservation per chunk
    diagnostics: bool = False      # per-tick SP1 diagnostics in chunk output
    paged: bool = True             # two-ring paged demand residency on wrap
                                   # chunks (False = carry the full tensor)
    latency_reservoir: int = 100_000
    # Tenancy policy: None (adopt the trace's tier mix, if any), a tenant-
    # mix registry name, or a TenancyPolicy.  Governs queue priorities /
    # aging, SLO targets, and cost caps; tier *assignment* always comes
    # stamped on the submissions themselves.
    tenancy: object = None
    # JSON-lines telemetry export: append summary() at every chunk
    # boundary (NaN-safe plain-dict serialization; see telemetry.json_safe).
    # Routed through repro.obs.exporter.JsonlSink: a persistent append
    # handle, flushed per chunk, fsynced on close().
    telemetry_path: Optional[str] = None
    # ------------------------------------------------------ observability
    # Prometheus /metrics endpoint: None = off, 0 = ephemeral port (read
    # it back from service.metrics_server.port), else the literal port.
    metrics_port: Optional[int] = None
    # Decision tracing (repro.obs.tracing).  Static gate: 0 compiles the
    # trace outputs out entirely (bitwise-neutral), 1 adds SP1 internals +
    # per-analyst shares, 2 adds SP2 water levels / swap counts / the
    # overdraw-guard scale.
    trace_level: int = 0
    trace_ticks: int = 4096        # host-side trace ring (newest ticks kept)
    # Append-only checksummed per-grant audit ledger (repro.obs.audit);
    # None = off.  Enabling it adds the per-pipeline grant ratios to the
    # chunk outputs for host-side attribution.
    audit_path: Optional[str] = None
    # Wrap tick-loop phases in jax.profiler.TraceAnnotation (the wall-clock
    # phase profiler itself is always on — it is host-side only).
    profile_annotations: bool = False
    # Rényi-DP block ledgers (repro.privacy.rdp.RdpSpec): every block's
    # budget is a curve over the spec's usable orders, submissions carry
    # demand curves, and a grant fits while some order of every demanded
    # block stays within capacity (dpf / fcfs).  None = scalar epsilon:
    # the service and its compiled programs are the scalar ones.
    rdp: Optional[RdpSpec] = None


def _chunk_metrics(state: ServiceState, mint_ops, *,
                   cfg: SchedulerConfig, round_fn, n_ticks: int,
                   mode: str, diagnostics: bool = False,
                   trace_level: int = 0, audit: bool = False,
                   block_axis: BlockAxis = LOCAL,
                   rdp: Optional[RdpSpec] = None):
    """Traceable: run ``n_ticks`` service ticks in one ``lax.scan``.

    Mirrors ``engine._episode_metrics`` tick-for-tick so a wrap-free ledger
    over an episode-compatible trace is bit-identical to ``run_episode``.

    Three statically-selected bodies (see
    :class:`~repro.service.state.MintPlan`):

    * ``"wrapfree"``: ``mint_ops = (mint_add, budget_total, created)``
      precomputed rows; carry is ``(done, capacity)`` and the mint is
      ``capacity += mint_add`` — **op-for-op the engine's round body**, so
      a service tick costs an engine round.
    * ``"paged"`` (ring wrapped, default): ``mint_ops = (mask, budgets,
      budget_total, created, mint_tick)``; minted slots evict their
      previous block (capacity set, not added; stale demand retired).
      Demand stays a scan *constant*: inside one chunk the only demand
      mutations are the monotone retirement wipes, each pinned to its
      slot's ``mint_tick``, so the tick body reconstructs the hot ring
      algebraically — :class:`~repro.core.demand.DemandView` fuses the
      wipe predicate into the activity-masking product the round performs
      anyway, and the has-demand expiry test is hoisted to three
      chunk-level reductions.  The wrapped tick carries O(1) demand state
      (down from O(M·N·B)) and adds zero full-tensor passes over the
      wrap-free body; every value is bit-identical to the full-tensor
      carry.  The chunk-boundary eviction sweep — one fused elementwise
      pass applying the chunk's accumulated wipes — grafts the cold store
      forward.
    * ``"carry"`` (ring wrapped, hot window spilled — a slot minted twice
      in one chunk): the pre-paging fallback — the full demand tensor
      joins the carry.

    ``rdp`` (a Rényi-DP ledger): the capacity carry is ``[A, B]``; each
    mint writes the block's capacity curve times its mint-plan budget
    (1.0) into every order, the debit is per order and not floored, and
    each tick reports ``exhausted_blocks`` (live blocks with no order
    left within capacity) and ``orders_alive`` (the mean count of orders
    within capacity over live blocks).  ``overdraw`` is the guarantee's
    ``max_k min_a (consumed - capacity)``.  The per-order ledger work
    runs in scope ``order_ledger``.
    """
    f32 = state.demand.dtype
    ticks = state.tick + jnp.arange(n_ticks, dtype=jnp.int32)
    retire = mode != "wrapfree"
    # Warm-started SP1 (PR 10): the per-block duals join the scan carry so
    # every tick's solve resumes from the previous tick's fixed point.
    # Minted slots reset their dual entry to 1.0 (the cold value) — the
    # new block's constraint has no history — which is the service-plane
    # mirror of the engine's birth-round reset.  Off (default) keeps the
    # carry structure, and therefore the compiled program, unchanged.
    warm = cfg.sp1_warm_start
    if rdp is not None:
        curve_g = jnp.asarray(rdp.capacity)[:, None]     # [A, 1]

    def per_order(row):
        """A mint-plan row [B] as the ledger holds it ([A, B] for RDP)."""
        if rdp is None:
            return row
        with jax.named_scope("order_ledger"):
            return row * curve_g

    if mode == "paged":
        *tick_ops, mint_tick, hot_slots = mint_ops   # [B] i32, [S, Hp/S]
        hot_slots = hot_slots.reshape(-1)            # local hot-ring slots
        spawn_b = state.spawn_tick[..., None]        # [M, N, 1]
        with jax.named_scope("ledger"):
            # the hot ring, gathered once per chunk: every in-chunk demand
            # mutation (and therefore every chunk-hoisted reduction below)
            # lives in these H columns — O(M*N*H) work, not O(M*N*B).
            hot_dem = state.demand[:, :, hot_slots]      # [M, N, H]
            mt_h = mint_tick[hot_slots][None, None, :]   # [1, 1, H]
            live_h = hot_dem > 0.0
            minted_h = mt_h != NEVER                     # padding: False
            doomed_h = live_h & (spawn_b < mt_h) & minted_h
            # has-demand expiry test, hoisted to chunk-level reductions
            # (the cold store never changes inside a chunk;
            # OR-decomposition over cold / never-wiped-hot /
            # not-yet-wiped-hot entries is exact): a pipeline still has
            # demand at tick t iff it has a cold entry, a hot entry it
            # submitted after the re-mint, or a doomed entry whose wipe
            # tick is still ahead.
            cold_any = jnp.any((state.demand > 0.0) &
                               (mint_tick[None, None, :] == NEVER), axis=-1)
            keep_any = jnp.any(live_h & minted_h & (spawn_b >= mt_h),
                               axis=-1)
            last_wipe = jnp.max(jnp.where(doomed_h, mt_h, -1), axis=-1)
            # paging telemetry (per-chunk): stale entries retired by the
            # chunk's mints + live hot-ring entries at the boundary.
            hot_evicted = block_axis.sum(jnp.sum(doomed_h.astype(jnp.int32)))
            hot_live = block_axis.sum(jnp.sum(
                (live_h & minted_h).astype(jnp.int32)))
    else:
        tick_ops = tuple(mint_ops)

    def tick_out(view, pending, capacity, budget_total, created, t,
                 lam=None):
        """Shared per-tick round + metrics, all mint modes."""
        with jax.named_scope("schedule"):
            now = t.astype(f32) * ROUND_SECONDS
            rnd = RoundInputs(
                demand=view.masked(pending),
                active=pending,
                arrival=jnp.where(pending, state.arrival, 0.0),
                loss=jnp.where(pending, state.loss, 1.0),
                capacity=capacity,
                budget_total=(budget_total if rdp is None
                              else budget_total * curve_g),
                now=now,
                # per-analyst tier weight (scan constant; all-ones in the
                # default single-tier service, which is bitwise-neutral)
                weight=state.weight,
                lam=lam, curve=state.curve)
            res = round_fn(rnd, cfg, block_axis=block_axis)
        with jax.named_scope("round_metrics"):
            mask = jnp.sum(pending, axis=1) > 0
            # RDP: the most budget a block still holds at some order, the
            # spend per analyst row and order, the guarantee's margin
            out = {
                "round_efficiency": res.efficiency,
                "round_fairness": res.fairness,
                "round_fairness_norm": ut.normalized_fairness(
                    res.utility, cfg.beta, mask),
                "round_jain": res.jain,
                "n_allocated": res.n_allocated,
                "leftover": block_axis.sum(jnp.sum(
                    res.leftover if rdp is None
                    else jnp.max(res.leftover, axis=0))),
                # realized epsilon granted per analyst row this tick — the
                # cost-cap / per-tenant spend signal (host maps rows to
                # tenants at the boundary); [M, A] on an RDP ledger
                "analyst_spend": (
                    block_axis.sum(jnp.sum(res.grants, axis=(1, 2)))
                    if rdp is None else jnp.sum(block_axis.sum(jnp.sum(
                        res.grants, axis=2))[..., None] * state.curve,
                        axis=1)),
                "conservation_gap": block_axis.max(jnp.max(jnp.abs(
                    jnp.where(created, capacity - res.consumed - res.leftover,
                              0.0)))),
                "overdraw": block_axis.max(jnp.max(
                    res.consumed - capacity if rdp is None
                    else jnp.min(res.consumed - capacity, axis=0))),
                "selected": res.selected,
            }
            # Certified swap pruning (PR 9): per-tick fallback indicator.  The
            # gate is STATIC (config-only), so it matches the sharded
            # out-specs; a baseline round under the same config carries no
            # certificate (None) and reports zero fallbacks.
            if cfg.swap_beam > 0 and cfg.refine and cfg.incremental_swap:
                out["cert_fallback"] = (
                    jnp.zeros((), jnp.int32) if res.swap_cert_ok is None
                    else (~res.swap_cert_ok).astype(jnp.int32))
            if warm:
                # solver effort per tick — a baseline round runs no SP1, so
                # it reports zero (keeps the sharded out-specs static)
                out["sp1_iters"] = (jnp.zeros((), jnp.int32)
                                    if res.sp1_iters is None
                                    else res.sp1_iters)
            if diagnostics:
                out.update(round_diagnostics(rnd, res, cfg, block_axis))
            # Observability ys — both statically gated, so the default
            # (trace_level=0, no audit) scan program is identical to a build
            # without the obs plane.  Every value is an intermediate the round
            # already computed; nothing feeds back into the carry.
            if trace_level > 0:
                out.update(trace_round_outputs(res, pending, trace_level))
            if audit:
                out["audit_x"] = res.x_pipeline          # [M, N] grant ratios
                out["audit_scale"] = (jnp.ones((), f32)
                                      if res.grant_scale is None
                                      else res.grant_scale)
        return res, out

    def order_counters(capacity, created):
        """RDP: the ledger's per-order counters after the tick's debit."""
        with jax.named_scope("order_ledger"):
            alive = jnp.sum((capacity >= 0.0).astype(jnp.int32),
                            axis=0)                            # [B]
            live = block_axis.sum(jnp.sum(created.astype(jnp.int32)))
            return {
                "exhausted_blocks": block_axis.sum(jnp.sum(
                    (created & (alive == 0)).astype(jnp.int32))),
                "orders_alive": block_axis.sum(jnp.sum(
                    jnp.where(created, alive, 0))).astype(f32)
                / jnp.maximum(live, 1).astype(f32)}

    def body(carry, xs):
        # Retirement wipes a minted slot's demand column only for
        # pipelines submitted BEFORE the mint tick — their entries
        # referenced the evicted block.  A pipeline spawning at exactly
        # the mint tick demands the block being minted then (prefetched
        # admission wrote it at the boundary), so its demand survives.
        if warm:
            *carry, lam = carry
        else:
            lam = None
        done, capacity = carry[-2:]
        # mint, retire and the pending set: the ledger side of the tick
        with jax.named_scope("ledger"):
            if mode == "paged":
                minted, budgets, budget_total, created, t = xs
                capacity = jnp.where(minted, per_order(budgets), capacity)
                view = DemandView(base=state.demand, mint_tick=mint_tick,
                                  spawn_tick=state.spawn_tick, now_tick=t)
                any_demand = cold_any | keep_any | (last_wipe > t)
            elif mode == "carry":
                demand = carry[0]
                minted, budgets, budget_total, created, t = xs
                stale = (minted[None, None, :]
                         & (state.spawn_tick < t)[..., None])
                demand = jnp.where(stale, 0.0, demand)
                capacity = jnp.where(minted, per_order(budgets), capacity)
                view = DemandView(base=demand)
                any_demand = jnp.any(demand > 0.0, axis=-1)
            elif warm:  # wrap-free + warm: mint mask rides along for the reset
                mint_add, budget_total, created, minted, t = xs
                view = DemandView(base=state.demand)
                capacity = capacity + per_order(mint_add)
            else:       # wrap-free: demand is a scan constant, mint is an add
                mint_add, budget_total, created, t = xs
                view = DemandView(base=state.demand)
                capacity = capacity + per_order(mint_add)
            if warm:
                lam = jnp.where(minted, 1.0, lam)
            pending = (state.spawn_tick <= t) & ~done
            if retire:
                # A long-pending pipeline can outlive its every demanded
                # block (all retired).  Zero demand must not read as
                # "trivially grantable" — greedy_cover would hand it a
                # phantom zero-budget grant.  It *expires* instead:
                # completed with nothing, slot recycled at the boundary,
                # counted separately in telemetry.
                has_demand = block_axis.any(any_demand)
                expired = pending & ~has_demand
                pending = pending & has_demand
        res, out = tick_out(view, pending, capacity, budget_total,
                            created, t, lam)
        with jax.named_scope("ledger"):        # debit
            if rdp is None:
                capacity = jnp.maximum(capacity - res.consumed, 0.0)
            else:
                with jax.named_scope("order_ledger"):
                    capacity = capacity - res.consumed
                out.update(order_counters(capacity, created))
            done = done | res.selected
            if retire:
                done = done | expired
                out["expired"] = expired
        if warm and res.sp1_lam is not None:
            lam = res.sp1_lam       # baselines run no SP1: pass-through
        new_carry = (done, capacity) if mode != "carry" \
            else (demand, done, capacity)
        if warm:
            new_carry = new_carry + (lam,)
        return new_carry, out

    init = (state.done, state.block_capacity)
    if mode == "carry":
        init = (state.demand,) + init
    if warm:
        init = init + (state.lam,)
    final, ys = jax.lax.scan(body, init, tuple(tick_ops) + (ticks,))
    if mode == "paged":
        # chunk-boundary eviction sweep: apply the chunk's accumulated
        # wipes to the cold page store in one fused elementwise pass
        # (shard-local on a striped mesh — mint_tick shards with the
        # ledger, so no cross-shard traffic).
        with jax.named_scope("ledger"):
            mt_b = mint_tick[None, None, :]
            swept = jnp.where((mt_b != NEVER) & (spawn_b < mt_b), 0.0,
                              state.demand)
        final = (swept,) + tuple(final)
        ys["hot_evicted"] = hot_evicted
        ys["hot_live"] = hot_live
    # Return only what changed: echoing the (unchanged) demand through the
    # jit in wrap-free mode would force XLA to copy the [M, N, B] buffer
    # into a fresh output every chunk — the host grafts the carries back
    # onto the state instead (see FlaasService.run_chunk).
    return final, ys


@functools.lru_cache(maxsize=128)
def _compiled_chunk(scheduler: str, cfg: SchedulerConfig, n_ticks: int,
                    mode: str, diagnostics: bool = False,
                    trace_level: int = 0, audit: bool = False,
                    rdp: Optional[RdpSpec] = None):
    round_fn = get_round_fn(scheduler)
    step = functools.partial(
        _chunk_metrics, cfg=cfg, round_fn=round_fn, n_ticks=n_ticks,
        mode=mode, diagnostics=diagnostics, trace_level=trace_level,
        audit=audit, rdp=rdp)
    # the outputs leave as one packed buffer (repro.service.outputs); the
    # compiled module's name (``jit_flaas_chunk``) is what a profiler
    # trace shows for the chunk program's device execution
    return jax.jit(packed(step, "flaas_chunk"))


RDP_SCHEDULERS = ("dpf", "fcfs")


def _check_rdp_config(cfg: ServiceConfig) -> None:
    """What a Rényi-DP ledger runs: DPF and FCFS (their keys have an RDP
    form), without the SP1 diagnostics and decision traces (scalar
    shares only)."""
    if cfg.scheduler not in RDP_SCHEDULERS:
        raise no_rdp_form(cfg.scheduler)
    if cfg.diagnostics or cfg.trace_level > 0:
        raise ValueError(
            f"ServiceConfig(rdp={cfg.rdp}) reports no SP1 diagnostics or "
            f"decision traces (diagnostics={cfg.diagnostics}, "
            f"trace_level={cfg.trace_level})")


def _rdp_key(rdp: Optional[RdpSpec]):
    """The ledger's identity in a checkpoint (None: scalar epsilon)."""
    return None if rdp is None else {"orders": list(rdp.orders),
                                     "eps_g": rdp.eps_g,
                                     "delta_g": rdp.delta_g}


class FlaasService:
    """Long-running scheduling service over an :class:`ArrivalTrace`."""

    def __init__(self, cfg: ServiceConfig, trace: ArrivalTrace):
        if trace.sim.pipelines_per_analyst > cfg.pipeline_slots:
            raise ValueError(
                f"trace submits {trace.sim.pipelines_per_analyst} pipelines "
                f"per analyst but rows have {cfg.pipeline_slots} slots")
        window_ticks = demand_window_ticks(trace.blocks_per_device)
        window = window_ticks * trace.blocks_per_tick
        if cfg.block_slots < window:
            raise ValueError(
                f"block ring ({cfg.block_slots}) smaller than the deepest "
                f"demand window ({window} blocks = {window_ticks} "
                f"ticks x {trace.blocks_per_tick} blocks/tick)")
        if cfg.rdp is not None:
            _check_rdp_config(cfg)
        self.cfg = cfg
        self.trace = trace
        # the mint plan's per-device budget: a Rényi-DP ledger mints the
        # spec's capacity curve into every block (scale 1.0)
        self._mint_budget = (np.ones_like(trace.device_budget)
                             if cfg.rdp is not None
                             else trace.device_budget)
        # Tenancy policy: explicit config wins; otherwise adopt the
        # trace's tier mix (a tiered trace activates SLO/aging/cost-cap
        # machinery without extra config).  None = plain single-class
        # service, bitwise-identical to the pre-tenancy behavior.
        self.tenancy = resolve_policy(
            cfg.tenancy if cfg.tenancy is not None
            else getattr(trace, "tiers", None))
        self.state = ServiceState.create(
            cfg.analyst_slots, cfg.pipeline_slots, cfg.block_slots,
            orders=0 if cfg.rdp is None else len(cfg.rdp.orders))
        self.table = SlotTable(cfg.analyst_slots, cfg.pipeline_slots)
        self.queue = AdmissionQueue(
            cfg.max_pending, max_pipelines=cfg.pipeline_slots,
            age_ticks=self.tenancy.age_ticks if self.tenancy else None)
        self.telemetry = StreamingTelemetry(cfg.latency_reservoir,
                                            seed=trace.seed)
        # host mirrors of each analyst row's tier contract (set at
        # admission; device side carries only the weight vector)
        self._row_tier = np.array(["default"] * cfg.analyst_slots, object)
        self._row_weight = np.ones(cfg.analyst_slots, np.float32)
        # host mirrors of the ledger metadata (MintPlan precomputes the
        # per-tick budget_total/created rows from these, which is what
        # keeps the wrap-free scan body engine-identical)
        self._ledger_budget = np.ones(cfg.block_slots, np.float32)
        self._ledger_birth = np.full(cfg.block_slots, -1, np.int32)
        self._wall = 0.0
        # host copy of state.tick for the round's spans (None = read it)
        self._next_tick: Optional[int] = None
        # ------------------------------------------------- observability
        self.registry = MetricsRegistry()
        self.profiler = PhaseProfiler(annotate=cfg.profile_annotations,
                                      ring_spans=ROUND_SPANS)
        self._compiled_keys = set()      # (mode, T) shapes already executed
        self.trace_sink = (DecisionTrace(cfg.trace_level, cfg.trace_ticks)
                           if cfg.trace_level > 0 else None)
        self._telemetry_sink = (JsonlSink(cfg.telemetry_path)
                                if cfg.telemetry_path else None)
        self.metrics_server = (MetricsServer(self.registry, cfg.metrics_port)
                               if cfg.metrics_port is not None else None)
        # audit: per-slot host mirrors of the admitted demand (global bids
        # + epsilon), attributed to the ledger at grant, dropped at release
        self._audit_slots: Dict[tuple, dict] = {}
        self.audit = (AuditWriter(cfg.audit_path, self._audit_meta())
                      if cfg.audit_path else None)

    # ------------------------------------------------------------ boundary
    def admit_boundary(self, n_ticks: int) -> int:
        """The host half of a chunk boundary: poll the trace across the
        upcoming ``n_ticks``, enqueue with backpressure, drain one
        admission batch into recycled slots.  Returns the chunk's first
        tick."""
        prof = self.profiler
        with prof.phase("poll"):
            tick0 = int(prof.to_host(self.state.tick))
            events = []
            for t in range(tick0, tick0 + n_ticks):
                events.extend(self.trace.step(t))
            self.queue.offer(events)
        with prof.phase("queue"):
            placements = self.queue.drain(
                self.table, self.cfg.admit_batch, now_tick=tick0,
                spend=self.telemetry.tenant_spend.get)
        if placements:
            with prof.phase("place"):
                for sub, row, _ in placements:
                    self._row_tier[row] = sub.tier
                    self._row_weight[row] = np.float32(sub.weight)
                arrays = self._placement_arrays(placements, tick0)
            with prof.phase("write"):
                weight = self._row_weight.copy()
                curve = None
                if self.cfg.rdp is not None:
                    *arrays, curve = arrays
                # admit_batch's nine uploads (ten with RDP curves)
                prof.sent((*arrays, weight) + (() if curve is None
                                               else (curve,)))
                self.state = admit_batch(self.state, *arrays, weight=weight,
                                         curve=curve)
            prof.count("admitted", len(placements))
            if self.tenancy is not None:
                self.telemetry.observe_admissions([
                    (sub.tier, max(0, tick0 - sub.submit_tick),
                     self.tenancy.spec(sub.tier).slo_admission_ticks)
                    for sub, _, _ in placements])
        self.telemetry.observe_boundary(self.queue.depth)
        return tick0

    def _slot_of(self, bids: np.ndarray) -> np.ndarray:
        """Global block id -> ledger ring slot.  Subclass hook: the sharded
        service overrides this with a striped layout (repro.shard)."""
        return bids % self.cfg.block_slots

    def _page_shards(self) -> int:
        """Shard count the hot ring is paged over.  Subclass hook: the
        sharded service pages each mesh shard's own ``bid % S`` stripe."""
        return 1

    def _ring_layout_shards(self) -> int:
        """Stripe count of the ledger-ring layout ``_slot_of`` implements
        (1 = the plain ``bid % B`` ring).  Recorded in every checkpoint so
        a restore onto a different shard count can remap the block axis
        (see :meth:`load_checkpoint`)."""
        return 1

    def _compiled_step(self, n_ticks: int, mode: str):
        """Compiled ``(state, mint_ops) -> (final_carry, ys)`` chunk step,
        ``ys`` a :class:`~repro.service.outputs.ChunkOutputs`.  Subclass
        hook: the sharded service returns a shard_map'd step."""
        return _compiled_chunk(self.cfg.scheduler, self.cfg.sched, n_ticks,
                               mode, self.cfg.diagnostics,
                               self.cfg.trace_level,
                               self.cfg.audit_path is not None,
                               self.cfg.rdp)

    def _plan_chunk(self, tick0: int, n_ticks: int):
        """(plan, mode, device mint_ops, compiled step) for the upcoming
        chunk.  Mode resolution: wrap-free chunks keep the engine-identical
        fast path; wrap chunks run paged (hot-ring carry) unless paging is
        off or the hot window spills the ring, which falls back to the
        full-tensor carry."""
        prof = self.profiler
        with prof.phase("plan"):
            plan = plan_mints(tick0, n_ticks, self.cfg.block_slots,
                              self._mint_budget,
                              self.trace.blocks_per_device,
                              self._ledger_budget, self._ledger_birth,
                              slot_fn=self._slot_of,
                              page_shards=self._page_shards()
                              if self.cfg.paged else 0)
        up = prof.to_device
        with prof.phase("upload"):
            if not plan.retire:
                mode = "wrapfree"  # budgets rows double as the capacity-add
                ops = (up(plan.budgets), up(plan.budget_total),
                       up(plan.created))
                if self.cfg.sched.sp1_warm_start:
                    # warm SP1 resets minted slots' duals even on wrap-free
                    # chunks (fresh slots hold 1.0 already, so this is a
                    # value-level no-op, but it keeps the tick body uniform)
                    ops = ops + (up(plan.mask),)
            else:
                mode = "paged" if plan.pages is not None else "carry"
                ops = (up(plan.mask), up(plan.budgets),
                       up(plan.budget_total), up(plan.created))
                if mode == "paged":
                    ops = ops + (up(plan.pages.mint_tick),
                                 up(plan.pages.hot_slots))
        return plan, mode, ops, self._compiled_step(n_ticks, mode)

    def tick_loop_fn(self, n_ticks: int):
        """The pure compiled tick loop for the upcoming chunk, as a
        zero-argument callable that does NOT advance state.  This is the
        benchmark hook that isolates the device scan from boundary work —
        symmetric with engine rounds/sec excluding ``generate_episode``."""
        _, _, ops, step = self._plan_chunk(int(self.state.tick), n_ticks)
        state = self.state
        return lambda: step(state, ops)

    # ----------------------------------------------------------- chunk step
    def run_chunk(self, n_ticks: Optional[int] = None) -> Dict[str, np.ndarray]:
        """One boundary-to-boundary step: poll/admit, scan, recycle."""
        T = self.cfg.chunk_ticks if n_ticks is None else n_ticks
        tick, self._next_tick = self._next_tick, None
        if tick is None:        # first round, after a restore or a raise
            tick = int(self.profiler.to_host(self.state.tick))
        with self.profiler.round(tick) as rnd:
            tick0, ys = self._round(T)
        self._next_tick = tick0 + T
        self._wall += rnd.seconds
        self.registry.histogram(
            "flaas_chunk_seconds",
            "Boundary-to-boundary chunk wall time").observe(rnd.seconds)
        if self.audit is not None:
            self.audit.flush()
        if self.metrics_server is not None:
            self.publish_metrics()
        if self._telemetry_sink is not None:
            self._export_telemetry()
        return ys

    def _round(self, T: int):
        """The spans of one round (``ROUND_SPANS``); returns ``(tick0,
        ys)``."""
        prof = self.profiler
        with prof.phase("admit_drain"):
            tick0 = self.admit_boundary(T)

        # plan this chunk's block mints; run the compiled scan; graft the
        # changed carries + ledger-metadata mirrors back onto the state.
        # (In paged mode final[0] is the cold store with the hot ring
        # already swept back in — the boundary eviction sweep.)
        with prof.phase("plan_mints"):
            plan, mode, ops, step = self._plan_chunk(tick0, T)
        key = (self.cfg.scheduler, mode, T)
        phase = ("chunk_execute" if key in self._compiled_keys
                 else "chunk_compile_execute")
        self._compiled_keys.add(key)
        with prof.phase(phase):
            final, ys = step(self.state, ops)
        with prof.phase("state_graft"):
            self._ledger_budget = plan.next_budget
            self._ledger_birth = plan.next_birth
            warm = self.cfg.sched.sp1_warm_start
            if warm:
                *final, lam_f = final
            self.state = dataclasses.replace(
                self.state,
                demand=final[0] if plan.retire else self.state.demand,
                done=final[-2], block_capacity=final[-1],
                lam=lam_f if warm else self.state.lam,
                block_budget=prof.to_device(plan.next_budget),
                block_birth=prof.to_device(plan.next_birth),
                tick=prof.to_device(tick0 + T, jnp.int32))
        with prof.phase("host_sync"):
            with prof.phase("device_wait"):
                jax.block_until_ready(ys)
            with prof.phase("copy_out"):
                ys = copy_to_host(ys, prof)
        with prof.phase("recycle"):
            ys = self._recycle(ys, plan, mode, tick0, T)
        with prof.phase("telemetry_fold", counters=True):
            self.telemetry.observe_chunk(ys)
        return tick0, ys

    def _recycle(self, ys, plan, mode: str, tick0: int, T: int):
        """The host half after the sync: drain the observability ys, check
        conservation, fold the per-chunk telemetry, recycle granted and
        expired slots.  Returns the ys left for the telemetry fold."""
        # chunk-boundary observability drains: decision traces out of the
        # ys dict into the host ring; audit grant ratios held for the
        # grant-attribution pass below.
        ys, traces = split_trace_ys(ys)
        if self.trace_sink is not None:
            self.trace_sink.extend(tick0, traces)
        audit_x = ys.pop("audit_x", None)            # [T, M, N]
        audit_scale = ys.pop("audit_scale", None)    # [T]
        if self.cfg.validate:
            self._check_conservation(ys)

        # certified swap pruning: fold this chunk's per-tick fallback
        # indicators (present only when cfg.sched.swap_beam > 0)
        cert_fb = ys.pop("cert_fallback", None)
        if cert_fb is not None:
            self.telemetry.observe_swap_certificates(cert_fb)

        # warm SP1: fold this chunk's per-tick solver iteration counts +
        # the mint-driven dual resets (present only when warm-start is on)
        sp1_iters = ys.pop("sp1_iters", None)
        if sp1_iters is not None:
            self.telemetry.observe_sp1(sp1_iters,
                                       resets=int(plan.mask.sum()))

        # Rényi-DP ledger counters (present only with cfg.rdp)
        exhausted = ys.pop("exhausted_blocks", None)
        orders_alive = ys.pop("orders_alive", None)
        if exhausted is not None:
            self.telemetry.observe_rdp(exhausted, orders_alive)

        # paging telemetry: hot-ring size/evictions/occupancy per chunk
        self.telemetry.observe_chunk_mode(mode, T)
        hot_evicted = ys.pop("hot_evicted", None)
        hot_live = ys.pop("hot_live", None)
        if hot_evicted is not None:
            H = plan.pages.hot_size
            MN = self.cfg.analyst_slots * self.cfg.pipeline_slots
            self.telemetry.observe_paging(
                pages_swept=H, slots_evicted=int(hot_evicted.sum()),
                hot_occupancy=float(hot_live.mean()) / max(MN * H, 1))

        # 4. recycle granted + expired slots, record grant latencies and
        #    per-tenant spend, fold telemetry.
        selected = ys.pop("selected")                      # [T, M, N]
        expired = ys.pop("expired", None)
        spend_t = ys.pop("analyst_spend")                  # [T, M]
        if self.tenancy is not None:
            # rows still own their tenants here (release happens below);
            # an RDP row's spend is its largest over the orders
            spend_m = spend_t.sum(axis=0)
            if spend_m.ndim == 2:
                spend_m = spend_m.max(axis=-1)
            for m in np.nonzero(spend_m > 0)[0]:
                owner = int(self.table.row_owner[m])
                if owner >= 0:
                    self.telemetry.observe_spend(
                        owner, str(self._row_tier[m]), float(spend_m[m]))
        done_now = selected.any(axis=0)
        if done_now.any():
            grant_tick = tick0 + np.argmax(selected, axis=0)
            lat = grant_tick[done_now] - self.table.submit_tick[done_now]
            self.telemetry.observe_latencies(lat)
            if self.tenancy is not None:
                tiers = self._row_tier[np.where(done_now)[0]]
                self.telemetry.observe_first_grants([
                    (str(t), int(l),
                     self.tenancy.spec(str(t)).slo_first_grant_ticks)
                    for t, l in zip(tiers, lat)])
            if self.audit is not None:
                # attribute every grant to its global blocks BEFORE the
                # slot-table release below recycles the rows
                self._audit_grants(tick0, selected, audit_x, audit_scale)
        release = done_now
        if expired is not None and expired.any():
            expired_now = expired.any(axis=0)
            self.telemetry.observe_expired(
                int((expired_now & self.table.occupied).sum()))
            release = release | expired_now
        self.table.release_done(release)
        if self._audit_slots:
            for m, n in zip(*np.nonzero(release)):
                self._audit_slots.pop((int(m), int(n)), None)
        return ys

    # ------------------------------------------------------------ main loop
    def run(self, n_ticks: int) -> Dict:
        """Run ``n_ticks`` service ticks; returns the telemetry summary."""
        end = int(self.state.tick) + n_ticks
        while int(self.state.tick) < end:
            self.run_chunk(min(self.cfg.chunk_ticks,
                               end - int(self.state.tick)))
        return self.summary()

    def summary(self) -> Dict:
        return self.telemetry.summary(admission=self.queue.stats.snapshot(),
                                      wall_seconds=self._wall)

    # -------------------------------------------------------- observability
    def publish_metrics(self) -> None:
        """Fold the current summary + profiler totals into the metrics
        registry (the ``flaas_*`` catalog).  Runs automatically at every
        chunk boundary while the exporter endpoint is up; call it manually
        to inspect ``service.registry`` without one."""
        absorb_summary(self.registry, self.summary())
        self.profiler.publish(self.registry)

    def close(self) -> None:
        """Orderly shutdown of the observability plane: flush + fsync the
        telemetry sink and audit ledger, stop the metrics endpoint.  The
        service itself stays usable (sinks do not reopen).  Idempotent;
        also runs on ``with FlaasService(...) as service:`` exit."""
        if self.metrics_server is not None:
            self.publish_metrics()
            self.metrics_server.close()
            self.metrics_server = None
        if self.audit is not None:
            self.audit.close()
            self.audit = None
        if self._telemetry_sink is not None:
            self._telemetry_sink.close()
            self._telemetry_sink = None

    def __enter__(self) -> "FlaasService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _audit_meta(self) -> Dict:
        """Budget geometry + writer identity for the audit ledger's
        ``open`` record (what the offline verifier maps bids to budgets
        with)."""
        return {
            "device_budget": [float(b) for b in
                              np.asarray(self.trace.device_budget).ravel()],
            "blocks_per_device": int(self.trace.blocks_per_device),
            "n_devices": int(self.trace.blocks_per_tick //
                             self.trace.blocks_per_device),
            "block_slots": int(self.cfg.block_slots),
            "layout_shards": self._ring_layout_shards(),
            "scheduler": self.cfg.scheduler,
            "tick": int(self.state.tick),
            **({} if self.cfg.rdp is None else {"rdp": {
                "orders": list(self.cfg.rdp.orders),
                "capacity": [float(c) for c in self.cfg.rdp.capacity]}}),
        }

    def _audit_grants(self, tick0: int, selected: np.ndarray,
                      audit_x: np.ndarray, audit_scale: np.ndarray) -> None:
        """Write one ledger record per pipeline granted this chunk.

        The admission mirror holds each slot's *global* block ids and
        epsilon demand; the entries still live at the grant tick are
        exactly those whose slot had not been re-minted yet (block
        ``bid``'s successor ``bid + B`` mints at tick ``(bid + B) / bpr``
        — the same wipe predicate the scan body applies), so the host
        attribution reproduces the device grant epsilon-for-epsilon."""
        B = self.cfg.block_slots
        bpr = self.trace.blocks_per_tick
        rel = np.argmax(selected, axis=0)                  # [M, N]
        for m, n in zip(*np.nonzero(selected.any(axis=0))):
            rec = self._audit_slots.get((int(m), int(n)))
            if rec is None:
                continue        # admitted before auditing was enabled
            tr = int(rel[m, n])
            gt = tick0 + tr
            x = np.float32(audit_x[tr, m, n]) * np.float32(audit_scale[tr])
            live = (rec["bids"] + B) // bpr > gt
            if x <= 0.0 or not live.any():
                continue        # selected with zero realized grant
            eps = rec["eps"][live].astype(np.float32) * x
            self.audit.grant(
                tick=gt, analyst=rec["analyst"], pipeline=int(n),
                tier=rec["tier"], x=float(x),
                bids=rec["bids"][live], eps=eps, curve=rec.get("curve"))

    # ----------------------------------------------------------- durability
    def checkpoint_host_state(self) -> Dict:
        """Everything the device pytree does not carry: ledger-metadata
        mirrors, slot table, admission queue, telemetry, and the trace
        cursor.  Restoring this plus the device state into a fresh process
        resumes the service bitwise (same grants, same draws, same
        summary fingerprint) — see :meth:`load_checkpoint`."""
        return {
            "kind": "flaas-service",
            "version": _CHECKPOINT_VERSION,
            "rdp": _rdp_key(self.cfg.rdp),
            "layout_shards": self._ring_layout_shards(),
            "geometry": (self.cfg.analyst_slots, self.cfg.pipeline_slots,
                         self.cfg.block_slots),
            "ledger_budget": self._ledger_budget.copy(),
            "ledger_birth": self._ledger_birth.copy(),
            "wall": self._wall,
            "table": self.table.state_dict(),
            "queue": self.queue.state_dict(),
            "telemetry": self.telemetry.state_dict(),
            "trace": self.trace.state_dict(),
            "row_tier": [str(t) for t in self._row_tier],
            "row_weight": self._row_weight.copy(),
            "tenancy": policy_key(self.tenancy),
            # v3 observability plane: registry counters resume bitwise,
            # profiler wall totals accumulate across restores, and the
            # audit mirrors keep not-yet-granted pipelines attributable
            # after a restore (the ledger file itself is append-only on
            # disk — reopening continues its hash chain).
            "obs": {
                "registry": self.registry.state_dict(),
                "profiler": self.profiler.state_dict(),
                "audit_slots": {k: {kk: (vv.copy()
                                         if isinstance(vv, np.ndarray)
                                         else vv)
                                    for kk, vv in rec.items()}
                                for k, rec in self._audit_slots.items()},
            },
        }

    def save_checkpoint(self, manager, metadata: Optional[Dict] = None) -> int:
        """Checkpoint the full service at the current chunk boundary via a
        :class:`~repro.checkpoint.manager.CheckpointManager`; returns the
        step (= tick) saved under."""
        step = int(self.state.tick)
        meta = {"scheduler": self.cfg.scheduler,
                "layout_shards": self._ring_layout_shards(),
                **(metadata or {})}
        with self.profiler.phase("checkpoint_save"):
            manager.save(step, self.state, metadata=meta,
                         host_state=self.checkpoint_host_state())
        return step

    def load_checkpoint(self, manager, step: Optional[int] = None) -> int:
        """Restore device + host state from ``manager`` into this (freshly
        constructed, same-config) service and return the restored tick.

        Elastic hand-off: a checkpoint written under an ``S``-striped ring
        layout restores onto an ``S'``-striped one by permuting every
        block-axis array with :func:`repro.shard.state.remap_ring` — both
        layouts place block ``bid`` as a function of ``bid % B`` only, so
        the permutation is exact and scheduling continues unchanged."""
        device, host, step = manager.restore(self.state, step=step,
                                             with_host=True)
        if step is None:
            raise ValueError(f"no checkpoint found in {manager.dir}")
        if not isinstance(host, dict) or host.get("kind") != "flaas-service":
            raise ValueError(
                "checkpoint carries no service host state (was it saved "
                "with FlaasService.save_checkpoint?)")
        if host.get("version") not in _COMPAT_VERSIONS:
            raise ValueError(
                f"service checkpoint version {host.get('version')} not "
                f"supported (accepted: {_COMPAT_VERSIONS})")
        if host.get("rdp") != _rdp_key(self.cfg.rdp):
            raise ValueError(
                f"checkpoint ledger {host.get('rdp') or 'scalar epsilon'} "
                f"does not match the configured "
                f"{_rdp_key(self.cfg.rdp) or 'scalar epsilon'} (Rényi-DP "
                f"and scalar ledgers do not convert)")
        geometry = (self.cfg.analyst_slots, self.cfg.pipeline_slots,
                    self.cfg.block_slots)
        if tuple(host["geometry"]) != geometry:
            raise ValueError(
                f"checkpoint geometry {tuple(host['geometry'])} != "
                f"configured {geometry}")
        ledger_budget = np.asarray(host["ledger_budget"], np.float32)
        ledger_birth = np.asarray(host["ledger_birth"], np.int32)
        src, dst = int(host["layout_shards"]), self._ring_layout_shards()
        if src != dst:
            # lazy import: repro.shard imports this module
            from repro.shard.state import remap_ring
            idx = remap_ring(src, dst, self.cfg.block_slots)
            device = dataclasses.replace(
                device,
                demand=np.asarray(device.demand)[:, :, idx],
                block_budget=np.asarray(device.block_budget)[idx],
                block_capacity=np.asarray(device.block_capacity)[..., idx],
                block_birth=np.asarray(device.block_birth)[idx],
                lam=np.asarray(device.lam)[idx])
            ledger_budget = ledger_budget[idx]
            ledger_birth = ledger_birth[idx]
        self.state = jax.tree.map(jnp.asarray, device)
        self._ledger_budget = ledger_budget.copy()
        self._ledger_birth = ledger_birth.copy()
        self._wall = float(host["wall"])
        self._next_tick = None
        self.table.load_state_dict(host["table"])
        self.queue.load_state_dict(host["queue"])
        self.telemetry.load_state_dict(host["telemetry"])
        self.trace.load_state_dict(host["trace"])
        if "row_tier" in host:
            self._row_tier = np.array([str(t) for t in host["row_tier"]],
                                      object)
            self._row_weight = np.asarray(host["row_weight"],
                                          np.float32).copy()
        else:
            # v1 (pre-tenancy) checkpoint: every row is the neutral default
            # tier, matching the all-ones weight leaf the device template
            # filled in (see checkpoint.manager._unflatten).
            self._row_tier = np.array(["default"] * self.cfg.analyst_slots,
                                      object)
            self._row_weight = np.ones(self.cfg.analyst_slots, np.float32)
        # v3 observability plane (pre-v3 checkpoints: counters start
        # fresh; pipelines admitted before the restore are simply absent
        # from the audit ledger — conservation is an upper bound, so the
        # verifier stays sound).
        obs = host.get("obs", {})
        if "registry" in obs:
            self.registry.load_state_dict(obs["registry"])
        if "profiler" in obs:
            self.profiler.load_state_dict(obs["profiler"])
        self._audit_slots = {
            tuple(k): {"analyst": int(rec["analyst"]),
                       "tier": str(rec["tier"]),
                       "bids": np.asarray(rec["bids"], np.int64).copy(),
                       "eps": np.asarray(rec["eps"], np.float32).copy(),
                       **({"curve": np.asarray(rec["curve"], np.float32)
                           .copy()} if "curve" in rec else {})}
            for k, rec in obs.get("audit_slots", {}).items()}
        return step

    # -------------------------------------------------------------- helpers
    def _export_telemetry(self) -> None:
        """Append one NaN-safe JSON line of the running summary to
        ``cfg.telemetry_path`` (chunk-boundary cadence, append-only so an
        external collector can tail the file).  The sink keeps one
        persistent handle — flushed per record, fsynced by
        :meth:`close` — and appends to pre-existing files, so restarts
        and checkpoint restores extend one continuous stream."""
        self._telemetry_sink.write(
            {"tick": int(self.state.tick), **self.summary()})

    def _placement_arrays(self, placements, boundary_tick: int):
        """Operands for one admission batch: ``[M, N]`` slot-metadata
        tables + flat COO demand triples (see
        :func:`repro.service.state.admit_batch`), and on a Rényi-DP
        ledger the ``[M, N, A]`` table of the slots' demand curves."""
        M, N = self.cfg.analyst_slots, self.cfg.pipeline_slots
        B = self.cfg.block_slots
        rdp = self.cfg.rdp
        if rdp is not None:
            A = len(rdp.orders)
            curve = np.zeros((M, N, A), np.float32)
        mask = np.zeros((M, N), bool)
        loss = np.zeros((M, N), np.float32)
        arr_s = np.zeros((M, N), np.float32)
        spawn = np.zeros((M, N), np.int32)
        bpr = self.trace.blocks_per_tick
        rows, cols, bids, eps = [], [], [], []
        for sub, row, cs in placements:
            spawn_tick = max(sub.submit_tick, boundary_tick)
            arrival = self.trace.arrival_seconds(sub.submit_tick)
            if rdp is not None:
                curves = np.asarray(
                    () if sub.curves is None else sub.curves, np.float32)
                if curves.shape != (len(cs), A):
                    raise ValueError(
                        f"analyst {sub.analyst}'s submission carries curves "
                        f"of shape {curves.shape}; the RDP ledger needs one "
                        f"per pipeline over its {A} orders {rdp.orders}")
                curve[row, cs] = curves
            for j, c in enumerate(cs):
                mask[row, c] = True
                loss[row, c] = sub.loss[j]
                arr_s[row, c] = arrival
                spawn[row, c] = spawn_tick
                # A submission deferred across a ring wrap may demand
                # blocks that have been (or are about to be) evicted;
                # their slots now/soon belong to newer blocks.  Writing
                # `bid % B` blindly would alias that stale demand onto
                # blocks the pipeline never asked for — drop it instead.
                # Keep an entry only if (1) its block has not already been
                # evicted (slot occupant's birth <= the bid's mint tick)
                # and (2) the block outlives the pipeline's activation
                # (its successor `bid + B` mints strictly after
                # spawn_tick; evictions after activation are handled by
                # the in-scan stale wipe, which is strict in spawn_tick).
                slots = self._slot_of(sub.bids[j])
                keep = ((self._ledger_birth[slots] <= sub.bids[j] // bpr) &
                        ((sub.bids[j] + B) // bpr > spawn_tick))
                if self.audit is not None:
                    # audit mirror: global (layout-independent) bids + the
                    # epsilon written to the device, for grant attribution
                    self._audit_slots[(int(row), int(c))] = {
                        "analyst": int(sub.analyst), "tier": str(sub.tier),
                        "bids": np.asarray(sub.bids[j],
                                           np.int64)[keep].copy(),
                        "eps": np.asarray(sub.eps[j],
                                          np.float32)[keep].copy()}
                    if rdp is not None:
                        self._audit_slots[(int(row), int(c))]["curve"] = \
                            curve[row, c].copy()
                rows.append(np.full(int(keep.sum()), row, np.int64))
                cols.append(np.full(int(keep.sum()), c, np.int64))
                bids.append(slots[keep])
                eps.append(sub.eps[j][keep])
        out = (mask, loss, arr_s, spawn, np.concatenate(rows),
               np.concatenate(cols), np.concatenate(bids),
               np.concatenate(eps))
        return out if rdp is None else out + (curve,)

    def _check_conservation(self, ys) -> None:
        gap = float(np.max(ys["conservation_gap"]))
        over = float(np.max(ys["overdraw"]))
        if gap > 1e-4 or over > 1e-4:
            raise AssertionError(
                f"budget conservation violated under "
                f"{self.cfg.scheduler!r} at tick {int(self.state.tick)}: "
                f"gap={gap:.3e} overdraw={over:.3e}")
