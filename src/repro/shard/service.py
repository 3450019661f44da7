"""The sharded service plane: FlaasService over a block-sharded mesh.

:class:`ShardedFlaasService` is the scale-out server: the block-ledger
ring and the demand tensor's block axis are partitioned over a 1-D device
mesh (:mod:`repro.shard.state`), and each chunk's tick loop runs as ONE
``shard_map`` program in which

* every per-block sweep (waterfill dual ascent, SP2 feasibility scans,
  capacity debits, mint/retire selects) touches only the shard's local
  ``B/S`` stripe — this is the memory and FLOP win;
* the analyst-level reductions (``mu_i`` row-max, matvec partials, the
  greedy pass's global visit order, KKT errors) finish with small
  ``psum``/``pmax`` collectives whose payloads are analyst- or
  pipeline-indexed, never block-indexed;
* mints stay **shard-local** by construction of the striped ring layout
  (shard ``s`` owns the ``bid % S == s`` stripe), so ring retirement needs
  no cross-shard traffic at all.

Admission stays on the host exactly as in :class:`FlaasService`: at every
chunk boundary the server all-gathers per-shard free-slot counts
(:func:`gather_shard_view`) — the signal a multi-host admission queue
needs — and then drains the same FIFO queue with the same backpressure
rules, so the sharded and unsharded services admit identically.

Parity contract (pinned by ``tests/test_shard_service.py``): on a 1-shard
mesh the layout and the arithmetic are bit-identical to
:class:`FlaasService`; on an N-shard mesh every metric matches to 1e-5
(the residual is float reassociation in psum partial sums) for all four
schedulers, ring wraps included.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.blockaxis import BlockAxis
from repro.core.registry import get_round_fn
from repro.core.scheduler import SchedulerConfig
from repro.obs.tracing import trace_ys_keys
from repro.service.outputs import packed
from repro.service.server import FlaasService, ServiceConfig, _chunk_metrics
from repro.service.state import NEVER
from repro.service.traces import ArrivalTrace

from .state import (AXIS, ShardedServiceState, mesh_shards, shard_mesh,
                    state_specs)

_METRIC_KEYS = ("round_efficiency", "round_fairness", "round_fairness_norm",
                "round_jain", "n_allocated", "leftover", "analyst_spend",
                "conservation_gap", "overdraw", "selected")
# diagnostics keys carrying a (sharded) block axis, by trailing-dims spec
_DIAG_SPECS = {"gamma_i": P(None, None, AXIS), "granted_i": P(None, None, AXIS),
               "cap_frac": P(None, AXIS)}
_DIAG_REPLICATED = ("utility", "analyst_mask", "a_i", "mu_i", "x_analyst",
                    "sp1_violation")


def _ys_specs(mode: str, diagnostics: bool, trace_level: int = 0,
              audit: bool = False, cert: bool = False,
              warm: bool = False, rdp: bool = False) -> Dict[str, P]:
    ys = {k: P() for k in _METRIC_KEYS}
    if rdp:
        # Rényi-DP ledger counters: post-psum scalars
        ys["exhausted_blocks"] = P()
        ys["orders_alive"] = P()
    if cert:
        # certified swap pruning: the per-tick fallback indicator is the
        # negation of an all-analyst AND over post-collective verdicts —
        # replicated across the mesh by construction.
        ys["cert_fallback"] = P()
    if warm:
        # warm SP1: the dual-ascent iteration count is driven by the
        # globally-reduced KKT error, so every shard exits its while_loop
        # at the same count — replicated by construction.
        ys["sp1_iters"] = P()
    if mode != "wrapfree":
        ys["expired"] = P()
    if mode == "paged":     # paging telemetry: post-psum scalars
        ys["hot_evicted"] = P()
        ys["hot_live"] = P()
    if diagnostics:
        ys.update({k: P() for k in _DIAG_REPLICATED})
        ys.update(_DIAG_SPECS)
    # decision-trace / audit ys (repro.obs): every value is an analyst- or
    # pipeline-indexed post-collective aggregate — replicated across the
    # mesh by construction, so the per-shard registry deltas fold at this
    # (existing) chunk-boundary gather with no extra collectives.
    ys.update({k: P() for k in trace_ys_keys(trace_level)})
    if audit:
        ys["audit_x"] = P()
        ys["audit_scale"] = P()
    return ys


def _op_specs(mode: str, warm: bool = False):
    """shard_map in_specs for the mint-op tuple of ``mode``.  The [T, B]
    rows shard their slot axis; the paged extras — the [B] per-slot
    ``mint_tick`` vector and the [S, Hp/S] local hot-ring slot table —
    shard with the ledger, handing each shard its own stripe's retirement
    schedule.  Warm SP1 appends the [T, B] mint mask to wrap-free chunks
    (the dual-reset schedule), sharded like every other slot-axis row."""
    if mode == "paged":
        return (P(None, AXIS),) * 4 + (P(AXIS), P(AXIS, None))
    if mode == "wrapfree":
        return (P(None, AXIS),) * (4 if warm else 3)
    return (P(None, AXIS),) * 4


def _sharded_body(scheduler: str, cfg: SchedulerConfig, n_ticks: int,
                  mode: str, diagnostics: bool, mesh,
                  trace_level: int = 0, audit: bool = False, rdp=None):
    """The shard_map'd chunk body: the SAME ``_chunk_metrics`` as
    ``server._compiled_chunk``, with every block-axis operand passed as a
    local stripe and the cross-shard reductions routed through
    ``BlockAxis(AXIS)``.  In paged mode each shard applies its own
    stripe's retirement schedule (``mint_tick`` shards with the ledger)
    and sweeps its own cold store — retirement adds no cross-shard
    traffic.  A Rényi-DP ledger (``rdp``) stripes its ``[A, B]`` capacity
    on B: every per-order fits-check and debit stays shard-local."""
    round_fn = get_round_fn(scheduler)
    fn = functools.partial(
        _chunk_metrics, cfg=cfg, round_fn=round_fn, n_ticks=n_ticks,
        mode=mode, diagnostics=diagnostics, trace_level=trace_level,
        audit=audit, block_axis=BlockAxis(AXIS), rdp=rdp)
    cap = P(None, AXIS) if rdp is not None else P(AXIS)
    carry = (P(None, None, AXIS), P(), cap) if mode != "wrapfree" \
        else (P(), cap)
    warm = cfg.sp1_warm_start
    if warm:
        carry = carry + (P(AXIS),)      # the [B] dual stripe rides along
    cert = (cfg.swap_beam > 0 and cfg.refine and cfg.incremental_swap)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(state_specs(rdp is not None), _op_specs(mode, warm)),
        out_specs=(carry, _ys_specs(mode, diagnostics, trace_level, audit,
                                    cert, warm, rdp is not None)),
        # replication of the P() outputs is guaranteed by construction
        # (they are all post-collective values).
        check_vma=False)


@functools.lru_cache(maxsize=64)
def _sharded_chunk(scheduler: str, cfg: SchedulerConfig, n_ticks: int,
                   mode: str, diagnostics: bool, mesh,
                   trace_level: int = 0, audit: bool = False, rdp=None):
    """Compiled analogue of ``server._compiled_chunk``: the shard_map'd
    body, its replicated outputs packed into one buffer in the same jit
    (the block-sharded diagnostics, off by default, are gathered into
    it)."""
    return jax.jit(packed(_sharded_body(
        scheduler, cfg, n_ticks, mode, diagnostics, mesh, trace_level,
        audit, rdp), "flaas_chunk"))


@functools.lru_cache(maxsize=16)
def _shard_view_fn(mesh, rdp: bool = False):
    """Per-shard free-slot census, all-gathered so every shard (and the
    host) sees the same admission picture: live minted blocks per shard
    (on a Rényi-DP ledger, with budget left at some order) plus the
    replicated pipeline-slot occupancy."""
    def census(capacity, birth, spawn_tick, done):
        if rdp:
            capacity = jnp.max(capacity, axis=0)
        live = jnp.sum(((birth >= 0) & (capacity > 0.0)).astype(jnp.int32))
        occupied = jnp.sum(((spawn_tick != NEVER) & ~done).astype(jnp.int32))
        return jax.lax.all_gather(live, AXIS), occupied

    return jax.jit(jax.shard_map(
        census, mesh=mesh,
        in_specs=(P(None, AXIS) if rdp else P(AXIS), P(AXIS), P(), P()),
        out_specs=(P(), P()), check_vma=False))


def gather_shard_view(service: "ShardedFlaasService"):
    """(per-shard live-block counts ``[S]``, free pipeline slots) from the
    device — the chunk-boundary all-gather behind sharded admission."""
    st = service.state                    # always mesh-committed (setter)
    live, occupied = _shard_view_fn(service.mesh, st.curve is not None)(
        st.block_capacity, st.block_birth, st.spawn_tick, st.done)
    M, N, _ = st.demand.shape
    return np.asarray(live), int(M * N - int(occupied))


class ShardedFlaasService(FlaasService):
    """Long-running scheduling service with a block-sharded ledger.

    Drop-in for :class:`FlaasService` (same config, traces, telemetry,
    replay machinery); ``mesh``/``n_shards`` pick the shard layout.
    ``cfg.block_slots`` must divide evenly over the shards."""

    def __init__(self, cfg: ServiceConfig, trace: ArrivalTrace, *,
                 mesh=None, n_shards: int | None = None):
        if mesh is None:
            mesh = shard_mesh(n_shards)
        elif n_shards is not None and mesh_shards(mesh) != n_shards:
            raise ValueError(
                f"mesh has {mesh_shards(mesh)} shards but n_shards="
                f"{n_shards} was also given")
        # ShardedServiceState owns the layout invariants (ring
        # divisibility, striped slot map, mesh re-commit); the `state`
        # property below routes every host graft through it, starting
        # with the base constructor's fresh-state assignment.
        self.sharded = None
        self._boot_mesh = mesh
        super().__init__(cfg, trace)
        self.shard_live_blocks = np.zeros(mesh_shards(mesh), np.int64)
        self.free_pipeline_slots = cfg.analyst_slots * cfg.pipeline_slots

    # ------------------------------------------------------------- layout
    @property
    def mesh(self):
        return (self.sharded.mesh if self.sharded is not None
                else self._boot_mesh)

    @property
    def n_shards(self) -> int:
        return self.sharded.n_shards

    @property
    def state(self):
        return self.sharded.state

    @state.setter
    def state(self, value):
        # every assignment (fresh create, admit batch, post-chunk graft)
        # re-commits to the block-axis layout; already-placed leaves are
        # no-ops.
        if self.sharded is None:
            self.sharded = ShardedServiceState.commit(value, self._boot_mesh)
        else:
            self.sharded = self.sharded.put(value)

    def _slot_of(self, bids: np.ndarray) -> np.ndarray:
        return self.sharded.slot_of(bids)

    def _page_shards(self) -> int:
        # each mesh shard pages its own `bid % S` stripe: the hot-ring
        # gather, wipes and boundary sweep are entirely shard-local.
        return mesh_shards(self.mesh)

    def _ring_layout_shards(self) -> int:
        # checkpoints record the stripe count; load_checkpoint remaps the
        # block axis when restoring onto a different shard count (the
        # `state` setter then re-commits the permuted state to this mesh).
        return mesh_shards(self.mesh)

    # -------------------------------------------------------------- chunk
    def _compiled_step(self, n_ticks: int, mode: str):
        step = _sharded_chunk(self.cfg.scheduler, self.cfg.sched, n_ticks,
                              mode, self.cfg.diagnostics, self.mesh,
                              self.cfg.trace_level,
                              self.cfg.audit_path is not None,
                              self.cfg.rdp)
        shardings = tuple(NamedSharding(self.mesh, spec)
                          for spec in _op_specs(
                              mode, self.cfg.sched.sp1_warm_start))

        def run(state, ops):
            # state is mesh-committed by the `state` setter; the mint-plan
            # operands are host-built per chunk and committed here.
            ops = tuple(jax.device_put(op, s)
                        for op, s in zip(ops, shardings))
            return step(state, ops)

        return run

    # ----------------------------------------------------------- boundary
    def admit_boundary(self, n_ticks: int) -> int:
        # sharded admission: all-gather the per-shard ledger census before
        # the host drains the queue — placement/backpressure then proceed
        # exactly as in the unsharded service (the queue is host-global).
        self.shard_live_blocks, self.free_pipeline_slots = \
            gather_shard_view(self)
        return super().admit_boundary(n_ticks)

    def summary(self) -> Dict:
        out = super().summary()
        out["sharding"] = {
            "n_shards": self.n_shards,
            "blocks_per_shard": self.cfg.block_slots // self.n_shards,
            "shard_live_blocks": [int(x) for x in self.shard_live_blocks],
            "free_pipeline_slots": int(self.free_pipeline_slots),
            "pending_pipelines": self.queue.pending_pipelines(),
        }
        return out
