"""Block-axis sharding of the service-plane state.

The block ledger (``block_budget`` / ``block_capacity`` / ``block_birth``,
all ``[B]``) and the ``[M, N, B]`` demand tensor are embarrassingly
shardable along the block axis: every per-block quantity (capacity,
waterfill multipliers, feasibility residuals) is independent until the
analyst-level reduction.  This module pins that layout down:

* **Mesh**: a 1-D device mesh with axis :data:`AXIS` (``"shard"``), built
  over any device subset via :func:`shard_mesh` (so a 1-shard parity mesh
  and an N-shard mesh coexist in one process).
* **Striped ring**: global block ``bid`` lives on shard ``bid % S`` at
  local slot ``(bid // S) % (B/S)`` (:func:`ring_slots`).  Each tick mints
  ``blocks_per_tick`` consecutive bids, so mints spread round-robin over
  shards and every mint/retire is **shard-local**: the slot of ``bid`` is
  reused exactly by ``bid + B``, the same retirement horizon as the
  unsharded ring (``bid % B``), which is what keeps the host-side
  eviction bookkeeping (:meth:`FlaasService._placement_arrays`) valid
  unchanged.  With ``S = 1`` the layout degenerates to ``bid % B``
  bit-for-bit.
* **NamedShardings**: :func:`state_shardings` gives every ledger array a
  block-axis ``NamedSharding`` and replicates the ``[M, N]`` pipeline
  tables (:class:`ServiceState` is ~``M*N*B`` floats — the demand tensor
  dominates, and it shards ``1/S`` per device).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.distributed import compat
from repro.service.state import ServiceState

AXIS = "shard"


def shard_mesh(n_shards: int | None = None, devices=None):
    """A 1-D ``(AXIS,)`` mesh over ``n_shards`` devices (default: all).

    Submeshes are explicit: ``shard_mesh(1)`` on an 8-device host is the
    1-shard parity oracle, ``shard_mesh(4)`` a 4-way shard of the same
    ledger."""
    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices) if n_shards is None else int(n_shards)
    if not 1 <= n <= len(devices):
        raise ValueError(
            f"n_shards={n} but only {len(devices)} devices are visible "
            f"(CPU runners: XLA_FLAGS=--xla_force_host_platform_device_count)")
    return compat.make_mesh((n,), (AXIS,), devices=devices[:n])


def mesh_shards(mesh) -> int:
    return int(mesh.shape[AXIS])


def ring_slots(bids, n_shards: int, block_slots: int):
    """Striped global-slot layout: ``bid -> (bid % S) * (B/S) + (bid // S)
    % (B/S)``.  Shard ``s`` owns the contiguous global range
    ``[s*B/S, (s+1)*B/S)``, i.e. exactly the ``bid % S == s`` stripe."""
    bids = np.asarray(bids)
    per_shard = block_slots // n_shards
    return (bids % n_shards) * per_shard + (bids // n_shards) % per_shard


def remap_ring(n_from: int, n_to: int, block_slots: int) -> np.ndarray:
    """Gather index remapping every block-axis array from the
    ``n_from``-striped ring layout to the ``n_to``-striped one:
    ``new = old[idx]`` puts each block's slot where
    :func:`ring_slots`(bid, n_to) expects it.

    This is what makes shard join/leave a checkpoint-remap-restore: both
    layouts are functions of ``bid % B`` only (the ``bid + B`` reuse
    horizon), so a slot's occupant under the old stripe count has exactly
    one home under the new one.  The old slot ``g`` holds the bid class
    ``n_from * (g % (B/S)) + g // (B/S)`` — the inverse of
    :func:`ring_slots` — and that class's new slot is ``ring_slots`` under
    ``n_to``.  ``n_from == n_to`` returns the identity permutation."""
    B = int(block_slots)
    for n in (n_from, n_to):
        if n < 1 or B % n:
            raise ValueError(
                f"block_slots={B} not divisible by {n} shards")
    g = np.arange(B, dtype=np.int64)
    per = B // n_from
    bid_class = n_from * (g % per) + g // per
    dst = ring_slots(bid_class, n_to, B)
    idx = np.empty(B, np.int64)
    idx[dst] = g
    return idx


def state_specs() -> ServiceState:
    """ServiceState-shaped pytree of PartitionSpecs: ledger arrays sharded
    on the block axis, pipeline tables replicated."""
    return ServiceState(
        demand=P(None, None, AXIS),
        arrival=P(), loss=P(), spawn_tick=P(), done=P(), weight=P(),
        block_budget=P(AXIS), block_capacity=P(AXIS), block_birth=P(AXIS),
        lam=P(AXIS), tick=P())


def state_shardings(mesh) -> ServiceState:
    """ServiceState-shaped pytree of NamedShardings for ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs(),
                        is_leaf=lambda s: isinstance(s, P))


def shard_state(state: ServiceState, mesh) -> ServiceState:
    """Commit ``state`` to the block-axis layout (no-op where already
    placed correctly)."""
    return jax.device_put(state, state_shardings(mesh))


@dataclasses.dataclass(frozen=True)
class ShardedServiceState:
    """A :class:`ServiceState` committed to a block-axis sharded layout.

    Thin pairing of the state pytree with its mesh; the service keeps the
    plain ``ServiceState`` in ``.state`` so every host-side code path of
    the unsharded server works unchanged."""

    state: ServiceState
    mesh: jax.sharding.Mesh

    @classmethod
    def commit(cls, state: ServiceState, mesh) -> "ShardedServiceState":
        """Validate an existing state against ``mesh`` and commit it to
        the block-axis layout (the single home of the ring-divisibility
        invariant)."""
        n = mesh_shards(mesh)
        block_slots = state.block_budget.shape[0]
        if block_slots % n:
            raise ValueError(
                f"block_slots={block_slots} not divisible by the mesh's "
                f"{n} shards")
        return cls(state=shard_state(state, mesh), mesh=mesh)

    @classmethod
    def create(cls, analyst_slots: int, pipeline_slots: int,
               block_slots: int, mesh) -> "ShardedServiceState":
        return cls.commit(ServiceState.create(analyst_slots, pipeline_slots,
                                              block_slots), mesh)

    @property
    def n_shards(self) -> int:
        return mesh_shards(self.mesh)

    @property
    def blocks_per_shard(self) -> int:
        return self.state.block_budget.shape[0] // self.n_shards

    def slot_of(self, bids):
        return ring_slots(bids, self.n_shards,
                          self.state.block_budget.shape[0])

    def put(self, state: ServiceState) -> "ShardedServiceState":
        """Re-commit a host-mutated state to the sharded layout."""
        return dataclasses.replace(self,
                                   state=shard_state(state, self.mesh))
