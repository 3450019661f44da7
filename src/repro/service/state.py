"""Persistent service-plane state: block ledger + pipeline slot table.

The engine's :class:`~repro.core.engine.Episode` is immutable and finite —
every block and pipeline the episode will ever see is pre-generated.  The
service plane instead runs *forever* over fixed-size device arrays:

* **Block ledger** (``block_budget`` / ``block_capacity`` / ``block_birth``,
  all ``[B]``): a ring over global block ids.  Block ``bid`` lives in slot
  ``bid % B``; when the ring wraps, minting a new block *retires* the slot's
  previous occupant (its leftover budget is abandoned and any pipeline
  demand still pointing at the slot is zeroed).  Slots that have never held
  a block carry the engine's pre-creation sentinel (budget 1, capacity 0,
  birth ``-1``) so a fresh ledger is bit-identical to an episode prefix.
* **Pipeline slot table** (``demand[M, N, B]`` + per-pipeline metadata):
  fixed ``M`` analyst rows x ``N`` pipeline columns.  A slot is *recycled*
  (host free-list, :class:`SlotTable`) once its pipeline is granted;
  admission overwrites the slot's demand row in full, so no stale demand
  survives recycling.  ``spawn_tick`` activates a pipeline mid-chunk
  (admission happens at chunk boundaries, activation at the pipeline's
  arrival tick — the same mechanism as the engine's ``spawn_round``).

Everything in :class:`ServiceState` is a device array; the host only reads
or writes it at chunk boundaries (see :mod:`repro.service.server`).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

NEVER = np.int32(np.iinfo(np.int32).max)   # spawn_tick sentinel: not admitted


@dataclasses.dataclass(frozen=True)
class ServiceState:
    """Device-resident scheduling state that survives across ticks."""

    demand: jax.Array          # [M, N, B] epsilon demand per pipeline slot
    arrival: jax.Array         # [M, N] submission time (seconds)
    loss: jax.Array            # [M, N] matching degree l_ij
    spawn_tick: jax.Array      # [M, N] i32 tick the pipeline activates
    done: jax.Array            # [M, N] bool — granted (slot awaiting recycle)
    weight: jax.Array          # [M] per-analyst tier weight (1.0 default)
    block_budget: jax.Array    # [B] total budget (1.0 pre-creation sentinel)
    block_capacity: jax.Array  # [B] remaining budget (0 pre-creation)
    block_birth: jax.Array     # [B] i32 mint tick (-1 pre-creation)
    lam: jax.Array             # [B] SP1 dual carried across ticks (1.0 cold;
                               #   reset to 1.0 when the slot is re-minted)
    tick: jax.Array            # scalar i32 — next tick the server will run
    # Rényi-DP ledger (ServiceConfig.rdp): each slot's demand curve over
    # the A usable orders; ``demand`` then holds per-block scales and
    # ``block_capacity`` is [A, B], the remaining budget at every order.
    # None (scalar epsilon) is structural: the scalar programs are the
    # ones compiled without the field.
    curve: Optional[jax.Array] = None   # [M, N, A]

    @property
    def shape(self):
        return self.demand.shape

    @classmethod
    def create(cls, analyst_slots: int, pipeline_slots: int,
               block_slots: int, orders: int = 0) -> "ServiceState":
        """A fresh state; ``orders`` > 0 makes a Rényi-DP ledger of that
        many orders (per-order capacity, a curve per slot)."""
        M, N, B = analyst_slots, pipeline_slots, block_slots
        cap = (B,) if not orders else (orders, B)
        return cls(
            demand=jnp.zeros((M, N, B), jnp.float32),
            arrival=jnp.zeros((M, N), jnp.float32),
            loss=jnp.ones((M, N), jnp.float32),
            spawn_tick=jnp.full((M, N), NEVER, jnp.int32),
            done=jnp.zeros((M, N), bool),
            weight=jnp.ones((M,), jnp.float32),
            block_budget=jnp.ones((B,), jnp.float32),
            block_capacity=jnp.zeros(cap, jnp.float32),
            block_birth=jnp.full((B,), -1, jnp.int32),
            lam=jnp.ones((B,), jnp.float32),
            tick=jnp.asarray(0, jnp.int32),
            curve=jnp.zeros((M, N, orders), jnp.float32) if orders else None)


jax.tree_util.register_dataclass(
    ServiceState,
    data_fields=["demand", "arrival", "loss", "spawn_tick", "done", "weight",
                 "block_budget", "block_capacity", "block_birth", "lam",
                 "tick", "curve"],
    meta_fields=[])


@jax.jit
def _admit_apply(state: ServiceState, mask, loss, arrival_seconds,
                 spawn_ticks, weight, rows, cols, bids, eps,
                 curve=None) -> ServiceState:
    # wipe every (re)filled slot's demand row, then write the new demands
    # as one small COO scatter — no stale demand survives recycling, and
    # nothing proportional to [M, N, B] crosses the host boundary.
    with jax.named_scope("admit"):
        demand = jnp.where(mask[..., None], 0.0, state.demand)
        demand = demand.at[rows, cols, bids].set(eps)
        if curve is not None:       # RDP: the slots' curves, whole rows
            curve = jnp.where(mask[..., None], curve, state.curve)
        return dataclasses.replace(
            state,
            demand=demand,
            loss=jnp.where(mask, loss, state.loss),
            arrival=jnp.where(mask, arrival_seconds, state.arrival),
            spawn_tick=jnp.where(mask, spawn_ticks, state.spawn_tick),
            done=state.done & ~mask,
            weight=weight,
            curve=state.curve if curve is None else curve)


def admit_batch(state: ServiceState, mask, loss, arrival_seconds,
                spawn_ticks, rows, cols, bids, eps,
                weight=None, curve=None) -> ServiceState:
    """Write one admission batch into the slot table (one fused jit'd
    update; host calls this only at chunk boundaries).

    ``mask[M, N]`` marks the slots being (re)filled; ``loss`` /
    ``arrival_seconds`` / ``spawn_ticks`` are full-table arrays whose
    values matter only under the mask.  The demand update arrives as flat
    COO triples ``(rows, cols, bids) -> eps`` — kilobytes per boundary
    instead of an [M, N, B] dense block.  The COO arrays are padded to the
    next power of two with duplicates of entry 0 (same index, same value —
    an idempotent write) so the jit cache stays logarithmic in batch
    size.  ``weight`` is the full post-admission ``[M]`` per-analyst tier
    weight vector (the server's host mirror); None keeps the current
    weights.  ``curve [M, N, A]`` (Rényi-DP ledgers only) is the full
    table of demand curves, read under the mask like ``loss``."""
    if weight is None:
        weight = state.weight
    n = len(rows)
    if n:
        pad = (1 << max(n - 1, 0).bit_length()) - n
        idx = np.concatenate([np.arange(n), np.zeros(pad, np.int64)])
    else:  # every demand entry was dropped as stale — metadata-only admit
        idx = np.zeros(0, np.int64)
    return _admit_apply(
        state, jnp.asarray(mask), jnp.asarray(loss, jnp.float32),
        jnp.asarray(arrival_seconds, jnp.float32),
        jnp.asarray(spawn_ticks, jnp.int32),
        jnp.asarray(weight, jnp.float32),
        jnp.asarray(np.asarray(rows)[idx], jnp.int32),
        jnp.asarray(np.asarray(cols)[idx], jnp.int32),
        jnp.asarray(np.asarray(bids)[idx], jnp.int32),
        jnp.asarray(np.asarray(eps, np.float32)[idx]),
        None if curve is None else jnp.asarray(curve, jnp.float32))


@dataclasses.dataclass
class PagePlan:
    """One chunk's hot-ring page schedule (two-ring paged demand residency).

    The chunk's mints can only touch the ring slots of the consecutive
    global-bid window ``[tick0*bpr, tick0*bpr + H)`` — so only those ``H``
    demand columns (the *hot ring*) can change inside the chunk, and the
    only change is the retirement wipe at each slot's mint tick.  The full
    ``[M, N, B]`` tensor (the *cold page store*) therefore stays a scan
    constant; the hot ring's residency is *algebraic*: ``mint_tick[b]``
    records when slot ``b`` is re-minted, and the tick body reconstructs
    the current hot values by fusing the wipe predicate
    ``(mint_tick <= t) & (spawn_tick < mint_tick)`` into the activity
    mask it applies anyway (:class:`repro.core.demand.DemandView`).  The
    chunk-boundary eviction sweep is one fused elementwise pass applying
    the chunk's accumulated wipes to the cold store.

    ``hot_slots`` additionally names the hot ring explicitly — the
    chunk-level expiry/telemetry reductions are computed on a one-off
    ``[M, N, H]`` gather of those columns instead of full-tensor passes.
    The window is padded up to a multiple of the shard count so every
    shard pages an equal-size stripe; padding slots carry
    ``mint_tick == NEVER`` and behave exactly like cold columns.

    Valid only while every slot is minted at most once per chunk
    (``H <= B``); :func:`plan_pages` returns None when the hot window
    *spills* and the caller falls back to carrying the full tensor.  The
    layout composes with the striped sharded ring as-is: ``mint_tick`` is
    a per-slot vector in the same (global) slot layout as the ledger, so
    it shards with it and every wipe stays shard-local."""

    mint_tick: np.ndarray    # [B] i32 — chunk tick re-minting the slot
                             #   (NEVER where the chunk leaves it cold)
    hot_slots: np.ndarray    # [S, Hp/S] i32 — LOCAL hot-ring slots per
                             #   shard (incl. shard-alignment padding)
    hot_size: int            # slots the chunk's mints touch (H, unpadded)


def plan_pages(tick0: int, n_ticks: int, block_slots: int,
               blocks_per_tick: int, slot_fn=None, n_shards: int = 1):
    """The chunk's :class:`PagePlan`, or None when the hot window would
    not fit in the ring (a slot would be minted twice within one chunk
    and a single re-mint tick could not describe it)."""
    S = int(n_shards)
    B = block_slots
    if B % S:
        raise ValueError(f"block_slots={B} not divisible by {S} shards")
    H = n_ticks * blocks_per_tick
    Hp = -(-H // S) * S                  # shard-aligned hot window
    if Hp > B:
        return None
    b0 = tick0 * blocks_per_tick
    bids = np.arange(b0, b0 + Hp, dtype=np.int64)
    slots = ((bids % B) if slot_fn is None else slot_fn(bids)).astype(
        np.int64)
    mint_tick = np.full(B, NEVER, np.int32)
    minted = bids < b0 + H               # padding bids are not minted
    mint_tick[slots[minted]] = (bids[minted] // blocks_per_tick).astype(
        np.int32)
    # shard s owns the contiguous global slot range [s*B/S, (s+1)*B/S);
    # a window of Hp consecutive bids lands Hp/S slots on every shard
    # under the striped layout (and trivially with S == 1).
    owner = slots // (B // S)
    local = slots % (B // S)
    counts = np.bincount(owner, minlength=S)
    if not (counts == Hp // S).all():    # layout does not stripe evenly
        return None                      # -> carry fallback, still exact
    hot_slots = np.empty((S, Hp // S), np.int32)
    for s in range(S):
        hot_slots[s] = local[owner == s]
    return PagePlan(mint_tick=mint_tick, hot_slots=hot_slots, hot_size=H)


@dataclasses.dataclass
class MintPlan:
    """One chunk's block-mint schedule, fully precomputed on the host so
    the device scan applies it with engine-identical ops.

    ``retire`` says whether any minted slot overwrites a live block (ring
    wrapped).  The wrap-free scan consumes ``budgets`` as a capacity *add*
    (fresh slots hold 0, so ``capacity += budgets`` is the engine's own
    mint op) plus ``budget_total``/``created`` directly, carrying only
    ``(done, capacity)`` — a service tick is then op-for-op an engine
    round.  Wrap chunks apply ``mask``/``budgets`` as selects (eviction =
    set, not add); the demand side of retirement is described by
    ``pages`` (the two-ring paged layout — only the hot ring joins the
    carry) with the full-tensor carry kept as the spill fallback.
    ``next_*`` are the host mirrors of the ledger metadata after the
    chunk."""

    mask: np.ndarray          # [T, B] bool — minted this tick
    budgets: np.ndarray       # [T, B] f32 — minted budget (0 elsewhere)
    budget_total: np.ndarray  # [T, B] f32 — ledger budget_total at tick t
    created: np.ndarray       # [T, B] bool — slot holds a block at tick t
    retire: bool
    next_budget: np.ndarray   # [B] f32 host mirror after the chunk
    next_birth: np.ndarray    # [B] i32 host mirror after the chunk
    pages: "PagePlan | None" = None   # hot-ring schedule (retire chunks)


def plan_mints(tick0: int, n_ticks: int, block_slots: int,
               device_budget: np.ndarray, blocks_per_device: int,
               prev_budget: np.ndarray, prev_birth: np.ndarray,
               slot_fn=None, page_shards: int = 0) -> MintPlan:
    """Mint schedule for ticks ``[tick0, tick0 + n_ticks)``; ``prev_*``
    are the host ledger mirrors at the chunk boundary.

    ``slot_fn`` maps global block ids to ring slots (default ``bid % B``).
    Any layout whose slot is reused exactly by ``bid + B`` works — the
    sharded service uses a striped layout so each mesh shard owns the
    ``bid % n_shards`` stripe (see :mod:`repro.shard`).  ``page_shards``
    > 0 additionally attaches a :class:`PagePlan` over that many shard
    stripes to retire chunks (None when the hot window spills)."""
    n_devices = device_budget.shape[0]
    bpr = n_devices * blocks_per_device
    B = block_slots
    ticks = np.arange(tick0, tick0 + n_ticks, dtype=np.int64)
    bids = ticks[:, None] * bpr + np.arange(bpr)[None, :]      # global ids
    slots = ((bids % B) if slot_fn is None else slot_fn(bids)).astype(
        np.int64)
    rows = np.repeat(np.arange(n_ticks), bpr)
    flat = slots.reshape(-1)
    per_tick = np.tile(
        np.repeat(device_budget.astype(np.float32), blocks_per_device),
        n_ticks)
    mask = np.zeros((n_ticks, B), bool)
    mask[rows, flat] = True
    budgets = np.zeros((n_ticks, B), np.float32)
    budgets[rows, flat] = per_tick

    budget_total = np.empty((n_ticks, B), np.float32)
    created = np.empty((n_ticks, B), bool)
    bud, birth = prev_budget.copy(), prev_birth.copy()
    for i in range(n_ticks):
        bud[slots[i]] = budgets[i, slots[i]]
        birth[slots[i]] = tick0 + i
        created[i] = birth >= 0
        budget_total[i] = np.where(created[i], bud, 1.0)
    retire = bool(bids.max() >= B)
    pages = plan_pages(tick0, n_ticks, B, bpr, slot_fn, page_shards) \
        if (retire and page_shards > 0) else None
    return MintPlan(mask=mask, budgets=budgets, budget_total=budget_total,
                    created=created, retire=retire,
                    next_budget=bud, next_birth=birth, pages=pages)


class SlotTable:
    """Host-side occupancy bookkeeping with free-list recycling.

    Analyst rows are handed out from an ascending free list; pipeline
    columns within a row are recycled as their pipelines complete.  A row
    returns to the free list when its last occupied slot is released — an
    analyst whose submissions are still queued at that moment gets a
    (possibly different) row when they drain; only analysts with a
    currently-occupied row keep their identity pinned to it."""

    def __init__(self, analyst_slots: int, pipeline_slots: int):
        self.M, self.N = analyst_slots, pipeline_slots
        self.occupied = np.zeros((self.M, self.N), bool)
        self.row_owner = np.full(self.M, -1, np.int64)   # external analyst id
        self.submit_tick = np.full((self.M, self.N), -1, np.int64)
        self._free_rows: List[int] = list(range(self.M - 1, -1, -1))

    # ------------------------------------------------------------- queries
    def free_pipeline_slots(self) -> int:
        return int((~self.occupied).sum())

    def live_rows(self) -> int:
        return self.M - len(self._free_rows)

    def row_for(self, analyst: int, n_pipes: int):
        """Row + free columns for an admission of ``n_pipes`` pipelines by
        ``analyst``, or None if it cannot be placed right now.

        Prefers the analyst's existing row (returning analysts keep their
        SP1 identity — one row per live analyst); otherwise pops a fresh
        row off the free list."""
        if n_pipes > self.N:
            return None                     # can never fit any row — the
                                            # queue rejects these at offer()
        owned = np.where(self.row_owner == analyst)[0]
        if owned.size:
            row = int(owned[0])
            cols = np.where(~self.occupied[row])[0]
            if cols.size >= n_pipes:
                return row, cols[:n_pipes].tolist()
            return None                     # row full — defer
        if not self._free_rows:
            return None                     # table full — defer
        row = self._free_rows[-1]           # peek; commit() pops
        return row, list(range(n_pipes))

    # ------------------------------------------------------------ mutation
    def commit(self, analyst: int, row: int, cols, submit_tick: int) -> None:
        if self.row_owner[row] == -1:
            popped = self._free_rows.pop()
            assert popped == row, "row_for/commit interleaving bug"
            self.row_owner[row] = analyst
        self.occupied[row, cols] = True
        self.submit_tick[row, cols] = submit_tick

    # -------------------------------------------------------- durability
    def state_dict(self) -> dict:
        """Snapshot for :meth:`FlaasService.save_checkpoint` — restoring
        it into a fresh table reproduces occupancy, analyst identities,
        submit ticks AND the free-list order (row hand-out is LIFO, so the
        order matters for bitwise resume)."""
        return {"occupied": self.occupied.copy(),
                "row_owner": self.row_owner.copy(),
                "submit_tick": self.submit_tick.copy(),
                "free_rows": list(self._free_rows)}

    def load_state_dict(self, d: dict) -> None:
        occupied = np.asarray(d["occupied"], bool)
        if occupied.shape != (self.M, self.N):
            raise ValueError(
                f"slot-table checkpoint is {occupied.shape}, table is "
                f"({self.M}, {self.N})")
        self.occupied = occupied.copy()
        self.row_owner = np.asarray(d["row_owner"], np.int64).copy()
        self.submit_tick = np.asarray(d["submit_tick"], np.int64).copy()
        self._free_rows = [int(r) for r in d["free_rows"]]

    def release_done(self, done: np.ndarray) -> np.ndarray:
        """Recycle slots whose pipelines were granted (``done[M, N]`` from
        the device).  Returns the ``[n, 2]`` (row, col) indices freed this
        call.  Rows with no remaining occupancy go back to the free list."""
        freed = np.argwhere(done & self.occupied)
        self.occupied[done] = False
        self.submit_tick[done] = -1
        for row in np.unique(freed[:, 0]) if freed.size else []:
            row = int(row)
            if not self.occupied[row].any() and self.row_owner[row] != -1:
                self.row_owner[row] = -1
                self._free_rows.append(row)
        return freed
