"""Plain reference of the live service over Rényi-DP block ledgers.

DPF over RDP budgets as PrivateKube schedules it (Luo et al., "Privacy
Budget Scheduling", OSDI '21, arXiv 2106.15335), written out tick by tick
in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``, with no kernels, no paging
and no shared code with the service (:mod:`repro.service`).  It is the
oracle the service's RDP path is tested against.

* Block capacity at order a: ``eps_G(a) = eps_G - log(1/delta_G)/(a-1)``;
  only orders with a positive capacity are kept (the caller passes those).
* A pipeline's demand on block k at order a is ``eps_k * curve(a)``: the
  submission's per-block scale times its curve.  RDP composes additively
  at every order.
* A grant fits iff every block the pipeline demands has some order whose
  remaining capacity holds the demand; it debits every order.  Remaining
  capacity is not floored at 0: an order a grant overdraws is dead on
  that block while another order still holds.
* DPF visits pending pipelines by dominant share, smallest first:
  ``max_k min_a eps_k curve(a) / eps_G,k(a)``; FCFS in arrival order.
  Pipelines with a demanded block that no order can serve are screened
  out of the round.
* The ring: block ``bid`` lives in slot ``bid % B``; minting writes the
  capacity curve into every order of the slot and retires its previous
  block, whose demand is wiped for pipelines activated before the mint;
  a pipeline whose every demanded block was retired expires.  Admission
  is one FIFO bounded by ``max_pending``, drained at each chunk boundary
  (``admit_batch`` batches at most) into the analyst's own row or the
  most recently freed one; a batch that does not fit stops the drain.
  Slots are released at the end of the chunk that granted or expired
  them.

Departures from the source: no budget unlocking (DPF-N / DPF-T): a block
offers its whole capacity from its mint, as in the DPBalance paper's §VI
DPF baseline; fits-checks compare normalized demand (demand over the
block's capacity at that order) with a slack of ``FEAS`` = 1e-6.
"""
from __future__ import annotations

from collections import deque
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ROUND_SECONDS = 10.0
BIG = 1e30
FEAS = 1e-6
NEVER = np.iinfo(np.int32).max


def rdp_round(demand, curve, pending, arrival, capacity, budget, key):
    """One round over an RDP ledger: (selection [M, N], screened active).

    ``demand [M, N, B]`` per-block scales, ``curve [M, N, A]``,
    ``capacity`` and ``budget`` ``[A, B]``; ``key`` is "dpf" or "fcfs"."""
    M, N, B = demand.shape
    A = curve.shape[-1]
    bt = jnp.maximum(budget, 1e-12)
    cap_frac = capacity / jnp.maximum(budget, 1e-9)
    # [M, N, A, B] normalized demand at every order (small at test sizes)
    norm = demand[:, :, None, :] * curve[:, :, :, None] / bt
    share = jnp.min(norm, axis=2)                            # [M, N, B]
    fits = jnp.any(norm <= cap_frac + FEAS, axis=2)          # [M, N, B]
    active = pending & ~jnp.any((demand > 0.0) & ~fits, axis=-1)
    mu = jnp.max(share, axis=-1)
    k = mu if key == "dpf" else arrival
    order = jnp.argsort(jnp.where(active, k, BIG).reshape(-1), stable=True)

    def visit(rem, v):
        g = norm.reshape(M * N, A, B)[v]
        d = demand.reshape(M * N, B)[v]
        ok = active.reshape(-1)[v] & jnp.all(
            (d == 0.0) | jnp.any(g <= rem + FEAS, axis=0))
        return jnp.where(ok, rem - g, rem), ok

    _, taken = jax.lax.scan(visit, cap_frac, order)
    return jnp.zeros(M * N, bool).at[order].set(taken).reshape(M, N)


def _tick(st, adm, minted, cap_in, forced, t, retire, *, cap_curve, key):
    demand, curve, spawn, done, arrival, cap, budget, created = st
    if cap_in is not None:
        cap = cap_in
    mask, a_curve, a_arr, a_spawn, rows, cols, slots, eps = adm
    # admission: refilled slots' rows are rewritten in full
    demand = jnp.where(mask[..., None], 0.0, demand)
    demand = demand.at[rows, cols, slots].set(eps, mode="drop")
    curve = jnp.where(mask[..., None], a_curve, curve)
    arrival = jnp.where(mask, a_arr, arrival)
    spawn = jnp.where(mask, a_spawn, spawn)
    done = done & ~mask
    # mint: retire the slot's previous block, write the capacity curve
    stale = minted[None, None, :] & (spawn < t)[..., None]
    demand = jnp.where(stale, 0.0, demand)
    cap = jnp.where(minted[None, :], cap_curve[:, None], cap)
    budget = jnp.where(minted[None, :], cap_curve[:, None], budget)
    created = created | minted
    bt = jnp.where(created[None, :], budget, 1.0)
    pending = (spawn <= t) & ~done
    expired = retire & pending & ~jnp.any(demand > 0.0, axis=-1)
    pending = pending & ~expired
    eff = demand * pending[..., None]
    own = rdp_round(eff, curve, pending, jnp.where(pending, arrival, 0.0),
                    cap, bt, key)
    sel = own if forced is None else forced
    granted = eff * sel[..., None]                           # [M, N, B]
    consumed = jnp.einsum("mnb,mna->ab", granted, curve)
    spend = jnp.einsum("mn,mna->ma", jnp.sum(granted, axis=-1), curve)
    cap = cap - consumed
    invalid = jnp.sum(sel & ~pending)
    done = done | sel | expired
    st = (demand, curve, spawn, done, arrival, cap, budget, created)
    return st, (own, expired, spend, invalid, cap)


class RdpDpfReference:
    """The plain RDP service, one tick per :meth:`step`.

    ``events[t]`` lists the submissions arriving at tick ``t`` (objects
    with ``analyst``, ``submit_tick``, ``bids``, ``eps``, ``curves``);
    ``capacity`` is a fresh block's ``[A]`` capacity curve."""

    def __init__(self, *, analyst_slots: int, pipeline_slots: int,
                 block_slots: int, blocks_per_tick: int, admit_batch: int,
                 max_pending: int, capacity: Sequence[float],
                 scheduler: str = "dpf", chunk_ticks: int = 1,
                 events: Optional[List[list]] = None):
        if scheduler not in ("dpf", "fcfs"):
            raise ValueError(f"no RDP reference for {scheduler!r}")
        self.M, self.N, self.B = analyst_slots, pipeline_slots, block_slots
        self.bpr, self.T = blocks_per_tick, chunk_ticks
        self.admit_batch, self.max_pending = admit_batch, max_pending
        self.cap_curve = jnp.asarray(capacity, jnp.float32)
        self.A = int(self.cap_curve.shape[0])
        self.events = events if events is not None else []
        M, N, B, A = self.M, self.N, self.B, self.A
        f32 = jnp.float32
        self.state = (jnp.zeros((M, N, B), f32), jnp.zeros((M, N, A), f32),
                      jnp.full((M, N), NEVER, jnp.int32),
                      jnp.zeros((M, N), bool), jnp.zeros((M, N), f32),
                      jnp.zeros((A, B), f32), jnp.ones((A, B), f32),
                      jnp.zeros(B, bool))
        self._fn = jax.jit(lambda *a: _tick(*a[:4], a[4], a[5], a[6],
                                            cap_curve=self.cap_curve,
                                            key=scheduler),
                           static_argnums=(6,))
        self.queue = deque()
        self.occupied = np.zeros((M, N), bool)
        self.owner = np.full(M, -1, np.int64)
        self.free_rows = list(range(M - 1, -1, -1))
        self.birth = np.full(B, -1, np.int64)
        self._freed = np.zeros((M, N), bool)
        self.tick = 0

    # -------------------------------------------------------- admission
    def _admit(self, t: int):
        M, N, B, A, bpr = self.M, self.N, self.B, self.A, self.bpr
        mask = np.zeros((M, N), bool)
        a_curve = np.zeros((M, N, A), np.float32)
        a_arr = np.zeros((M, N), np.float32)
        a_spawn = np.zeros((M, N), np.int32)
        rows, cols, slots, eps = [], [], [], []
        if t % self.T == 0:
            # the boundary: poll the chunk's arrivals, drain the FIFO
            for u in range(t, t + self.T):
                for b in (self.events[u] if u < len(self.events) else ()):
                    if len(b.bids) > N or len(self.queue) >= self.max_pending:
                        continue
                    self.queue.append(b)
            placed = 0
            while self.queue and placed < self.admit_batch:
                b = self.queue[0]
                n = len(b.bids)
                owned = np.flatnonzero(self.owner == b.analyst)
                if owned.size:
                    row = int(owned[0])
                    free = np.flatnonzero(~self.occupied[row])
                    if free.size < n:
                        break
                    cs = free[:n]
                elif self.free_rows:
                    row = self.free_rows.pop()
                    self.owner[row] = b.analyst
                    cs = np.arange(n)
                else:
                    break
                self.queue.popleft()
                placed += 1
                self.occupied[row, cs] = True
                spawn = max(b.submit_tick, t)
                for j, c in enumerate(cs):
                    mask[row, c] = True
                    a_curve[row, c] = b.curves[j]
                    a_arr[row, c] = b.submit_tick * ROUND_SECONDS
                    a_spawn[row, c] = spawn
                    bid = np.asarray(b.bids[j], np.int64)
                    s = bid % B
                    # keep an entry while its block is still in the ring
                    # and outlives the pipeline's activation
                    keep = (self.birth[s] <= bid // bpr) & \
                        ((bid + B) // bpr > spawn)
                    rows.append(np.full(int(keep.sum()), row))
                    cols.append(np.full(int(keep.sum()), c))
                    slots.append(s[keep])
                    eps.append(np.asarray(b.eps[j], np.float32)[keep])
        cat = (lambda xs, dt: np.concatenate(xs).astype(dt) if xs
               else np.zeros(0, dt))
        rows, cols = cat(rows, np.int32), cat(cols, np.int32)
        slots, eps = cat(slots, np.int32), cat(eps, np.float32)
        pad = (1 << max(rows.size - 1, 0).bit_length()) - rows.size
        rows = np.concatenate([rows, np.full(pad, M, np.int32)])  # dropped
        cols = np.concatenate([cols, np.zeros(pad, np.int32)])
        slots = np.concatenate([slots, np.zeros(pad, np.int32)])
        eps = np.concatenate([eps, np.zeros(pad, np.float32)])
        return (mask, a_curve, a_arr, a_spawn, rows, cols, slots, eps)

    def _release(self, freed: np.ndarray) -> None:
        rel = np.argwhere(freed & self.occupied)
        self.occupied[freed] = False
        for row in np.unique(rel[:, 0]) if rel.size else []:
            if not self.occupied[row].any() and self.owner[row] != -1:
                self.owner[row] = -1
                self.free_rows.append(int(row))

    # ------------------------------------------------------------- tick
    def step(self, forced=None, capacity=None):
        """Run one tick; returns ``(own [M, N], expired [M, N], spend
        [M, A], invalid, capacity [A, B])``.  ``forced`` (the program's
        selection) and ``capacity`` (the program's ledger before the
        tick), given together, judge a tick from the program's history:
        the reference decides from ``capacity``, then applies
        ``forced``."""
        t = self.tick
        adm = self._admit(t)
        bids = t * self.bpr + np.arange(self.bpr)
        minted = np.zeros(self.B, bool)
        minted[bids % self.B] = True
        self.birth[bids % self.B] = t
        # the chunk's first tick decides whether the chunk retires
        t0 = t - t % self.T
        retire = bool((t0 + self.T) * self.bpr - 1 >= self.B)
        with jax.default_matmul_precision("highest"):
            self.state, out = self._fn(
                self.state, adm, minted,
                None if capacity is None else jnp.asarray(capacity,
                                                          jnp.float32),
                None if forced is None else jnp.asarray(forced, bool),
                np.int32(t), retire)
        own, expired, spend, invalid, cap = (np.asarray(o) for o in out)
        sel = own if forced is None else np.asarray(forced, bool)
        self._freed |= sel | expired
        if (t + 1) % self.T == 0:         # the chunk's end: release slots
            self._release(self._freed)
            self._freed[:] = False
        self.tick += 1
        return own, expired, spend, int(invalid), cap
