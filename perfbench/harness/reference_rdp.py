"""Plain reference of the live service over Rényi-DP block ledgers, for the
``rdp_live`` entry.  It imports nothing of the program.

DPF over RDP budgets (Luo et al., "Privacy Budget Scheduling", OSDI '21,
arXiv 2106.15335), as the DPBalance paper's §VI DPF baseline runs it,
tick by tick in straightforward ``jax.numpy`` under
``jax.default_matmul_precision("highest")``:

* a block's capacity at order a is ``eps_G(a) = eps_G - ln(1/delta_G) /
  (a - 1)`` over the usable orders (the configuration's, capacity > 0);
* a pipeline demands ``scale_k * curve(a)`` of block k at order a, and
  RDP composes additively at every order;
* a grant fits iff every block it demands has some order whose remaining
  capacity holds the demand; it debits every order, and remaining
  capacity is not floored at 0;
* DPF visits pending pipelines by ``max_k min_a`` of the normalized
  demand, smallest first; pipelines with a demanded block that no order
  can serve are screened out of the round;
* the ring, admission and expiry are ``reference.py``'s: block ``bid`` in
  slot ``bid % B``; minting writes the capacity curve into every order of
  the slot and wipes the demand of pipelines activated before it; a
  pipeline whose every demanded block was retired expires; one FIFO
  bounded by ``max_pending``, drained each tick (``admit_batch`` batches
  at most) into the analyst's own row or the most recently freed one.

Departures from the source: no budget unlocking (DPF-N / DPF-T); fits
compare normalized demand (demand over the capacity at that order) with a
slack of ``FEAS``.

Each tick is judged from the program's history, as ``reference.py``
does: :meth:`ReferenceRdp.step` takes the program's ledger before the
tick (``capacity [A, B]``), decides the tick, then applies the program's
selection (``forced``).  Without them it follows its own decisions and
ledger: the control in the program's place.  ``dtype`` is the precision
of every float it holds.
"""
from __future__ import annotations

import functools
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

ROUND_SECONDS = 10.0
BIG = 1e30
FEAS = 1e-6
NEVER = np.iinfo(np.int32).max


def capacity_curve(rdp: dict) -> np.ndarray:
    """A fresh block's capacity at each usable order."""
    a = np.asarray(rdp["orders"], np.float64)
    cap = rdp["eps_g"] - np.log(1.0 / rdp["delta_g"]) / (a - 1.0)
    return cap[cap > 0]


def dpf_rdp_round(demand, curve, pending, capacity, budget):
    """One DPF round over an RDP ledger: the selection [M, N]."""
    M, N, B = demand.shape
    A = curve.shape[-1]
    bt = jnp.maximum(budget, 1e-12)
    cap_frac = capacity / jnp.maximum(budget, 1e-9)
    share = jnp.min(demand[:, :, None, :] * curve[:, :, :, None] / bt,
                    axis=2)
    fits = jnp.any(demand[:, :, None, :] * curve[:, :, :, None] / bt
                   <= cap_frac + FEAS, axis=2)
    active = pending & ~jnp.any((demand > 0.0) & ~fits, axis=-1)
    mu = jnp.max(share, axis=-1)
    order = jnp.argsort(jnp.where(active, mu, BIG).reshape(-1), stable=True)

    def visit(rem, v):
        d = demand.reshape(M * N, B)[v]
        g = d[None, :] * curve.reshape(M * N, A)[v][:, None] / bt
        ok = active.reshape(-1)[v] & jnp.all(
            (d == 0.0) | jnp.any(g <= rem + FEAS, axis=0))
        return jnp.where(ok, rem - g, rem), ok

    _, taken = jax.lax.scan(visit, cap_frac, order)
    return jnp.zeros(M * N, bool).at[order].set(taken).reshape(M, N)


def _tick(st, adm, minted, cap_in, forced, t, retire, *, cap_curve):
    demand, curve, spawn, done, cap, budget, created = st
    dt = demand.dtype
    if cap_in is not None:
        cap = cap_in.astype(dt)
    mask, a_curve, a_spawn, rows, cols, slots, eps = adm
    demand = jnp.where(mask[..., None], 0.0, demand).astype(dt)
    demand = demand.at[rows, cols, slots].set(eps.astype(dt), mode="drop")
    curve = jnp.where(mask[..., None], a_curve.astype(dt), curve)
    spawn = jnp.where(mask, a_spawn, spawn)
    done = done & ~mask
    stale = minted[None, None, :] & (spawn < t)[..., None]
    demand = jnp.where(stale, 0.0, demand).astype(dt)
    cc = cap_curve.astype(dt)[:, None]
    cap = jnp.where(minted[None, :], cc, cap)
    budget = jnp.where(minted[None, :], cc, budget)
    created = created | minted
    bt = jnp.where(created[None, :], budget, 1.0).astype(dt)
    pending = (spawn <= t) & ~done
    expired = retire & pending & ~jnp.any(demand > 0.0, axis=-1)
    pending = pending & ~expired
    eff = demand * pending[..., None].astype(dt)
    own = dpf_rdp_round(eff, curve, pending, cap, bt)
    sel = own if forced is None else forced
    granted = eff * sel[..., None].astype(dt)
    consumed = jnp.einsum("mnb,mna->ab", granted, curve).astype(dt)
    spend = jnp.einsum("mn,mna->ma", jnp.sum(granted, axis=-1), curve)
    cap = (cap - consumed).astype(dt)
    invalid = jnp.sum(sel & ~pending)
    done = done | sel | expired
    return (demand, curve, spawn, done, cap, budget, created), \
        (own, expired, spend, invalid, cap)


@functools.lru_cache(maxsize=None)
def _compiled_tick(cap_curve: tuple, forced: bool):
    fn = functools.partial(_tick, cap_curve=jnp.asarray(cap_curve))
    if not forced:
        return jax.jit(lambda st, adm, mint, t, r: fn(st, adm, mint, None,
                                                      None, t, r),
                       static_argnums=(4,))
    return jax.jit(fn, static_argnums=(6,))


class ReferenceRdp:
    """The plain RDP service, one tick at a time (module docstring).
    ``arrivals`` is the benchmark's stream with curves attached
    (``generator_rdp.Curves``)."""

    def __init__(self, deployment: dict, rdp: dict, arrivals,
                 dtype=jnp.float32):
        d = deployment
        self.M, self.N = d["analyst_slots"], d["pipeline_slots"]
        self.B = d["block_slots"]
        self.admit_batch, self.max_pending = d["admit_batch"], d["max_pending"]
        self.arrivals = arrivals
        self.bpr = arrivals.bpr
        self.cap_curve = tuple(float(c) for c in capacity_curve(rdp))
        self.A = len(self.cap_curve)
        self.dtype = dtype
        M, N, B, A = self.M, self.N, self.B, self.A
        self.state = (jnp.zeros((M, N, B), dtype), jnp.zeros((M, N, A), dtype),
                      jnp.full((M, N), NEVER, jnp.int32),
                      jnp.zeros((M, N), bool), jnp.zeros((A, B), dtype),
                      jnp.ones((A, B), dtype), jnp.zeros(B, bool))
        self.queue = deque()
        self.occupied = np.zeros((M, N), bool)
        self.owner = np.full(M, -1, np.int64)
        self.free_rows = list(range(M - 1, -1, -1))
        self.birth = np.full(B, -1, np.int64)
        self.tick = 0

    def _admit(self, t: int):
        for b in self.arrivals.events[t]:
            if len(b.bids) > self.N or len(self.queue) >= self.max_pending:
                continue
            self.queue.append(b)
        M, N, B, A, bpr = self.M, self.N, self.B, self.A, self.bpr
        mask = np.zeros((M, N), bool)
        a_curve = np.zeros((M, N, A), np.float32)
        a_spawn = np.zeros((M, N), np.int32)
        rows, cols, slots, eps = [], [], [], []
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
            spawn = max(b.tick, t)
            for j, c in enumerate(cs):
                mask[row, c] = True
                a_curve[row, c] = b.curves[j]
                a_spawn[row, c] = spawn
                s = b.bids[j] % B
                keep = (self.birth[s] <= b.bids[j] // bpr) & \
                    ((b.bids[j] + B) // bpr > spawn)
                rows.append(np.full(int(keep.sum()), row))
                cols.append(np.full(int(keep.sum()), c))
                slots.append(s[keep])
                eps.append(b.eps[j][keep])
        cat = (lambda xs, dt: np.concatenate(xs).astype(dt) if xs
               else np.zeros(0, dt))
        rows, cols = cat(rows, np.int32), cat(cols, np.int32)
        slots, eps = cat(slots, np.int32), cat(eps, np.float32)
        pad = (1 << max(rows.size - 1, 0).bit_length()) - rows.size
        rows = np.concatenate([rows, np.full(pad, M, np.int32)])   # dropped
        cols = np.concatenate([cols, np.zeros(pad, np.int32)])
        slots = np.concatenate([slots, np.zeros(pad, np.int32)])
        eps = np.concatenate([eps, np.zeros(pad, np.float32)])
        return (mask, a_curve, a_spawn, rows, cols, slots, eps)

    def _release(self, freed: np.ndarray) -> None:
        rel = np.argwhere(freed & self.occupied)
        self.occupied[freed] = False
        for row in np.unique(rel[:, 0]) if rel.size else []:
            if not self.occupied[row].any() and self.owner[row] != -1:
                self.owner[row] = -1
                self.free_rows.append(int(row))

    def step(self, forced=None, capacity=None):
        """One tick: ``(own [M, N], expired [M, N], spend [M, A], invalid,
        capacity [A, B])``.  ``forced`` and ``capacity``: both or
        neither (module docstring)."""
        t = self.tick
        adm = self._admit(t)
        bids = t * self.bpr + np.arange(self.bpr)
        minted = np.zeros(self.B, bool)
        minted[bids % self.B] = True
        self.birth[bids % self.B] = t
        retire = bool(bids.max() >= self.B)
        fn = _compiled_tick(self.cap_curve, forced is not None)
        args = (self.state, adm, minted)
        if forced is not None:
            args += (np.asarray(capacity), np.asarray(forced, bool))
        with jax.default_matmul_precision("highest"):
            self.state, out = fn(*args, np.int32(t), retire)
        own, expired, spend, invalid, cap = (np.asarray(o) for o in out)
        sel = own if forced is None else np.asarray(forced, bool)
        self._release(sel | expired)
        self.tick += 1
        return own, expired, spend, int(invalid), cap
