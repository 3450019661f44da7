"""Plain reference of the live scheduling service, written from the paper.

It imports nothing of the program.  From the benchmark's own arrivals it
keeps, in straightforward code, everything a live FLaaS service decides:

* admission: one FIFO bounded by ``max_pending``, drained at every tick's
  boundary (up to ``admit_batch`` batches) into free analyst rows, the most
  recently freed row first; a batch that does not fit stops the drain;
* the block ledger: a ring of ``block_slots`` slots, block ``bid`` in slot
  ``bid % B``; minting a block sets its slot's capacity to the device's
  budget and retires the slot's previous block, whose demand is wiped for
  the pipelines admitted before the mint; a pipeline whose every demanded
  block has been retired expires;
* the round (arXiv 2402.09715, Alg. 1): DPBalance -- SP1 alpha-fair
  analyst shares by multiplicative dual ascent (Eqs. 17-19, 39), SP2
  ascending-share greedy cover, one pass of single swaps and the
  sequential kappa boost (Eqs. 20-24), with a final overdraw guard -- or
  DPF, the smallest dominant share first (Luo et al., OSDI '21);
* the debit: each granted pipeline consumes ``x * demand`` of its blocks.

Every round is checked one by one.  The reference takes the ledger the
program left after the previous round (``capacity``), decides the round
from it and reports its own selection, then applies the selection the
program made (``forced``) and reports the ε it debits per row and the
capacity it leaves per block.  Each round is so judged from the same
history: the capped SP1 solve amplifies float32 rounding across rounds,
and a reference left to its own ledger drifts from a sound program by more
than one round's rounding.  Without ``forced`` it follows its own
decisions and its own ledger: that is how the control runs in the
program's place.

``dtype`` is the precision of every float the reference holds (float32 as
the configuration states; bfloat16 for the control).
"""
from __future__ import annotations

import functools
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

ROUND_SECONDS = 10.0
BIG = 1e30
FEAS = 1e-6                    # fits-check slack on normalized shares


# ----------------------------------------------------------------- round
def _boost(gamma, mu, a, sel, budget, kappa_max):
    """Sequential kappa boost of one analyst's selection.  [N,K] -> x [N]."""
    left = budget - jnp.sum(gamma * sel[:, None], axis=0)
    order = jnp.argsort(-(mu * a), stable=True)

    def step(left, xs):
        dem, s = xs
        ratio = jnp.where(dem > 1e-9, left / jnp.maximum(dem, 1e-9), jnp.inf)
        extra = jnp.where(s, jnp.clip(jnp.min(ratio), 0.0, kappa_max - 1.0),
                          0.0)
        return left - extra * dem, extra

    _, extras = jax.lax.scan(step, left, (gamma[order], sel[order]))
    x = jnp.zeros_like(mu).at[order].set(extras)
    x = jnp.where(sel, 1.0 + x, 0.0)
    return x, jnp.sum(mu * a * x * sel)


def _greedy(gamma, mu, active, budget):
    order = jnp.argsort(jnp.where(active, mu, BIG), stable=True)

    def step(rem, xs):
        dem, act = xs
        ok = act & jnp.all(dem <= rem + FEAS)
        return jnp.where(ok, rem - dem, rem), ok

    _, taken = jax.lax.scan(step, budget, (gamma[order], active[order]))
    return jnp.zeros_like(active).at[order].set(taken) & active


def _swap(gamma, mu, a, active, sel, budget, kappa_max):
    """One pass of single swaps: the feasible (s out, u in) pair with the
    best boosted objective replaces the selection if it improves it."""
    N = mu.shape[0]
    s_idx, u_idx = jnp.meshgrid(jnp.arange(N), jnp.arange(N), indexing="ij")

    def cand(s, u):
        c = sel.at[s].set(False).at[u].set(True)
        ok = sel[s] & ~sel[u] & active[u] & (s != u)
        used = jnp.sum(gamma * c[:, None], axis=0)
        ok = ok & jnp.all(used <= budget + FEAS)
        obj = _boost(gamma, mu, a, c, budget, kappa_max)[1]
        return c, jnp.where(ok, obj, -BIG)

    cands, objs = jax.vmap(cand)(s_idx.reshape(-1), u_idx.reshape(-1))
    base = _boost(gamma, mu, a, sel, budget, kappa_max)[1]
    best = jnp.argmax(objs)
    return jnp.where(objs[best] > base + 1e-12, cands[best], sel)


def _sp1(mu, a, c, mask, cap, beta, iters, tol):
    """alpha-fair analyst shares by multiplicative dual ascent."""
    w = jnp.maximum(mu * a, 1e-12)
    w_pow = jnp.where(mask, w ** (1.0 - beta), 0.0)
    ratio = jnp.where(c > 1e-12, cap[None, :] / jnp.maximum(c, 1e-12),
                      jnp.inf)
    xcap = jnp.min(ratio, axis=1)
    mask = mask & (jnp.max(c, axis=1) > 1e-12) & jnp.isfinite(xcap)
    xcap = jnp.where(mask, xcap, 0.0)
    cap_safe = jnp.maximum(cap, 1e-12)

    def x_of(lam):
        denom = jnp.maximum(jnp.sum(c * lam[None, :], axis=1), 1e-12)
        x = jnp.minimum((w_pow / denom) ** (1.0 / beta), xcap)
        return jnp.where(mask, x, 0.0)

    def cond(st):
        return (st[1] < iters) & (st[2] > tol)

    def body(st):
        lam, it, _ = st
        g = (jnp.sum(c * x_of(lam)[:, None], axis=0) - cap) / cap_safe
        eta = 0.5 / (1.0 + 0.001 * it)
        lam = jnp.clip(lam * jnp.exp(eta * g), 1e-12, 1e12)
        viol = jnp.maximum(jnp.max(jnp.maximum(g, 0.0)),
                           jnp.max(lam * jnp.abs(g)))
        return lam, it + 1, viol

    lam0 = jnp.ones(c.shape[1], c.dtype)
    lam, _, _ = jax.lax.while_loop(
        cond, body, (lam0, jnp.asarray(0, jnp.int32),
                     jnp.asarray(jnp.inf, c.dtype)))
    x = x_of(lam)
    load = jnp.sum(c * x[:, None], axis=0)
    scale = jnp.where(load > cap, cap_safe / jnp.maximum(load, 1e-12), 1.0)
    return x * jnp.min(scale)


def dpbalance_round(demand, active, arrival, loss, capacity, budget_total,
                    now, forced, p):
    """One DPBalance round.  Returns (own selection, x of ``forced``)."""
    gamma = demand / jnp.maximum(budget_total, 1e-12)
    mu_ij = jnp.max(gamma, axis=-1)
    cap_frac = capacity / jnp.maximum(budget_total, 1e-9)
    active = active & ~jnp.any(gamma > cap_frac + FEAS, axis=-1)
    act = active.astype(gamma.dtype)
    g_i = jnp.sum(gamma * act[..., None], axis=1)
    mu_i = jnp.max(g_i, axis=-1)
    wait = jnp.maximum(now - arrival, 0.0)
    t_i = jnp.sum(wait * act, axis=1) / jnp.maximum(jnp.sum(act, axis=1), 1.0)
    w = mu_ij * act
    l_i = jnp.sum(w * loss, axis=1) / jnp.maximum(jnp.sum(w, axis=1), 1e-12)
    a_i = jnp.exp(-t_i / p["tau"]) * l_i
    mask = jnp.any(active, axis=1)
    x_i = _sp1(mu_i, a_i, g_i, mask, cap_frac, p["beta"], p["solver_iters"],
               p["solver_tol"])
    budget = g_i * x_i[:, None]
    a_ij = jnp.exp(-wait / p["tau"]) * loss
    km = p["kappa_max"]

    def sp2(g, m, aa, ac, b):
        sel0 = _greedy(g, m, ac, b)
        return _swap(g, m, aa, ac, sel0, b, km)

    own = jax.lax.map(lambda z: sp2(*z), (gamma, mu_ij, a_ij, active, budget))
    sel = own if forced is None else forced
    x = jax.vmap(lambda g, m, aa, s, b: _boost(g, m, aa, s, b, km)[0])(
        gamma, mu_ij, a_ij, sel, budget)
    return own, x


def dpf_round(demand, active, arrival, loss, capacity, budget_total, now,
              forced, p):
    """One DPF round: grant-if-fits in ascending dominant share."""
    M, N, K = demand.shape
    gamma = demand / jnp.maximum(budget_total, 1e-12)
    mu_ij = jnp.max(gamma, axis=-1)
    cap_frac = capacity / jnp.maximum(budget_total, 1e-9)
    active = active & ~jnp.any(gamma > cap_frac + FEAS, axis=-1)
    order = jnp.argsort(jnp.where(active, mu_ij, BIG).reshape(-1),
                        stable=True)

    def step(rem, xs):
        dem, act = xs
        ok = act & jnp.all(dem <= rem + FEAS)
        return jnp.where(ok, rem - dem, rem), ok

    _, taken = jax.lax.scan(step, cap_frac,
                            (gamma.reshape(M * N, K)[order],
                             active.reshape(-1)[order]))
    own = jnp.zeros(M * N, bool).at[order].set(taken).reshape(M, N)
    sel = own if forced is None else forced
    return own, sel.astype(demand.dtype)


ROUNDS = {"dpbalance": dpbalance_round, "dpf": dpf_round}


def _tick(st, adm, mint, forced, cap_in, t, retire, *, round_fn, p, guard):
    """One service tick: admit, mint, expire, decide, debit."""
    demand, spawn, done, arrival, loss, cap, budget, created = st
    if cap_in is not None:
        cap = cap_in.astype(cap.dtype)
    mask, a_loss, a_arr, a_spawn, rows, cols, slots, eps = adm
    minted, mint_budget = mint
    dt = demand.dtype
    # admission: the refilled slots' demand rows are rewritten in full
    demand = jnp.where(mask[..., None], 0.0, demand).astype(dt)
    demand = demand.at[rows, cols, slots].set(eps.astype(dt), mode="drop")
    loss = jnp.where(mask, a_loss.astype(dt), loss)
    arrival = jnp.where(mask, a_arr.astype(dt), arrival)
    spawn = jnp.where(mask, a_spawn, spawn)
    done = done & ~mask
    # mint: evict the slot's previous block, wipe demand admitted before
    stale = minted[None, None, :] & (spawn < t)[..., None]
    demand = jnp.where(stale, 0.0, demand).astype(dt)
    cap = jnp.where(minted, mint_budget.astype(dt), cap)
    budget = jnp.where(minted, mint_budget.astype(dt), budget)
    created = created | minted
    budget_total = jnp.where(created, budget, 1.0).astype(dt)
    pending = (spawn <= t) & ~done
    expired = retire & pending & ~jnp.any(demand > 0.0, axis=-1)
    pending = pending & ~expired
    eff = demand * pending[..., None].astype(dt)
    now = t.astype(dt) * ROUND_SECONDS
    own, x = round_fn(eff, pending, jnp.where(pending, arrival, 0.0),
                      jnp.where(pending, loss, 1.0), cap, budget_total, now,
                      forced, p)
    sel = own if forced is None else forced
    grants = eff * x[..., None]
    consumed = jnp.sum(grants, axis=(0, 1))
    if guard:       # overdraw guard of Alg. 1's grant step (DPBalance)
        over = consumed > cap * (1.0 + 1e-6) + 1e-7
        scale = jnp.min(jnp.where(over, cap / jnp.maximum(consumed, 1e-9),
                                  1.0))
        grants, consumed = grants * scale, consumed * scale
    spend = jnp.sum(grants, axis=(1, 2))
    cap = jnp.maximum(cap - consumed, 0.0).astype(dt)
    invalid = jnp.sum(sel & ~pending)
    done = done | sel | expired
    st = (demand, spawn, done, arrival, loss, cap, budget, created)
    return st, (own, expired, spend, invalid, cap)


@functools.lru_cache(maxsize=None)
def _compiled_tick(scheduler: str, params: tuple, forced: bool):
    p = dict(params)
    fn = functools.partial(_tick, round_fn=ROUNDS[scheduler], p=p,
                           guard=scheduler == "dpbalance")
    if not forced:
        return jax.jit(lambda st, adm, mint, t, r: fn(st, adm, mint, None,
                                                      None, t, r))
    return jax.jit(fn)


class ReferenceService:
    """The plain service, one tick at a time (see the module docstring)."""

    NEVER = np.iinfo(np.int32).max

    def __init__(self, deployment: dict, sched: dict, scheduler: str,
                 arrivals, dtype=jnp.float32):
        d = deployment
        self.M, self.N = d["analyst_slots"], d["pipeline_slots"]
        self.B = d["block_slots"]
        self.admit_batch, self.max_pending = d["admit_batch"], d["max_pending"]
        self.arrivals = arrivals
        self.bpr = arrivals.bpr
        self.mint_budget = np.repeat(
            arrivals.device_budget.astype(np.float32), arrivals.bpd)
        self.scheduler = scheduler
        self.params = tuple(sorted(sched.items()))
        self.dtype = dtype
        M, N, B = self.M, self.N, self.B
        self.state = (jnp.zeros((M, N, B), dtype),
                      jnp.full((M, N), self.NEVER, jnp.int32),
                      jnp.zeros((M, N), bool), jnp.zeros((M, N), dtype),
                      jnp.ones((M, N), dtype), jnp.zeros(B, dtype),
                      jnp.ones(B, dtype), jnp.zeros(B, bool))
        self.queue = deque()
        self.occupied = np.zeros((M, N), bool)
        self.owner = np.full(M, -1, np.int64)
        self.free_rows = list(range(M - 1, -1, -1))
        self.birth = np.full(B, -1, np.int64)       # mint tick per slot
        self.tick = 0

    # -------------------------------------------------------- admission
    def _admit(self, t: int):
        for b in self.arrivals.events[t]:
            if len(b.bids) > self.N or len(self.queue) >= self.max_pending:
                continue                              # rejected
            self.queue.append(b)
        M, N, B, bpr = self.M, self.N, self.B, self.bpr
        mask = np.zeros((M, N), bool)
        a_loss = np.zeros((M, N), np.float32)
        a_arr = np.zeros((M, N), np.float32)
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
                a_loss[row, c] = b.loss[j]
                a_arr[row, c] = b.tick * ROUND_SECONDS
                a_spawn[row, c] = spawn
                s = b.bids[j] % B
                # keep an entry while its block is still in the ring and
                # outlives the pipeline's activation
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
        n = rows.size
        size = 1 << max(n - 1, 0).bit_length()        # few compiled sizes
        pad = size - n
        rows = np.concatenate([rows, np.full(pad, M, np.int32)])   # dropped
        cols = np.concatenate([cols, np.zeros(pad, np.int32)])
        slots = np.concatenate([slots, np.zeros(pad, np.int32)])
        eps = np.concatenate([eps, np.zeros(pad, np.float32)])
        return (mask, a_loss, a_arr, a_spawn, rows, cols, slots, eps)

    def _release(self, freed: np.ndarray) -> None:
        rel = np.argwhere(freed & self.occupied)
        self.occupied[freed] = False
        for row in np.unique(rel[:, 0]) if rel.size else []:
            if not self.occupied[row].any() and self.owner[row] != -1:
                self.owner[row] = -1
                self.free_rows.append(int(row))

    # ------------------------------------------------------------- tick
    def step(self, forced=None, capacity=None):
        """Run one tick.  ``forced`` [M, N]: the program's selection;
        ``capacity`` [B]: the program's ledger before the tick (both or
        neither)."""
        t = self.tick
        adm = self._admit(t)
        bids = t * self.bpr + np.arange(self.bpr)
        minted = np.zeros(self.B, bool)
        minted[bids % self.B] = True
        mint_b = np.zeros(self.B, np.float32)
        mint_b[bids % self.B] = self.mint_budget
        self.birth[bids % self.B] = t
        retire = bool(bids.max() >= self.B)
        fn = _compiled_tick(self.scheduler, self.params, forced is not None)
        args = (self.state, adm, (minted, mint_b))
        if forced is not None:
            args += (np.asarray(forced, bool), np.asarray(capacity))
        self.state, out = fn(*args, np.int32(t), retire)
        own, expired, spend, invalid, cap = (np.asarray(o) for o in out)
        sel = own if forced is None else np.asarray(forced, bool)
        self._release(sel | expired)
        self.tick += 1
        return own, expired, spend, int(invalid), cap
