"""Plain reference of the engine's episodes, written from the paper.

It imports nothing of the program.  An episode (arXiv 2402.09715 §VI,
``R`` rounds of 10 s) holds ``M`` analysts x ``N`` pipelines over the ``K``
blocks its devices mint, each pipeline with a fixed demand.  Round ``r``:

* the blocks minted at round ``r`` join the ledger with their device's
  budget; ``budget_total`` is a minted block's budget, 1 for the others;
* the round's pipelines are those whose analyst has arrived and that no
  earlier round granted (``active = spawned & ~done``);
* DPBalance decides: :func:`perfbench.harness.reference.dpbalance_round`
  (SP1, SP2 greedy cover, one pass of swaps, the kappa boost);
* the grants are debited, under the overdraw guard, and every block's
  capacity is floored at 0;
* the round's numbers: pipelines granted, dominant efficiency (Eq 8) and
  dominant fairness (Eq 9) of the realized dominant shares (Eqs 3, 7),
  and the epsilon left over all blocks.

:func:`replay` judges each round of the program from the program's own
state, as the live check does: the ledger the program held at the
round's start (its capacity share per block) and the pipelines its
earlier selections granted.  The reference decides the round on its own
and reports that selection, then applies the program's selection and
reports what it debits and the round's numbers.  :func:`own` runs each
episode from round 0 on the reference's own decisions and ledger: that is
how the control runs in the program's place.

Rounds are independent in :func:`replay`, so all rounds of all episodes of
a fleet run as one batch, ``BATCH`` at a time; :func:`own` batches the
episodes of one round.  ``dtype`` is the precision of every float the
reference holds (float32 as the configuration states; bfloat16 for the
control).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from .reference import FEAS, ROUND_SECONDS, dpbalance_round

BATCH = 64                      # episode-rounds decided side by side
INPUTS = ("demand", "loss", "arrival", "spawn", "budget", "born")


def _round(ep, r, done, capacity, cap_frac, forced, p):
    """One round of one episode (module docstring).  ``capacity`` [K] is
    the ledger in epsilon and ``cap_frac`` [K] the same ledger as a share
    of each block's total budget, the form the round decides on."""
    dt = ep["demand"].dtype
    created = ep["born"] <= r
    bt = jnp.where(created, ep["budget"], 1.0).astype(dt)
    active = (ep["spawn"][:, None] <= r) & ~done
    eff = ep["demand"] * active[..., None].astype(dt)
    gamma = eff / jnp.maximum(bt, 1e-12)
    arrival = jnp.where(active, ep["arrival"], 0.0).astype(dt)
    loss = jnp.where(active, ep["loss"], 1.0).astype(dt)
    now = r.astype(dt) * ROUND_SECONDS
    # the round on normalized demand and ledger (its own first step)
    own, x = dpbalance_round(gamma, active, arrival, loss, cap_frac,
                             jnp.ones_like(bt), now, forced, p)
    sel = own if forced is None else forced
    consumed = jnp.sum(eff * x[..., None], axis=(0, 1))
    over = consumed > capacity * (1.0 + 1e-6) + 1e-7
    scale = jnp.min(jnp.where(over, capacity / jnp.maximum(consumed, 1e-9),
                              1.0))
    left = jnp.maximum(capacity - consumed * scale, 0.0).astype(dt)
    # Eqs 7-9 over the analysts that have a feasible pipeline this round
    act = active & ~jnp.any(gamma > cap_frac + FEAS, axis=-1)
    a = act.astype(dt)
    wait = jnp.maximum(now - arrival, 0.0)
    t_i = jnp.sum(wait * a, axis=1) / jnp.maximum(jnp.sum(a, axis=1), 1.0)
    w = jnp.max(gamma, axis=-1) * a
    l_i = jnp.sum(w * loss, axis=1) / jnp.maximum(jnp.sum(w, axis=1), 1e-12)
    mask = jnp.any(act, axis=1)
    realized = jnp.max(jnp.sum(gamma * x[..., None], axis=1), axis=-1)
    util = realized * jnp.exp(-t_i / p["tau"]) * l_i * mask
    total = jnp.maximum(jnp.sum(util), 1e-12)
    share = jnp.clip(util / total, 1e-6, 1.0)
    beta = p["beta"]
    s = jnp.sum(jnp.where(mask, share ** (1.0 - beta), 0.0))
    sign = float(np.sign(1.0 - beta))
    fairness = sign * jnp.maximum(s, 1e-12) ** (1.0 / beta)
    return {"own": own, "left": left, "n_allocated": jnp.sum(sel),
            "efficiency": jnp.sum(util), "fairness": fairness,
            "leftover": jnp.sum(left)}


def _params(sched: dict, scheduler: str) -> tuple:
    if scheduler != "dpbalance":
        raise ValueError(f"the episode reference decides DPBalance rounds "
                         f"only, not {scheduler!r}")
    return tuple(sorted(sched.items()))


def _device(fleet: Dict, dtype) -> Dict:
    return {k: jnp.asarray(fleet[k], dtype if fleet[k].dtype == np.float32
                           else None) for k in INPUTS}


@functools.lru_cache(maxsize=None)
def _compiled_replay(params: tuple):
    p = dict(params)

    def item(fleet, e, r, done, cap_frac, forced):
        ep = {k: fleet[k][e] for k in INPUTS}
        dt = ep["demand"].dtype
        bt = jnp.where(ep["born"] <= r, ep["budget"], 1.0).astype(dt)
        return _round(ep, r, done, cap_frac * bt, cap_frac, forced, p)

    def run(fleet, e, r, done, cap_frac, forced):
        return jax.lax.map(lambda z: item(fleet, *z),
                           (e, r, done, cap_frac, forced), batch_size=BATCH)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _compiled_own(params: tuple):
    p = dict(params)

    def item(ep, r, done, capacity):
        dt = ep["demand"].dtype
        capacity = capacity + ep["budget"] * (ep["born"] == r).astype(dt)
        bt = jnp.where(ep["born"] <= r, ep["budget"], 1.0).astype(dt)
        cap_frac = capacity / jnp.maximum(bt, 1e-9)
        out = _round(ep, r, done, capacity, cap_frac, None, p)
        return dict(out, cap_frac=cap_frac)

    def run(fleet, r, done, capacity):
        return jax.lax.map(lambda z: item(z[0], r, z[1], z[2]),
                           (fleet, done, capacity), batch_size=BATCH)
    return jax.jit(run)


def replay(fleet: Dict, selected: np.ndarray, cap_frac: np.ndarray,
           sched: dict, scheduler: str, dtype=jnp.float32) -> Dict:
    """The reference's view of every round of a fleet the program ran.

    ``fleet``: the inputs (``INPUTS``, numpy, a leading episode axis);
    ``selected [E, R, M, N]`` and ``cap_frac [E, R, K]``: the program's
    selection in each round and its ledger at the round's start, as a
    share of each block's budget.  Returns ``[E, R, ...]`` arrays: ``own``
    (the reference's own selection), ``left`` (every block's epsilon after
    the program's selection is debited), ``n_allocated``, ``efficiency``,
    ``fairness`` and ``leftover``."""
    fn = _compiled_replay(_params(sched, scheduler))
    E, R = selected.shape[:2]
    done = np.zeros_like(selected)
    done[:, 1:] = np.logical_or.accumulate(selected, axis=1)[:, :-1]
    e, r = np.meshgrid(np.arange(E), np.arange(R), indexing="ij")
    out = fn(_device(fleet, dtype), jnp.asarray(e.reshape(-1), jnp.int32),
             jnp.asarray(r.reshape(-1), jnp.int32),
             jnp.asarray(done.reshape(E * R, *done.shape[2:])),
             jnp.asarray(cap_frac.reshape(E * R, -1), dtype),
             jnp.asarray(selected.reshape(E * R, *selected.shape[2:])))
    return {k: np.asarray(v).reshape(E, R, *v.shape[1:])
            for k, v in out.items()}


def own(fleet: Dict, n_rounds: int, sched: dict, scheduler: str,
        dtype=jnp.float32) -> Dict:
    """The reference's own run of every episode of ``fleet``, shaped as
    the program's answers: ``selected [E, R, M, N]``, ``cap_frac [E, R,
    K]`` at each round's start, ``n_allocated``, ``round_efficiency``,
    ``round_fairness``, ``leftover`` ``[E, R]``, ``final_capacity [E, K]``
    and ``final_done [E, M, N]``."""
    fn = _compiled_own(_params(sched, scheduler))
    dev = _device(fleet, dtype)
    E, M, N, K = dev["demand"].shape
    done = jnp.zeros((E, M, N), bool)
    capacity = jnp.zeros((E, K), dtype)
    rounds = []
    for r in range(n_rounds):
        out = fn(dev, jnp.int32(r), done, capacity)
        done = done | out["own"]
        capacity = out["left"]
        rounds.append(jax.device_get(out))
    def stack(key, dt=np.float32):
        return np.stack([np.asarray(o[key], dt) for o in rounds], axis=1)
    return {"selected": stack("own", bool), "cap_frac": stack("cap_frac"),
            "n_allocated": stack("n_allocated", np.int32),
            "round_efficiency": stack("efficiency"),
            "round_fairness": stack("fairness"),
            "leftover": stack("leftover"),
            "final_capacity": np.asarray(capacity, np.float32),
            "final_done": np.asarray(done)}
