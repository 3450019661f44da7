"""Arrival generator of the benchmark: the paper's §VI demand model.

A copy of the numpy draws of the service's own arrival trace (the
``poisson`` pattern), kept here so that a change to the program's generator
does not move the benchmark's inputs.  Each tick draws ``Poisson(rate)``
analyst batches (at least one at tick 0); a batch targets a device subset
(``subset_frac`` of the devices w.p. ``p_subset_devices``, else all) and
holds ``pipelines_per_analyst`` pipelines, each demanding the latest 1 or
``DEPTH`` blocks of every targeted device (``DEPTH`` w.p. ``p_ten_blocks``)
with mice or elephant epsilon, and a matching degree ``loss ~ U(0.5, 1)``.

Global block ids follow the ledger's layout: at tick ``t`` the devices mint
``blocks_per_device`` blocks each, ids ``t * bpr + device * bpd + s``.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

DEPTH = 10                      # deepest per-device demand window (blocks)


@dataclasses.dataclass
class Batch:
    """One analyst batch, as generated (program-independent)."""

    analyst: int
    tick: int
    bids: List[np.ndarray]      # per pipeline: global block ids (int64)
    eps: List[np.ndarray]       # per pipeline: epsilon per block (float32)
    loss: np.ndarray            # [n_pipelines] float32


class Arrivals:
    """Seeded arrival stream; ``ticks(n)`` extends the recorded prefix.

    The draws of tick ``t`` depend only on the seed, so a longer prefix
    never changes a shorter one."""

    def __init__(self, deployment: dict, seed: int):
        self.d = deployment
        self.rng = np.random.default_rng(int(seed))
        self.device_budget = self.rng.uniform(*deployment["budget_range"],
                                              deployment["n_devices"])
        self.bpd = int(deployment["blocks_per_device"])
        self.bpr = int(deployment["n_devices"]) * self.bpd
        self.events: List[List[Batch]] = []
        self._next_analyst = 0

    def ticks(self, n: int) -> List[List[Batch]]:
        while len(self.events) < n:
            t = len(self.events)
            k = int(self.rng.poisson(self.d["arrival_rate"]))
            if t == 0:
                k = max(k, 1)
            self.events.append([self._draw(t) for _ in range(k)])
        return self.events

    def _draw(self, tick: int) -> Batch:
        d, rng = self.d, self.rng
        bpd, bpr = self.bpd, self.bpr
        T = (tick + 1) * bpd
        subset = rng.random() < d["p_subset_devices"]
        n_dev = max(1, int(d["subset_frac"] * d["n_devices"])) if subset \
            else d["n_devices"]
        devices = rng.choice(d["n_devices"], size=n_dev, replace=False)
        bids, eps, loss = [], [], []
        for _ in range(d["pipelines_per_analyst"]):
            mice = rng.random() < d["mice_frac"]
            lo, hi = d["mice_eps"] if mice else d["elephant_eps"]
            depth = DEPTH if rng.random() < d["p_ten_blocks"] else 1
            ts = np.arange(max(0, T - depth), T)
            base = (ts // bpd) * bpr + (ts % bpd)
            b = (devices[:, None] * bpd + base[None, :]).reshape(-1)
            bids.append(b.astype(np.int64))
            eps.append(rng.uniform(lo, hi, b.size).astype(np.float32))
            loss.append(rng.uniform(0.5, 1.0))
        aid = self._next_analyst
        self._next_analyst += 1
        return Batch(analyst=aid, tick=tick, bids=bids, eps=eps,
                     loss=np.asarray(loss, np.float32))
