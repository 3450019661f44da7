"""Demand curves of the benchmark's Rényi-DP stream (the ``privatekube_rdp``
configuration), drawn on top of the paper's §VI arrivals
(:mod:`perfbench.harness.generator`).

The harness's own copy of the curves and their calibration: it imports
nothing of the program, so a change there does not move the benchmark's
inputs.  The §VI stream is drawn unchanged (the same analysts, ticks,
blocks and matching degrees for a seed); each pipeline's first per-block
epsilon draw is its §VI demand ``eps``, its per-block scale becomes 1.0,
and its curve is a mechanism calibrated so that its best share of a
fresh block, ``min_a d(a) / eps_G(a)``, is ``eps / mean_budget`` (the
mean §VI device budget, 1.25).  The mechanism, from its own stream
seeded by ``(seed, MECHANISM_STREAM)``, is Gaussian or Laplace with
probability ``p_gaussian`` each:

* Gaussian (sensitivity 1, noise sigma): ``d(a) = a / (2 sigma^2)``,
  calibrated in closed form;
* Laplace (scale b) [Mironov '17, Prop. 6]: ``d(a) = log(a/(2a-1)
  e^{(a-1)/b} + (a-1)/(2a-1) e^{-a/b}) / (a-1)``, summed in log space,
  calibrated by bisection on ``log b``.
"""
from __future__ import annotations

import numpy as np

MECHANISM_STREAM = 0x5D9F


def capacity(orders, eps_g: float, delta_g: float) -> np.ndarray:
    """A fresh block's capacity at each order: eps_g - ln(1/delta)/(a-1)."""
    a = np.asarray(orders, np.float64)
    return eps_g - np.log(1.0 / delta_g) / (a - 1.0)


def usable(rdp: dict):
    """(orders, capacity) of the configuration's usable orders (> 0)."""
    cap = capacity(rdp["orders"], rdp["eps_g"], rdp["delta_g"])
    keep = cap > 0
    return np.asarray(rdp["orders"], np.float64)[keep], cap[keep]


def gaussian(sigma, a):
    return np.asarray(a, np.float64) / (2.0 * sigma ** 2)


def laplace(b, a):
    a = np.asarray(a, np.float64)
    lse = np.logaddexp(np.log(a / (2 * a - 1)) + (a - 1) / b,
                       np.log((a - 1) / (2 * a - 1)) - a / b)
    return lse / (a - 1)


def calibrate(kind: str, share, orders, cap) -> np.ndarray:
    """The curves ``[n, A]`` of mechanism ``kind`` whose best shares are
    ``share [n]``: Gaussian in closed form, Laplace by bisection on
    ``log b`` (the best share falls as b grows), all pipelines at once."""
    share = np.asarray(share, np.float64)
    if kind == "gaussian":
        best = np.min(gaussian(1.0, orders) / cap)     # sigma = 1
        return gaussian(np.sqrt(best / share)[:, None], orders[None, :])
    lo = np.full(share.shape, np.log(1e-4))
    hi = np.full(share.shape, np.log(1e4))
    for _ in range(60):                 # 18.4 / 2^60 in log b
        mid = 0.5 * (lo + hi)
        over = np.min(laplace(np.exp(mid)[:, None], orders[None, :]) / cap,
                      axis=1) > share
        lo, hi = np.where(over, mid, lo), np.where(over, hi, mid)
    return laplace(np.exp(0.5 * (lo + hi))[:, None], orders[None, :])


class Curves:
    """Attaches curves to a :class:`~perfbench.harness.generator.Arrivals`
    stream's batches, tick by tick, as ``batch.curves`` (float32 ``[A]``
    per pipeline), setting every ``batch.eps`` to ones.  Pipelines draw
    their mechanism in stream order, so the curves of a tick do not
    depend on how the stream was extended."""

    def __init__(self, deployment: dict, rdp: dict, seed: int):
        self.orders, self.cap = usable(rdp)
        self.mean_budget = float(np.mean(deployment["budget_range"]))
        self.p_gaussian = float(rdp["p_gaussian"])
        self.rng = np.random.default_rng([int(seed), MECHANISM_STREAM])
        self.done = 0

    def attach(self, events) -> None:
        """Give every batch of ``events`` (a tick list) from the first one
        not yet seen its curves."""
        batches = [b for tick in events[self.done:] for b in tick]
        self.done = len(events)
        eps = np.asarray([float(e[0]) for b in batches for e in b.eps])
        if not eps.size:
            return
        gauss = self.rng.random(eps.size) < self.p_gaussian
        curves = np.empty((eps.size, len(self.orders)), np.float32)
        for kind, pick in (("gaussian", gauss), ("laplace", ~gauss)):
            if pick.any():
                curves[pick] = calibrate(kind, eps[pick] / self.mean_budget,
                                         self.orders, self.cap)
        i = 0
        for b in batches:
            n = len(b.eps)
            b.curves = list(curves[i:i + n])
            b.eps = [np.ones_like(e) for e in b.eps]
            i += n
