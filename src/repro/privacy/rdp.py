"""Renyi differential privacy primitives (paper §III-A, Defs 1-4).

The FLaaS platform accounts privacy in (alpha, eps)-RDP [Mironov'17].  The
training substrate adds Gaussian noise to clipped per-example gradients
(DP-SGD); each application of the Gaussian mechanism with noise multiplier
sigma costs eps(alpha) = alpha / (2 sigma^2) at Renyi order alpha.  RDP
composes additively over sequential uses (Def 4) and takes the max over
disjoint data (Def 3) — exactly the bounded+additive structure that lets the
scheduler treat privacy as a consumable resource.

All functions are jnp-based and jit/vmap friendly so the accountant can run
on-device alongside the scheduler.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp
import numpy as np

# Standard order grid (Opacus/TF-Privacy style) + a few low orders.
DEFAULT_ORDERS = np.array(
    [1.25, 1.5, 1.75, 2.0, 2.5, 3.0, 4.0, 5.0, 6.0, 8.0, 10.0, 12.0,
     16.0, 20.0, 24.0, 32.0, 48.0, 64.0], dtype=np.float64)


def gaussian_rdp(sigma, alpha):
    """RDP of the Gaussian mechanism with sensitivity 1: eps = alpha/(2 sigma^2)."""
    sigma = jnp.asarray(sigma)
    return jnp.asarray(alpha) / (2.0 * sigma ** 2)


def subsampled_gaussian_rdp(sigma, q, alpha):
    """Upper bound on RDP of the Poisson-subsampled Gaussian mechanism.

    Uses the standard 'q^2 alpha / sigma^2' regime bound valid for
    q <= 1/5, sigma >= 4 and alpha bounded by sigma^2 L / 2 (Abadi-style
    moments-accountant asymptotics); falls back to the unsubsampled bound
    where that regime does not apply.  Tight numerical accountants exist but
    this closed form is what budget *scheduling* needs: a monotone,
    composable per-step cost.
    """
    sigma = jnp.asarray(sigma, jnp.float64 if _x64() else jnp.float32)
    q = jnp.asarray(q)
    alpha = jnp.asarray(alpha)
    amplified = 3.5 * q ** 2 * alpha / sigma ** 2
    plain = gaussian_rdp(sigma, alpha)
    regime = (q <= 0.2) & (sigma >= 1.0)
    return jnp.where(regime, jnp.minimum(amplified, plain), plain)


def _x64() -> bool:
    import jax
    return jax.config.read("jax_enable_x64")


def rdp_to_dp(eps_rdp, alpha, delta):
    """Convert (alpha, eps)-RDP to (eps, delta)-DP:
    eps_dp = eps_rdp + log(1/delta) / (alpha - 1)."""
    return jnp.asarray(eps_rdp) + jnp.log(1.0 / delta) / (jnp.asarray(alpha) - 1.0)


def sigma_for_rdp_budget(eps_rdp, alpha, steps: int = 1):
    """Smallest Gaussian noise multiplier whose `steps`-fold composition stays
    within an (alpha, eps_rdp) budget: sigma = sqrt(steps * alpha / (2 eps))."""
    eps_rdp = jnp.maximum(jnp.asarray(eps_rdp), 1e-12)
    return jnp.sqrt(steps * jnp.asarray(alpha) / (2.0 * eps_rdp))


def best_dp_over_orders(eps_rdp_per_order, orders, delta):
    """Given composed RDP at each order, report the tightest (eps, delta)-DP."""
    eps = rdp_to_dp(jnp.asarray(eps_rdp_per_order), jnp.asarray(orders), delta)
    idx = jnp.argmin(eps)
    return eps[idx], jnp.asarray(orders)[idx]


def laplace_rdp(b, alpha):
    """RDP of the Laplace mechanism with sensitivity 1 and scale ``b``
    [Mironov'17, Prop. 6], for alpha > 1:

        eps(alpha) = log(alpha/(2 alpha - 1) e^{(alpha-1)/b}
                         + (alpha-1)/(2 alpha - 1) e^{-alpha/b}) / (alpha - 1)

    summed in log space (``logaddexp``): the plain exponentials overflow
    float32 at small ``b``."""
    b = jnp.asarray(b)
    alpha = jnp.asarray(alpha)
    lse = jnp.logaddexp(
        jnp.log(alpha / (2.0 * alpha - 1.0)) + (alpha - 1.0) / b,
        jnp.log((alpha - 1.0) / (2.0 * alpha - 1.0)) - alpha / b)
    return lse / (alpha - 1.0)


def capacity_curve(orders, eps_g: float, delta_g: float) -> np.ndarray:
    """A block's RDP capacity at each order (Luo et al., OSDI '21, §3):
    the (alpha, eps)-RDP budget whose conversion to (eps_g, delta_g)-DP
    stays within the block's global budget,
    ``eps_g(alpha) = eps_g - log(1/delta_g) / (alpha - 1)``.  Orders where
    it is not positive cannot hold any grant."""
    a = np.asarray(orders, np.float64)
    return eps_g - np.log(1.0 / delta_g) / (a - 1.0)


@dataclasses.dataclass(frozen=True)
class RdpSpec:
    """A Rényi-DP block ledger: every block's budget is the curve
    :func:`capacity_curve` over ``orders``.  Construction keeps only the
    usable orders (capacity > 0), so ``len(spec.orders)`` is the order
    axis A of every per-order array; a spec with none is an error.
    Hashable: it keys the compiled service programs."""

    orders: Tuple[float, ...] = tuple(DEFAULT_ORDERS.tolist())
    eps_g: float = 10.0
    delta_g: float = 1e-7

    def __post_init__(self):
        cap = capacity_curve(self.orders, self.eps_g, self.delta_g)
        usable = tuple(float(a) for a, c in zip(self.orders, cap) if c > 0)
        if not usable:
            raise ValueError(
                f"no usable RDP order: eps_g={self.eps_g}, delta_g="
                f"{self.delta_g} leave every order in {self.orders} "
                f"without capacity")
        object.__setattr__(self, "orders", usable)
        object.__setattr__(self, "eps_g", float(self.eps_g))
        object.__setattr__(self, "delta_g", float(self.delta_g))

    @property
    def capacity(self) -> np.ndarray:
        """[A] float32 capacity of a freshly minted block at each order."""
        return capacity_curve(self.orders, self.eps_g,
                              self.delta_g).astype(np.float32)
