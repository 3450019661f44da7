"""The Rényi-DP grant path's device time per round, in ms: scope
``order_fits`` (inside ``schedule``: the per-order share key, the
infeasibility screen and the order-axis grant-if-fits scan); the union of
the intervals of the device ops on whose ``op_name`` the scope lies,
inside the traced window (``harness/rdp_scopes.py``)."""
from perfbench.harness import rdp_scopes


def read(ctx):
    return rdp_scopes.scope_ms_per_round(ctx, "order_fits")
