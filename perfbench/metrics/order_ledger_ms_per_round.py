"""The Rényi-DP ledger's device time per round, in ms: scope
``order_ledger`` (inside ``ledger``: the per-order mint, the per-order
debit and the exhausted-block counters); the union of the intervals of
the device ops on whose ``op_name`` the scope lies, inside the traced
window (``harness/rdp_scopes.py``)."""
from perfbench.harness import rdp_scopes


def read(ctx):
    return rdp_scopes.scope_ms_per_round(ctx, "order_ledger")
