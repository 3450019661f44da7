"""The scheduler's device time per round, in ms: scope ``schedule`` (the
round's inputs, then SP1 and SP2, or a baseline's grant scan); the union
of the intervals of the device ops on whose ``op_name`` the scope lies,
inside the traced window (``harness/scopes.py``)."""
from perfbench.harness import scopes


def read(ctx):
    return scopes.scope_ms_per_round(ctx, "schedule")
