"""SP1's device time per round, in ms: the alpha-fair dual ascent,
scope ``sp1``; the union of the intervals of the device ops on whose
``op_name`` the scope lies, inside the traced window
(``harness/scopes.py``)."""
from perfbench.harness import scopes


def read(ctx):
    return scopes.scope_ms_per_round(ctx, "sp1")
