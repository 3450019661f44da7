"""The ledger's device time per round, in ms: scope ``ledger`` (mint,
retire and debit in each tick, the paged hot-ring reductions and the
eviction sweep); the union of the intervals of the device ops on whose
``op_name`` the scope lies, inside the traced window
(``harness/scopes.py``)."""
from perfbench.harness import scopes


def read(ctx):
    return scopes.scope_ms_per_round(ctx, "ledger")
