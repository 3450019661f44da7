"""The admission write per round, in ms: the ``admit_drain/write`` span
(the ``admit_batch`` call: its operands' host-to-device copies and the
dispatch of the COO scatter)."""
from perfbench.harness import scopes


def read(ctx):
    return scopes.span_ms_per_round(ctx, "admit_drain/write")
