"""Host<->device transfers per round, both directions: the program's
transfer counter, read from the ``h2d=`` / ``d2h=`` arguments that each
round's last span (``flaas/telemetry_fold``) carries in the traced window
(``harness/scopes.py``)."""
from perfbench.harness import scopes


def read(ctx):
    red = scopes.for_run(ctx)
    if red is None or not red["transfers"]:
        return None
    return red["transfers"] / ctx["rounds"]
