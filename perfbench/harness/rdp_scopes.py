"""Device time in the Rényi-DP ledger's named scopes (``order_fits``,
``order_ledger``), which ``scopes.SCOPES`` does not list: read from the
same trace with ``scopes.load``, as the union of the intervals of the
device ops on whose ``op_name`` the scope lies, inside the window,
averaged over the chips that ran anything.  A trace without the scope
(a program without the RDP ledger) reads None."""
from __future__ import annotations

import functools
import glob
import os
from typing import Dict, Optional

import numpy as np

from . import scopes


@functools.lru_cache(maxsize=2)
def _scope_s(path: str, mtime: float, scope: str):
    """(window seconds, the scope's seconds) of the trace at ``path``."""
    trace = scopes.load(path)
    if trace.get("window") is None:
        return None
    w0, w1 = trace["window"]
    names = trace.get("op_names", {})
    per_chip = []
    for dev in trace["devices"]:
        ops = dev["ops"]
        s = np.asarray(ops["start_ns"], float)
        e = np.asarray(ops["end_ns"], float)
        key = np.asarray(ops["key"], np.int64)
        keep = (e > w0) & (s < w1)
        if not keep.any():
            continue
        inside = np.asarray([scope in names.get(mod, {}).get(op, "")
                             .split("/") for mod, op in dev["keys"]],
                            bool)[key[keep]]
        per_chip.append(scopes._length(np.clip(s[keep], w0, w1)[inside],
                                       np.clip(e[keep], w0, w1)[inside]))
    if not per_chip:
        return None
    return (w1 - w0) * 1e-9, float(np.mean(per_chip)) * 1e-9


def scope_ms_per_round(ctx: Dict, scope: str) -> Optional[float]:
    """The scope's device ms per round of the traced run ``ctx``
    describes (the newest trace, whose window must be the run's)."""
    tr = ctx.get("trace")
    if tr is None or not ctx.get("rounds"):
        return None
    paths = glob.glob(os.path.join(scopes.TRACE_ROOT, "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    got = _scope_s(path, os.path.getmtime(path), scope)
    if got is None or abs(got[0] - tr["window_s"]) > 1e-6 or got[1] <= 0.0:
        return None
    return got[1] / ctx["rounds"] * 1e3
