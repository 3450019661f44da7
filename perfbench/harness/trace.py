"""Reduction of a profiler trace to device busy time, idle gaps and ops.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
:func:`load_events` flattens it to plain event records; :func:`reduce`
works on those records alone, so the reduction can be checked on a small
recorded trace without a chip.

* Device ops: the events of the ``XLA Ops`` line of every ``/device:TPU:<n>``
  plane.  Busy time is the union of their intervals inside the window,
  averaged over the chips that ran anything.
* Window: the host annotation named ``window`` (the harness opens it
  around the measured rounds).
* Idle gaps: the stretches of the window in which a chip ran no op.  Each
  gap is charged to the host annotation (``flaas/<phase>``, the service's
  own phase spans) that overlaps it most, or to ``other``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "window"
PHASE_PREFIX = "flaas/"


def load_events(trace_dir: str) -> List[Dict]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir`` as
    ``{"plane", "line", "name", "start_ns", "dur_ns"}``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        return []
    prof = ProfileData.from_file(paths[-1])
    out = []
    for plane in prof.planes:
        device = DEVICE_PLANE.match(plane.name)
        if not (device or plane.name.startswith("/host")):
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                name = ev.name
                if not device and name != WINDOW and \
                        not name.startswith(PHASE_PREFIX):
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": name, "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns)})
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(events: List[Dict], top: int = 10) -> Optional[Dict]:
    """``{"busy_s", "window_s", "device_ops", "idle_gaps"}``, or None when
    the trace holds no window or no device op inside it."""
    win = [e for e in events if e["name"] == WINDOW
           and not DEVICE_PLANE.match(e["plane"])]
    if not win:
        return None
    w0 = win[0]["start_ns"]
    w1 = w0 + win[0]["dur_ns"]
    phases = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"],
                     e["name"][len(PHASE_PREFIX):]) for e in events
                    if e["name"].startswith(PHASE_PREFIX)
                    and not DEVICE_PLANE.match(e["plane"]))
    starts = [p[0] for p in phases]
    longest = max((p[1] - p[0] for p in phases), default=0.0)
    per_chip: Dict[str, list] = {}
    op_time: Dict[str, float] = {}
    for e in events:
        if not (DEVICE_PLANE.match(e["plane"]) and e["line"] == OPS_LINE):
            continue
        s = max(e["start_ns"], w0)
        t = min(e["start_ns"] + e["dur_ns"], w1)
        if t <= s:
            continue
        per_chip.setdefault(e["plane"], []).append((s, t))
        op_time[e["name"]] = op_time.get(e["name"], 0.0) + (t - s) * 1e-9
    if not per_chip:
        return None
    busy, gaps = [], {}
    for ivs in per_chip.values():
        merged = _union(ivs)
        busy.append(sum(t - s for s, t in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, t in zip(edges[0::2], edges[1::2]):
            if t <= s:
                continue
            best, who = 0.0, "other"
            # the spans that can overlap [s, t] start in [s - longest, t)
            lo = bisect.bisect_left(starts, s - longest)
            hi = bisect.bisect_left(starts, t)
            for ps, pt, name in phases[lo:hi]:
                ov = min(t, pt) - max(s, ps)
                if ov > best:
                    best, who = ov, name
            gaps[who] = gaps.get(who, 0.0) + (t - s) * 1e-9 / len(per_chip)
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy) / len(busy) * 1e-9,
            "window_s": (w1 - w0) * 1e-9,
            "device_ops": [[k, v / len(per_chip)] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
