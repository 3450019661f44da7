"""Device time by named scope, launch latency, idle time by innermost span
and transfer counts, from the profiler trace of a traced run.

The program names its device work with ``jax.named_scope`` (``schedule``,
``sp1``, ``sp2``, ``grant_scan``, ``ledger``, ``round_metrics``,
``admit``): each scope is one element of an HLO op's ``op_name``
metadata (``jit(f)/while/body/schedule/sp1/...``).  A TPU trace names
each device op by its HLO instruction (``%fusion.27 = f32[...] ...``) and
carries no ``op_name`` (checked on a v5e trace); the profiler keeps every
program's HLO in the ``/host:metadata`` plane (one ``Hlo Proto`` per
module), so each op's scopes are looked up there: the op's module is the
``XLA Modules`` event that encloses it on its device's timeline.  An op
left without scope metadata by the compiler (the admission's scatter
fusion) takes the scope that every named op of its module shares, if
there is one.

:func:`load` flattens an ``.xplane.pb`` to :func:`reduce`'s plain input
(so the reduction is checked on a small recorded trace without a chip):

* ``window``: ``[start_ns, end_ns]`` of the harness's ``window`` span;
* ``spans``: ``[name, start_ns, end_ns, tick]`` of every ``flaas/<span>``
  annotation;
* ``transfers``: ``[start_ns, count]`` of each annotation that carries a
  round's transfer counts (``h2d=``, ``d2h=``; the round's last span);
* ``devices``: per device plane, ``modules`` (``[name, start_ns,
  end_ns]``, by start), ``keys`` (``[hlo_module, hlo_op]``) and ``ops``
  (columns ``key``, an index into ``keys``, ``start_ns``, ``end_ns``);
* ``op_names``: ``{hlo_module: {hlo_op: op_name}}``.

The device and host planes are read through ``jax.profiler.ProfileData``,
as ``harness/trace.py`` reads them.  ``ProfileData`` does not reach the
metadata plane's per-module stats, so the ``Hlo Proto``s are parsed with
the part of ``xplane.proto`` (XSpace) and ``hlo.proto`` (HloProto) that
names them, declared below with their field numbers; protobuf skips the
fields it leaves out.

:func:`reduce` gives, averaged over the chips that ran anything, inside
the window: busy seconds; seconds per scope (the union of the intervals
of the ops on whose ``op_name`` the scope lies, so ``schedule`` holds
``sp1``); the busy seconds outside every scope; each round's launch (the
``flaas/chunk_execute`` span's start on the host clock to the first op of
that round's run of the chunk program on the device clock: a diagnostic,
since the two clocks of a trace can disagree by tenths of a millisecond);
idle seconds charged to the innermost span open at each idle instant; and
the transfers counted.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from typing import Dict, List, Optional

import numpy as np

SCOPES = ("schedule", "sp1", "sp2", "grant_scan", "ledger",
          "round_metrics", "admit")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
METADATA_PLANE = "/host:metadata"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW = "window"
SPAN_PREFIX = "flaas/"
COUNTS = ("h2d", "d2h")
LAUNCH_SPAN = "chunk_execute"
CHUNK_MODULE = "jit_flaas_chunk("
LAUNCH_UNTIL = "host_sync/device_wait"


# ------------------------------------------------------------ protobuf
@functools.lru_cache(maxsize=None)
def _messages():
    """(XSpace, HloProto) message classes, in a private descriptor pool:
    only what leads to the metadata plane's ``Hlo Proto`` stats."""
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory
    F = descriptor_pb2.FieldDescriptorProto
    I64, STR, BYT, MSG = F.TYPE_INT64, F.TYPE_STRING, F.TYPE_BYTES, \
        F.TYPE_MESSAGE
    REP = F.LABEL_REPEATED
    fd = descriptor_pb2.FileDescriptorProto(
        name="perfbench_trace.proto", package="pbt", syntax="proto3")

    def message(name, fields, maps=()):
        m = fd.message_type.add(name=name)
        for fname, number, ftype, *more in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=REP if "rep" in more else F.LABEL_OPTIONAL)
            ref = [x for x in more if x.startswith(".")]
            if ref:
                f.type_name = ref[0]
        for entry, value in maps:
            e = m.nested_type.add(name=entry)
            e.options.map_entry = True
            e.field.add(name="key", number=1, type=I64,
                        label=F.LABEL_OPTIONAL)
            e.field.add(name="value", number=2, type=MSG,
                        label=F.LABEL_OPTIONAL, type_name=value)

    message("XSpace", [("planes", 1, MSG, "rep", ".pbt.XPlane")])
    message("XPlane", [
        ("name", 2, STR),
        ("event_metadata", 4, MSG, "rep", ".pbt.XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, MSG, "rep", ".pbt.XPlane.StatMetadataEntry")],
        maps=(("EventMetadataEntry", ".pbt.XEventMetadata"),
              ("StatMetadataEntry", ".pbt.XStatMetadata")))
    message("XStat", [("metadata_id", 1, I64), ("bytes_value", 6, BYT)])
    message("XEventMetadata", [("name", 2, STR),
                               ("stats", 5, MSG, "rep", ".pbt.XStat")])
    message("XStatMetadata", [("name", 2, STR)])
    message("HloProto", [("hlo_module", 1, MSG, ".pbt.HloModuleProto")])
    message("HloModuleProto", [
        ("computations", 3, MSG, "rep", ".pbt.HloComputationProto")])
    message("HloComputationProto", [
        ("instructions", 2, MSG, "rep", ".pbt.HloInstructionProto")])
    message("HloInstructionProto", [("name", 1, STR),
                                    ("metadata", 7, MSG, ".pbt.OpMetadata")])
    message("OpMetadata", [("op_name", 2, STR)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    get = message_factory.GetMessageClass
    return (get(pool.FindMessageTypeByName("pbt.XSpace")),
            get(pool.FindMessageTypeByName("pbt.HloProto")))


def _op_names(data: bytes) -> Dict[str, Dict[str, str]]:
    """``{hlo_module: {hlo_op: op_name}}`` from the metadata plane of the
    serialized XSpace ``data``."""
    XSpace, HloProto = _messages()
    space = XSpace()
    space.ParseFromString(data)
    out = {}
    for plane in space.planes:
        if plane.name != METADATA_PLANE:
            continue
        hlo_stat = {k for k, v in plane.stat_metadata.items()
                    if v.name == "Hlo Proto"}
        for md in plane.event_metadata.values():
            for st in md.stats:
                if st.metadata_id not in hlo_stat:
                    continue
                hlo = HloProto()
                hlo.ParseFromString(st.bytes_value)
                out[md.name] = {ins.name: ins.metadata.op_name
                                for comp in hlo.hlo_module.computations
                                for ins in comp.instructions}
    return out


def _hlo_op(event_name: str) -> str:
    """``%fusion.27 = f32[...] fusion(...)`` -> ``fusion.27``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def newest_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str) -> Dict:
    """The trace at ``path`` as :func:`reduce`'s input (module docstring)."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    prof = ProfileData.from_serialized_xspace(data)
    window, spans, transfers, devices = None, [], [], []
    for plane in prof.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(_device(plane))
            continue
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == WINDOW:
                    window = window or [ev.start_ns, ev.end_ns]
                    continue
                if not name.startswith(SPAN_PREFIX):
                    continue
                stats = dict(ev.stats)
                tick = stats.get("tick")
                spans.append([name, ev.start_ns, ev.end_ns,
                              None if tick is None else int(tick)])
                if all(k in stats for k in COUNTS):
                    transfers.append([ev.start_ns,
                                      sum(int(stats[k]) for k in COUNTS)])
    return {"window": window, "spans": spans, "transfers": transfers,
            "devices": devices, "op_names": _op_names(data)}


def _device(plane) -> Dict:
    """One device plane: its modules, and its ops each keyed by its module
    (the ``XLA Modules`` event that encloses it) and HLO instruction."""
    lines = {line.name: line for line in plane.lines}
    mods = []
    if MODULES_LINE in lines:
        mods = sorted(([ev.name, ev.start_ns, ev.end_ns]
                       for ev in lines[MODULES_LINE].events),
                      key=lambda m: m[1])
    empty = {"key": [], "start_ns": [], "end_ns": []}
    if OPS_LINE not in lines:
        return {"modules": mods, "keys": [], "ops": empty}
    names: Dict[str, int] = {}
    rows = [(names.setdefault(ev.name, len(names)), ev.start_ns, ev.end_ns)
            for ev in lines[OPS_LINE].events]
    if not rows:
        return {"modules": mods, "keys": [], "ops": empty}
    mid = np.fromiter((r[0] for r in rows), np.int64, len(rows))
    s = np.fromiter((r[1] for r in rows), np.float64, len(rows))
    e = np.fromiter((r[2] for r in rows), np.float64, len(rows))
    m_start = np.asarray([m[1] for m in mods], float)
    m_end = np.asarray([m[2] for m in mods], float)
    i = np.searchsorted(m_start, s, side="right") - 1
    inside = (i >= 0) & (s < m_end[np.maximum(i, 0)] if len(mods)
                         else np.zeros(len(s), bool))
    mod_idx = np.where(inside, i, -1)
    width = len(names)
    uniq, key = np.unique((mod_idx + 1) * width + mid, return_inverse=True)
    op = [_hlo_op(n) for n in names]          # names in insertion order
    keys = []
    for u in uniq.tolist():
        mi, m = divmod(u, width)
        keys.append([mods[mi - 1][0] if mi > 0 else "", op[m]])
    return {"modules": mods, "keys": keys,
            "ops": {"key": key, "start_ns": s, "end_ns": e}}


# ------------------------------------------------------------ reduction
def _merge(starts: np.ndarray, ends: np.ndarray):
    """The union of ``[starts, ends)`` as sorted disjoint intervals."""
    if not len(starts):
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > e[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(s) - 1)
    return s[first], e[last]


def _length(starts, ends) -> float:
    s, e = _merge(np.asarray(starts, float), np.asarray(ends, float))
    return float(np.sum(e - s))


def op_scopes(op_name: str) -> List[str]:
    """The named scopes on an ``op_name`` path, outermost first."""
    return [p for p in op_name.split("/") if p in SCOPES]


def _innermost_segments(spans):
    """Disjoint ``(start, end, name)`` pieces of the ``flaas/`` spans, each
    charged to the innermost span open over it (spans nest)."""
    marks = []
    for name, s, e, _ in spans:
        if name.startswith(SPAN_PREFIX) and e > s:
            marks.append((s, 1, -e, name[len(SPAN_PREFIX):]))
            marks.append((e, 0, 0.0, name[len(SPAN_PREFIX):]))
    marks.sort()
    out, stack, prev = [], [], None
    for t, is_start, _, name in marks:
        if stack and prev is not None and t > prev:
            out.append((prev, t, stack[-1]))
        if is_start:
            stack.append(name)
        elif name in stack:
            stack.reverse()
            stack.remove(name)
            stack.reverse()
        prev = t
    return out


def _key_scopes(keys, names) -> List[List[str]]:
    """The scopes of each ``[hlo_module, hlo_op]`` key.  An op the compiler
    left without scope metadata (a fusion it built, a copy it inserted)
    takes the scope that every named op of its module lies in, where
    there is one: the whole program is that scope's code."""
    paths = [op_scopes(names.get(mod, {}).get(op, "")) for mod, op in keys]
    common: Dict[str, set] = {}
    for (mod, _), path in zip(keys, paths):
        if path:
            common[mod] = common.get(mod, set(path)) & set(path)
    for i, ((mod, _), path) in enumerate(zip(keys, paths)):
        if not path and common.get(mod):
            paths[i] = [s for s in SCOPES if s in common[mod]]
    return paths


def _busy_before(ms: np.ndarray, me: np.ndarray, t: np.ndarray):
    """Busy ns in ``(-inf, t)`` of the merged intervals ``[ms, me)``."""
    done = np.concatenate(([0.0], np.cumsum(me - ms)))
    j = np.searchsorted(ms, t, side="right")
    k = np.maximum(j - 1, 0)
    part = np.clip(t - ms[k], 0.0, (me - ms)[k])
    return done[k] + np.where(j > 0, part, 0.0)


def reduce(trace: Dict) -> Optional[Dict]:
    """Scope seconds, launches, innermost idle and transfers inside the
    window (module docstring), or None when the trace holds no window or
    no device op inside it."""
    if not trace or trace.get("window") is None:
        return None
    w0, w1 = trace["window"]
    names = trace.get("op_names", {})
    spans = [sp for sp in trace["spans"] if sp[2] > w0 and sp[1] < w1]
    seg = _innermost_segments(spans)
    seg_s = np.clip(np.asarray([x[0] for x in seg], float), w0, w1)
    seg_e = np.clip(np.asarray([x[1] for x in seg], float), w0, w1)
    chips = []
    for dev in trace["devices"]:
        ops = dev["ops"]
        s = np.asarray(ops["start_ns"], float)
        e = np.asarray(ops["end_ns"], float)
        key = np.asarray(ops["key"], np.int64)
        keep = (e > w0) & (s < w1)
        if not keep.any():
            continue
        s, e, key = np.clip(s[keep], w0, w1), np.clip(e[keep], w0, w1), \
            key[keep]
        paths = _key_scopes(dev["keys"], names)
        ms, me = _merge(s, e)
        busy = float(np.sum(me - ms))
        scope_s = {}
        for scope in SCOPES:
            mask = np.asarray([scope in p for p in paths], bool)[key]
            if mask.any():
                scope_s[scope] = _length(s[mask], e[mask]) * 1e-9
        scoped = np.asarray([bool(p) for p in paths], bool)[key]
        unscoped = busy - (_length(s[scoped], e[scoped]) if scoped.any()
                           else 0.0)
        # idle: the window outside the union of ops; each innermost span
        # segment is charged its length less the busy time inside it
        idle: Dict[str, float] = {}
        seg_idle = (seg_e - seg_s) - (_busy_before(ms, me, seg_e)
                                      - _busy_before(ms, me, seg_s))
        for (_, _, who), v in zip(seg, seg_idle):
            idle[who] = idle.get(who, 0.0) + float(v)
        other = (w1 - w0) - busy - float(np.sum(seg_idle))
        idle["other"] = idle.get("other", 0.0) + max(other, 0.0)
        chips.append({"busy": busy, "scope": scope_s, "unscoped": unscoped,
                      "idle": idle,
                      "launch": _launches(spans, dev["modules"], s)})
    if not chips:
        return None
    n = len(chips)
    scope_s, idle = {}, {}
    for c in chips:
        for k, v in c["scope"].items():
            scope_s[k] = scope_s.get(k, 0.0) + v / n
        for k, v in c["idle"].items():
            idle[k] = idle.get(k, 0.0) + v * 1e-9 / n
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(c["busy"] for c in chips) * 1e-9 / n,
        "scope_s": scope_s,
        "unscoped_s": sum(c["unscoped"] for c in chips) * 1e-9 / n,
        "launch_s": [x * 1e-9 for c in chips for x in c["launch"]],
        "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "transfers": sum(n for t, n in trace.get("transfers", ())
                         if w0 <= t < w1),
    }


def _launches(spans, modules, op_start) -> List[float]:
    """Per ``flaas/chunk_execute`` span in the window: its start to the
    first op of the chunk program's run of that round (ns): the
    ``jit_flaas_chunk`` module starting nearest the span, between the
    previous round's ``host_sync/device_wait`` end and this one's.  Host
    and device clocks of a trace can disagree by up to about a millisecond,
    so a launch can read below zero."""
    runs = sorted((m for m in modules if m[0].startswith(CHUNK_MODULE)),
                  key=lambda m: m[1])
    m_start = np.asarray([m[1] for m in runs], float)
    op_start = np.sort(op_start)
    waits = sorted(sp[2] for sp in spans
                   if sp[0] == SPAN_PREFIX + LAUNCH_UNTIL)
    out = []
    for name, s, _, _ in spans:
        if name != SPAN_PREFIX + LAUNCH_SPAN or not len(runs):
            continue
        j = int(np.searchsorted(waits, s))
        lo = waits[j - 1] if j > 0 else -np.inf
        hi = waits[j] if j < len(waits) else np.inf
        near = np.flatnonzero((m_start > lo) & (m_start < hi))
        if not len(near):
            continue
        i = near[np.argmin(np.abs(m_start[near] - s))]
        k = int(np.searchsorted(op_start, m_start[i]))
        if k < len(op_start) and op_start[k] < runs[i][2]:
            out.append(float(op_start[k] - s))
    return out


# --------------------------------------------------- per-layer readers
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".perfbench", "trace")


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, mtime: float) -> Optional[Dict]:
    return reduce(load(path))


def reduce_file(path: str) -> Optional[Dict]:
    """:func:`reduce` of the trace at ``path``, parsed once per file."""
    return _reduce_file(path, os.path.getmtime(path))


def for_run(ctx: Dict) -> Optional[Dict]:
    """:func:`reduce` of the traced run that ``ctx`` (a per-layer reader's
    argument) describes: the newest trace under ``.perfbench/trace/``,
    whose window must be the run's; None without one."""
    tr = ctx.get("trace")
    if tr is None or not ctx.get("rounds"):
        return None
    paths = glob.glob(os.path.join(TRACE_ROOT, "*", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    if not paths:
        return None
    red = reduce_file(max(paths, key=os.path.getmtime))
    if red is None or abs(red["window_s"] - tr["window_s"]) > 1e-6:
        return None
    return red


def scope_ms_per_round(ctx: Dict, scope: str) -> Optional[float]:
    red = for_run(ctx)
    if red is None or red["scope_s"].get(scope, 0.0) <= 0.0:
        return None
    return red["scope_s"][scope] / ctx["rounds"] * 1e3


def span_ms_per_round(ctx: Dict, span: str) -> Optional[float]:
    """A program span's seconds in the window, in ms per round."""
    p = ctx.get("phases") or {}
    if span not in p or not ctx.get("rounds"):
        return None
    return p[span] / ctx["rounds"] * 1e3
