"""Wall-clock span profiler for the service tick loop.

The chunk loop has a handful of host phases worth separating: admission
drain, mint/page planning, device execution (first execution per compiled
shape = compile+execute, flagged separately), the state graft, host sync
(device->numpy), recycling, telemetry fold, checkpoint save.
:class:`PhaseProfiler` accumulates ``perf_counter`` wall time and call
counts per span, always on.

* **Spans nest.**  A :meth:`~PhaseProfiler.phase` opened inside another is
  named ``<parent>/<child>``; its parent's own totals still cover the
  child, so a parent reads the same with or without children.  Each span
  records its name, its parent, the round's ``tick`` and its self time
  (duration minus the time its children cover) in
  :meth:`~PhaseProfiler.round_spans`.
* **Counters** at the same boundaries: host->device and device->host
  transfers with their bytes (counted by :meth:`~PhaseProfiler.to_device`
  / :meth:`~PhaseProfiler.to_host`, which wrap ``jnp.asarray`` /
  ``np.asarray`` and change nothing else, and by
  :meth:`~PhaseProfiler.sent` for operands a callee moves itself),
  submissions admitted, chunk outputs that rode one packed
  device->host copy (:meth:`~PhaseProfiler.packed`), and XLA
  compilations, each charged to the innermost span open in the compiling
  thread (one process-wide ``jax.monitoring`` listener).
* **Per-round ring**: :meth:`~PhaseProfiler.round` opens one round; on
  close it writes one row of a preallocated structured array of the last
  ``RING_ROUNDS`` rounds (tick, wall seconds, each ring span's self
  seconds, the counters), read through :meth:`~PhaseProfiler.rounds`.

With ``annotate`` each span is also a ``jax.profiler.TraceAnnotation``
``flaas/<name>`` (with ``tick=``), so the spans land on the XLA profiler
timeline beside the device ops; a span opened with ``counters=True`` (the
round's last) also carries the round's transfer counts so far (``h2d=``,
``d2h=``).

State rides the checkpoint host payload (wall totals and counters resume
across restores), and :meth:`publish` mirrors the totals into the metrics
registry as ``flaas_phase_seconds_total`` / ``flaas_phase_calls_total``,
``flaas_transfers_total`` / ``flaas_transfer_bytes_total{direction}``,
``flaas_packed_outputs_total`` and ``flaas_compiles_total{span}``.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
RING_ROUNDS = 4096
# per-round counters, in ring column order
COUNTERS = ("h2d", "h2d_bytes", "d2h", "d2h_bytes", "admitted", "compiles",
            "packed_outputs")

# The compile listener is process-wide (jax.monitoring has no per-object
# registration); it finds the innermost open span of the compiling thread.
_open = threading.local()
_register_lock = threading.Lock()
_registered = False
_COUNTER = {name: i for i, name in enumerate(COUNTERS)}


def _on_event(event: str, duration: float, **_) -> None:
    if event != BACKEND_COMPILE:
        return
    span = getattr(_open, "span", None)
    if span is not None:
        span.compiled()


def _listen() -> None:
    global _registered
    with _register_lock:
        if not _registered:
            jax.monitoring.register_event_duration_secs_listener(_on_event)
            _registered = True


def _device_nbytes(a: np.ndarray) -> int:
    """Bytes of ``a`` on the device: 64-bit host types arrive as JAX's
    canonical (32-bit unless x64 is on) type."""
    if a.dtype.itemsize < 8:
        return a.nbytes
    return a.size * jax.dtypes.canonicalize_dtype(a.dtype).itemsize


class SpanRecord(NamedTuple):
    name: str                  # "<parent>/<child>" when nested
    parent: Optional[str]
    tick: Optional[int]        # the round's tick (None outside a round)
    seconds: float
    self_seconds: float        # seconds minus the children's seconds


class _Span:
    __slots__ = ("prof", "name", "counters", "up", "outer", "t0", "child_s",
                 "ann")

    def __init__(self, prof: "PhaseProfiler", name: str, counters: bool):
        self.prof = prof
        self.name = name
        self.counters = counters

    def __enter__(self):
        prof = self.prof
        self.up = up = prof._top               # enclosing span, or None
        if up is not None:
            self.name = f"{up.name}/{self.name}"
        prof._top = self
        self.outer = getattr(_open, "span", None)
        _open.span = self
        self.child_s = 0.0
        if prof.annotate:
            args = {} if prof._tick is None else {"tick": prof._tick}
            if self.counters:
                c = prof._round_counts
                args["h2d"] = c[_COUNTER["h2d"]]
                args["d2h"] = c[_COUNTER["d2h"]]
            self.ann = jax.profiler.TraceAnnotation(f"flaas/{self.name}",
                                                    **args)
            self.ann.__enter__()
        else:
            self.ann = None
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        prof, up = self.prof, self.up
        prof._top = up
        _open.span = self.outer
        if up is not None:
            up.child_s += dt
        prof._close(self.name, up, dt, dt - self.child_s)
        return False

    def compiled(self) -> None:
        prof = self.prof
        prof.compiles[self.name] = prof.compiles.get(self.name, 0) + 1
        prof._round_counts[_COUNTER["compiles"]] += 1


class _Round:
    __slots__ = ("prof", "tick", "t0", "seconds")

    def __init__(self, prof: "PhaseProfiler", tick: Optional[int]):
        self.prof = prof
        self.tick = tick
        self.seconds = 0.0

    def __enter__(self):
        self.prof._begin_round(self.tick)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        if exc[0] is None:
            self.prof._end_round(self.seconds)
        else:
            self.prof._tick = None
        return False


class PhaseProfiler:
    def __init__(self, annotate: bool = False,
                 ring_spans: Sequence[str] = ()):
        self.annotate = bool(annotate)
        self.seconds: Dict[str, float] = {}
        self.calls: Dict[str, int] = {}
        # direction -> [count, bytes]
        self.transfers: Dict[str, List[int]] = {"h2d": [0, 0],
                                                "d2h": [0, 0]}
        self.compiles: Dict[str, int] = {}
        self.packed_outputs = 0
        self._top: Optional[_Span] = None      # innermost open span
        self._tick: Optional[int] = None
        self._spans: List[tuple] = []
        self._ring_spans = tuple(ring_spans)
        self._col = {name: i for i, name in enumerate(self._ring_spans)}
        self._round_s = [0.0] * len(self._ring_spans)
        self._round_counts = [0] * len(COUNTERS)
        self._ring = np.zeros(RING_ROUNDS, dtype=np.dtype(
            [("tick", np.int64), ("wall_s", np.float64)]
            + [(name, np.float64) for name in self._ring_spans]
            + [(name, np.int64) for name in COUNTERS]))
        self._n_rounds = 0
        _listen()

    # --------------------------------------------------------------- spans
    def phase(self, name: str, counters: bool = False) -> _Span:
        """Context manager timing one span (see the module docstring).
        With ``counters`` its annotation carries the round's transfer
        counts so far."""
        return _Span(self, name, counters)

    def round(self, tick: Optional[int]) -> _Round:
        """Context manager around one round: its spans carry ``tick``, and
        its close writes one ring row.  ``.seconds`` is the round's wall
        time once closed (a round that raises writes no row)."""
        return _Round(self, tick)

    def observe(self, name: str, seconds: float) -> None:
        self.seconds[name] = self.seconds.get(name, 0.0) + float(seconds)
        self.calls[name] = self.calls.get(name, 0) + 1

    def _close(self, name: str, up: Optional[_Span], seconds: float,
               self_s: float) -> None:
        self.observe(name, seconds)
        col = self._col.get(name)
        if col is not None:
            self._round_s[col] += self_s
        self._spans.append((name, None if up is None else up.name,
                            self._tick, seconds, self_s))

    def _begin_round(self, tick: Optional[int]) -> None:
        self._tick = None if tick is None else int(tick)
        self._spans = []
        self._round_s = [0.0] * len(self._ring_spans)
        self._round_counts = [0] * len(COUNTERS)

    def _end_round(self, seconds: float) -> None:
        row = ((-1 if self._tick is None else self._tick, seconds)
               + tuple(self._round_s) + tuple(self._round_counts))
        self._ring[self._n_rounds % len(self._ring)] = row
        self._n_rounds += 1
        self._tick = None

    def round_spans(self) -> List[SpanRecord]:
        """The spans of the latest round (or of the open one), in close
        order: children before their parents."""
        return [SpanRecord(*rec) for rec in self._spans]

    def rounds(self) -> np.ndarray:
        """The ring's rounds, oldest first (a copy; at most
        ``RING_ROUNDS`` rows)."""
        n, cap = self._n_rounds, len(self._ring)
        if n <= cap:
            return self._ring[:n].copy()
        i = n % cap
        return np.concatenate((self._ring[i:], self._ring[:i]))

    def leaves(self) -> List[str]:
        """The ring spans with no child among the ring spans."""
        spans = self._ring_spans
        return [s for s in spans
                if not any(o.startswith(s + "/") for o in spans)]

    def slow_rounds(self, over_s: float = 0.1):
        """``(tick, ms, leaf)`` of each ring round slower than ``over_s``:
        the leaf span that took most of it."""
        rows = self.rounds()
        leaves = self.leaves()
        out = []
        for r in rows[rows["wall_s"] > over_s]:
            worst = max(leaves, key=lambda s: r[s]) if leaves else None
            out.append((int(r["tick"]), float(r["wall_s"]) * 1e3, worst))
        return out

    # ------------------------------------------------------------ counters
    def count(self, counter: str, n: int = 1) -> None:
        """Add ``n`` to a per-round counter (``admitted``)."""
        self._round_counts[_COUNTER[counter]] += int(n)

    def _transfer(self, direction: str, nbytes: int) -> None:
        tot = self.transfers[direction]
        tot[0] += 1
        tot[1] += nbytes
        i = _COUNTER[direction]
        self._round_counts[i] += 1
        self._round_counts[i + 1] += nbytes

    def packed(self, n: int) -> None:
        """Count ``n`` chunk outputs that rode one packed device->host
        copy (the copy itself is counted by :meth:`to_host`)."""
        self.packed_outputs += int(n)
        self.count("packed_outputs", n)

    def sent(self, arrays: Sequence[np.ndarray]) -> None:
        """Count each host array as one host->device transfer of the bytes
        the device receives, for a callee that moves them itself."""
        for a in arrays:
            self._transfer("h2d", _device_nbytes(a))

    def to_device(self, x, dtype=None):
        """``jnp.asarray(x, dtype)`` of a host value, counted as one
        host->device transfer of the bytes the device receives."""
        a = np.asarray(x, dtype)
        self._transfer("h2d", _device_nbytes(a))
        return jnp.asarray(a)

    def to_host(self, x) -> np.ndarray:
        """``np.asarray(x)`` of a device array, counted as one
        device->host transfer."""
        a = np.asarray(x)
        self._transfer("d2h", a.nbytes)
        return a

    # ------------------------------------------------------------ reporting
    def summary(self) -> Dict[str, Dict[str, float]]:
        out = {}
        for name in sorted(self.seconds):
            n = self.calls[name]
            s = self.seconds[name]
            out[name] = {"calls": n, "seconds": s,
                         "mean_us": (s / n) * 1e6 if n else 0.0}
        return out

    def publish(self, registry) -> None:
        sec = registry.counter("flaas_phase_seconds_total",
                               "Host wall seconds per tick-loop phase",
                               ("phase",))
        cnt = registry.counter("flaas_phase_calls_total",
                               "Calls per tick-loop phase", ("phase",))
        for name in self.seconds:
            sec.set_total(self.seconds[name], (name,))
            cnt.set_total(self.calls[name], (name,))
        xn = registry.counter("flaas_transfers_total",
                              "Host<->device transfers", ("direction",))
        xb = registry.counter("flaas_transfer_bytes_total",
                              "Host<->device transfer bytes",
                              ("direction",))
        for direction, (n, nbytes) in self.transfers.items():
            xn.set_total(n, (direction,))
            xb.set_total(nbytes, (direction,))
        registry.counter(
            "flaas_packed_outputs_total",
            "Chunk outputs brought to the host in one packed copy"
        ).set_total(self.packed_outputs)
        comp = registry.counter("flaas_compiles_total",
                                "XLA compilations by innermost open span",
                                ("span",))
        for name, n in self.compiles.items():
            comp.set_total(n, (name,))

    # ---------------------------------------------------------- durability
    def state_dict(self) -> dict:
        return {"seconds": dict(self.seconds), "calls": dict(self.calls),
                "transfers": {k: list(v) for k, v in self.transfers.items()},
                "compiles": dict(self.compiles),
                "packed_outputs": self.packed_outputs}

    def load_state_dict(self, d: dict) -> None:
        self.seconds = {k: float(v) for k, v in d.get("seconds", {}).items()}
        self.calls = {k: int(v) for k, v in d.get("calls", {}).items()}
        self.transfers = {"h2d": [0, 0], "d2h": [0, 0]}
        for k, (n, nbytes) in d.get("transfers", {}).items():
            self.transfers[k] = [int(n), int(nbytes)]
        self.compiles = {k: int(v) for k, v in d.get("compiles", {}).items()}
        self.packed_outputs = int(d.get("packed_outputs", 0))
