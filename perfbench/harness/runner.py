"""One run of one cell: find the chip, set up, measure, check, print.

The last line of standard output is one JSON object: ``correct``,
``attempted`` and ``failed`` (rounds), ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``,
``breakdown`` (traced runs) and, last, ``checks``: every number of the
output check beside its limit.  The same numbers end standard error.
"""
from __future__ import annotations

import argparse
import importlib
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Optional

import numpy as np

from . import check, spec as specmod

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts XLA compilations (or cache loads) while started."""

    def __init__(self):
        import jax
        self.count, self._on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self._on and event == BACKEND_COMPILE:
            self.count += 1

    def start(self):
        self._on = True

    def stop(self):
        self._on = False


def enable_cache() -> None:
    """JAX's persistent compilation cache, at the program's fixed path
    (``.jax_cache`` in the checkout unless JAX_COMPILATION_CACHE_DIR is
    set), holding every program so that only a cell's first run compiles."""
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def find_chips(n: int):
    """The accelerator, or SystemExit: a run without a TPU, or with fewer
    chips than the cell asks for, prints no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU found (JAX platform {devs[0].platform!r})"
                         f"; this benchmark measures the chip only")
    if len(devs) < n:
        raise SystemExit(f"the cell needs {n} chips, JAX sees {len(devs)}")
    specmod.peaks(devs[0].device_kind)          # unknown kind: an error
    return devs


ENTRY = ("run", "numbers", "control_raw", "REFERENCE")


def entry_module(name: str):
    """The module ``harness/<name>.py`` that drives a mix's entry and owns
    its output check: ``run(cell, seed, seconds, traced, t_start,
    trace_dir, ...)``, the run's raw readings; ``numbers(cell, raw,
    dtype=None)``, the numbers compared, each named in the cell's limits;
    ``control_raw(cell, seed, n, dtype)``, the plain reference at
    ``dtype`` deciding on its own, shaped as a run's raw readings; and
    ``REFERENCE``, the name of its reference module.  An entry that lacks
    one is an error."""
    try:
        mod = importlib.import_module(f"{__package__}.{name}")
    except ModuleNotFoundError:
        raise SystemExit(f"unknown entry {name!r}") from None
    missing = [k for k in ENTRY if not hasattr(mod, k)]
    if missing:
        raise SystemExit(f"entry {name!r} lacks {', '.join(missing)}")
    return mod


def measure(cell: dict, seed: int, seconds: float, traced: bool,
            t_start: float, trace_dir: Optional[str] = None,
            service_cls=None) -> dict:
    """Run the cell's entry and check its answers; the result object."""
    import jax
    entry = entry_module(cell["traffic"]["entry"])
    counter = CompileCounter()
    kw = {} if service_cls is None else {"service_cls": service_cls}
    raw = entry.run(cell, seed, seconds, traced, t_start, trace_dir,
                    compiles=counter, **kw)
    print(f"compiles_in_window: {counter.count}", flush=True)
    if len(raw["round_s"]):
        slow = np.flatnonzero(raw["round_s"] > 0.1)
        print(f"round_ms_max: {float(np.max(raw['round_s'])) * 1e3}; "
              f"rounds over 100 ms: {slow.tolist()[:20]}", flush=True)
    gcs = raw.get("full_gc_s", [])
    print(f"full_gc_in_window: {len(gcs)} passes, {sum(gcs)} s", flush=True)

    ctx = {"rounds": raw["rounds"], "window_s": raw["wall_s"],
           "round_s": raw["round_s"], "phases": raw["phases"], "trace": None}
    breakdown = None
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": int(cell["workload"]["chips"]),
              "memory_peak_bytes": raw["memory_peak_bytes"]}
    if traced:
        from . import trace as tr
        red = tr.reduce(tr.load_events(trace_dir))
        ctx["trace"] = red
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}

    metrics = {}
    if traced:
        for m in cell["per_layer"]:
            v = specmod.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"setup_s": raw["setup_s"],
               "rounds_per_s": raw["rounds"] / raw["wall_s"]
               if raw["wall_s"] > 0 else 0.0,
               "round_ms_p95": float(np.percentile(raw["round_s"], 95)) * 1e3
               if len(raw["round_s"]) else float("nan")}
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    t_check = time.perf_counter()
    ok, checks = check.verdict(entry.numbers(cell, raw), cell["limits"])
    print(f"check_s: {time.perf_counter() - t_check}", flush=True)
    ok = ok and raw["failed"] == 0
    if raw["error"]:
        print(f"round failed: {raw['error']}", file=sys.stderr)
    out = {"correct": bool(ok), "attempted": int(raw["rounds"]),
           "failed": int(raw["failed"]), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    check.print_checks(checks)
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = specmod.cell(args.workload, specmod.benchmark())
    entry_module(cell["traffic"]["entry"])
    find_chips(int(cell["workload"]["chips"]))
    enable_cache()
    trace_dir = None
    if args.trace:
        trace_dir = str(specmod.ROOT / ".perfbench" / "trace" / args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        Path(trace_dir).mkdir(parents=True)
    out = measure(cell, args.seed, args.seconds, bool(args.trace), t_start,
                  trace_dir)
    print(json.dumps(out), flush=True)
    return 0
