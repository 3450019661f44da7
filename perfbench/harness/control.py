"""Readings that the limits of a cell's output check are set from.

    python3 perfbench/harness/control.py --workload <name> --seconds <s> \
        --program-seeds 1,2,... --control-seeds 7,8,9 [--out <file>]

In one process, on the chip: for each program seed, a run of the cell
(short window, the cell's own load) and the numbers its answers read; for
each control seed, the control in the program's place -- the plain
reference computed in bfloat16, the precision below the float32 that the
configuration states, deciding on its own over as many ticks (or calls)
as a program run made -- and the numbers it reads against the float32
reference.  The cell's entry (``harness/<entry>.py``) owns both: its
``numbers`` and its ``control_raw``.  Each reading is one JSON line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    import jax.numpy as jnp
    from perfbench.harness import runner, spec as specmod
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = specmod.cell(args.workload, specmod.benchmark())
    entry = runner.entry_module(cell["traffic"]["entry"])
    runner.find_chips(int(cell["workload"]["chips"]))
    runner.enable_cache()
    out = open(args.out, "a") if args.out else None
    n = 0

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for s in filter(None, args.program_seeds.split(",")):
        t0 = time.perf_counter()
        raw = entry.run(cell, int(s), args.seconds, False, t0)
        t1 = time.perf_counter()
        nums = entry.numbers(cell, raw)
        n = max(n, raw.get("ticks") or raw.get("calls") or 0)
        emit({"kind": "program", "workload": args.workload, "seed": int(s),
              "ticks": raw.get("ticks"), "calls": raw.get("calls"),
              "rounds": raw["rounds"],
              "failed": raw["failed"],
              "error": raw["error"], "run_s": t1 - t0,
              "reference_s": time.perf_counter() - t1, **nums})
    for s in filter(None, args.control_seeds.split(",")):
        t0 = time.perf_counter()
        raw = entry.control_raw(cell, int(s), n or 200, jnp.bfloat16)
        nums = entry.numbers(cell, raw)
        emit({"kind": "control", "dtype": "bfloat16",
              "workload": args.workload, "seed": int(s),
              "run_s": time.perf_counter() - t0, **nums})
    return 0


if __name__ == "__main__":
    sys.exit(main())
