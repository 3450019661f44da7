"""Readings that the limits of a cell's output check are set from.

    python3 perfbench/harness/control.py --workload <name> --seconds <s> \
        --program-seeds 1,2,... --control-seeds 7,8,9 [--out <file>]

In one process, on the chip: for each program seed, a run of the cell
(short window, the cell's own load) and the numbers its answers read; for
each control seed, the control in the program's place -- the plain
reference computed in bfloat16, the precision below the float32 that the
configuration states, deciding on its own over as many ticks as a program
run made -- and the numbers it reads against the float32 reference.  Each
reading is one JSON line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def control_answers(cell: dict, seed: int, n_ticks: int, dtype) -> dict:
    """The answers of the reference at ``dtype``, deciding on its own."""
    import numpy as np
    from perfbench.harness.generator import Arrivals
    from perfbench.harness.reference import ReferenceService
    d = cell["config"]["deployment"]
    arrivals = Arrivals(d, seed)
    arrivals.ticks(n_ticks)
    ref = ReferenceService(d, cell["config"]["scheduler"],
                           cell["traffic"]["scheduler"], arrivals,
                           dtype=dtype)
    sel, spend, exp, cap = [], [], [], []
    for _ in range(n_ticks):
        own, expired, sp, _, c = ref.step()
        sel.append(own)
        spend.append(sp)
        exp.append(expired)
        cap.append(c)
    return {"selected": np.stack(sel), "spend": np.stack(spend),
            "expired": np.stack(exp), "capacity": np.stack(cap),
            "owner": ref.owner.copy()}, arrivals


def control_raw(cell: dict, seed: int, n_ticks: int, dtype) -> dict:
    """Readings of the control in the program's place, shaped as a run's."""
    ans, arrivals = control_answers(cell, seed, n_ticks, dtype)
    return {"answers": ans, "arrivals": arrivals}


def main(argv=None) -> int:
    import jax.numpy as jnp
    from perfbench.harness import check, runner, spec as specmod
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = specmod.cell(args.workload, specmod.benchmark())
    runner.find_chips(int(cell["workload"]["chips"]))
    runner.enable_cache()
    out = open(args.out, "a") if args.out else None
    n_ticks = 0

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for s in filter(None, args.program_seeds.split(",")):
        t0 = time.perf_counter()
        entry = runner.entry_module(cell["traffic"]["entry"])
        raw = entry.run(cell, int(s), args.seconds, False, t0)
        t1 = time.perf_counter()
        nums = check.numbers(cell, raw)
        n_ticks = max(n_ticks, raw.get("ticks", 0))
        emit({"kind": "program", "workload": args.workload, "seed": int(s),
              "ticks": raw.get("ticks"), "rounds": raw["rounds"],
              "failed": raw["failed"],
              "error": raw["error"], "run_s": t1 - t0,
              "reference_s": time.perf_counter() - t1, **nums})
    for s in filter(None, args.control_seeds.split(",")):
        t0 = time.perf_counter()
        raw = control_raw(cell, int(s), n_ticks or 200, jnp.bfloat16)
        nums = check.numbers(cell, raw)
        emit({"kind": "control", "dtype": "bfloat16",
              "workload": args.workload, "seed": int(s),
              "run_s": time.perf_counter() - t0, **nums})
    return 0


if __name__ == "__main__":
    sys.exit(main())
