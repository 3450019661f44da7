#!/usr/bin/env python3
"""One run of one cell, as ``perfbench/run.py`` makes it, followed by what
the program's own records say about the window:

    python3 perfbench/explain.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Before the result line (the same JSON object as ``run.py``'s, last) it
prints, from the service's per-round ring (``PhaseProfiler.rounds``):

* ``rounds_per_s``: the window's rounds over its wall time, traced or not;
* ``slow_rounds``: ``(tick, ms, leaf)`` of each window round over 100 ms,
  with the leaf span that took most of it;
* ``leaf_ms`` / ``leaf_ms_mean``: each leaf span's median and mean ms per
  round (the mean is what a span's per-layer metric reads), and
  ``outside_leaves`` (the round's time no leaf covers);
* ``transfers``: median host->device and device->host transfers a round;
* ``admitted``: submissions admitted a round (mean, and the share of
  rounds that admitted any), beside the ``admit_drain/write`` ms of the
  rounds that did;
* ``compiles``: compilations in the window, and by span over the run;

and with ``--trace 1``, from the trace (``harness/scopes.py``): device
ms per round by scope, the unscoped remainder and its share of busy
time, launch latency (a diagnostic: the ``flaas/chunk_execute`` span's
start on the host clock to the chunk program's first op on the device
clock, which can disagree by tenths of a millisecond), and idle seconds
by innermost span.
"""
import time

T_START = time.perf_counter()

import argparse                                               # noqa: E402
import json                                                   # noqa: E402
import shutil                                                 # noqa: E402
import sys                                                    # noqa: E402
from pathlib import Path                                      # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def window_rows(prof, rounds: int):
    rows = prof.rounds()
    return rows[len(rows) - min(rounds, len(rows)):]


def explain(prof, raw) -> None:
    import numpy as np
    rounds = raw["rounds"]
    rows = window_rows(prof, rounds)
    leaves = prof.leaves()
    wall = raw["wall_s"]
    print(f"rounds_per_s: {rounds / wall if wall > 0 else 0.0}")
    first = int(rows["tick"][0]) if len(rows) else 0
    slow = [x for x in prof.slow_rounds(0.1) if x[0] >= first]
    print(f"slow_rounds: {slow}")
    leaf_ms = {s: float(np.median(rows[s])) * 1e3 for s in leaves
               if np.any(rows[s] > 0)}
    print(f"leaf_ms: {json.dumps(leaf_ms)}")
    mean_ms = {s: float(np.mean(rows[s])) * 1e3 for s in leaf_ms}
    print(f"leaf_ms_mean: {json.dumps(mean_ms)}")
    outside = rows["wall_s"] - sum(rows[s] for s in leaves)
    print(f"outside_leaves: median {float(np.median(outside)) * 1e3} ms, "
          f"max {float(np.max(outside)) * 1e3} ms")
    print(f"transfers: h2d {float(np.median(rows['h2d']))}, "
          f"d2h {float(np.median(rows['d2h']))} a round (median); "
          f"{float(np.mean(rows['h2d'] + rows['d2h']))} mean")
    adm = rows["admitted"]
    wrote = rows["admit_drain/write"][adm > 0]
    print(f"admitted: {float(np.mean(adm))} a round, "
          f"{100.0 * float(np.mean(adm > 0))} % of rounds; write "
          f"{float(np.median(wrote)) * 1e3 if len(wrote) else 0.0} ms "
          f"median in those rounds")
    print(f"compiles: {int(rows['compiles'].sum())} in the window; by span "
          f"over the run {json.dumps(prof.compiles)}")


def explain_trace(trace_dir: str, rounds: int) -> None:
    from perfbench.harness import scopes
    path = scopes.newest_xplane(trace_dir)
    red = scopes.reduce_file(path) if path else None
    if red is None:
        print("scopes: no trace")
        return
    per = {k: v / rounds * 1e3 for k, v in red["scope_s"].items()}
    print(f"scopes_ms_per_round: {json.dumps(per)}")
    busy = red["busy_s"]
    print(f"unscoped: {red['unscoped_s'] / rounds * 1e3} ms/round, "
          f"{100.0 * red['unscoped_s'] / busy if busy else 0.0} % of busy")
    la = sorted(red["launch_s"])
    if la:
        print(f"launch_ms: median {la[len(la) // 2] * 1e3}, "
              f"max {la[-1] * 1e3}, rounds {len(la)}")
    print(f"idle_by_span_s: {json.dumps(red['idle_by_span'])}")


def main() -> int:
    from perfbench.harness import live, runner, spec as specmod
    from repro.service import FlaasService
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    cell = specmod.cell(args.workload, specmod.benchmark())
    runner.find_chips(int(cell["workload"]["chips"]))
    runner.enable_cache()
    kept, raws = [], []

    class Kept(FlaasService):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            kept.append(self.profiler)

    entry = live.run

    def run(*a, **kw):
        raws.append(entry(*a, **kw))
        return raws[-1]

    live.run = run
    trace_dir = None
    if args.trace:
        trace_dir = str(specmod.ROOT / ".perfbench" / "trace" /
                        args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        Path(trace_dir).mkdir(parents=True)
    out = runner.measure(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, trace_dir, service_cls=Kept)
    explain(kept[-1], raws[-1])
    if args.trace:
        explain_trace(trace_dir, raws[-1]["rounds"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
