#!/usr/bin/env python3
"""Bring-up check: the FLaaS service and its Pallas budget kernels on a TPU.

    python chip_smoke.py                # one chip: phases (a), (b), (c)
    python chip_smoke.py --four-chips   # four chips: sharded service only

(a) ``FlaasService`` at the paper's §VI deployment (100 devices, 6 analyst
    rows x 25 pipeline slots, a 2000-block ring, poisson arrivals, seed 0)
    for all four schedulers: 32 ticks in chunks of 8, ε conservation
    checked at every chunk boundary, and replay parity against the engine
    oracle (``replay_gap`` over the paper's 10-round episode, <= 1e-5,
    which also checks the engine's ε conservation).
(b) One dpbalance ``schedule_round`` at fleet size — N = 1000 pipelines x
    B = 100k blocks (400 MB of demand on the device), budget-scarce, with
    the certified swap beam on — checked for ε conservation.
(c) One paper-size dpbalance round with the Pallas kernels compiled
    (``use_pallas``, warm-start SP1, swap beam), checked to hold all five
    budget kernels and to agree with the same round on the jnp path.

``--four-chips`` runs only ``ShardedFlaasService`` striped over a 4-device
mesh at the paper geometry against ``FlaasService`` on one chip.

Everything runs in this one process.  Any failed phase, or a JAX that finds
no TPU, exits non-zero; on success the last line of stdout is one JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import base64
import dataclasses
import json
import re
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

KERNELS = ("rowmax", "matvec", "dual_step", "boost_scan", "swap_eval")
TOL = 1e-5                       # the parity bound the tests pin
PAPER = dict(analyst_slots=6, pipeline_slots=25, block_slots=2000)
BEAM = 8


def log(msg: str) -> None:
    print(msg, flush=True)


def paper_service(name: str, factory=None, **kw):
    from repro.core import SchedulerConfig
    from repro.service import FlaasService, ServiceConfig, make_trace
    trace = make_trace("paper_default", "poisson", seed=0, n_devices=100)
    cfg = ServiceConfig(scheduler=name, sched=SchedulerConfig(beta=2.2),
                        chunk_ticks=8, admit_batch=16, max_pending=256,
                        **PAPER)
    return (factory or FlaasService)(cfg, trace, **kw)


def phase_service() -> None:
    from repro.core import SCHEDULER_NAMES, SchedulerConfig
    from repro.service import replay_gap
    for name in SCHEDULER_NAMES:
        svc = paper_service(name)       # validate=True: conservation/chunk
        t0 = time.perf_counter()
        summary = svc.run(32)
        wall = time.perf_counter() - t0
        prof = svc.profiler.summary()
        # the engine oracle (run_episode checks ε conservation itself),
        # over the paper's 10-round episode in one chunk: every analyst is
        # then admitted at tick 0's boundary into rows in arrival order,
        # the engine's analyst order.  A later boundary can place an
        # analyst in a recycled row, and fcfs breaks arrival ties by row.
        gaps = replay_gap(svc.trace.reset(), 10, SchedulerConfig(beta=2.2),
                          name, chunk_ticks=10)
        worst = max(gaps.values())
        log(f"  (a) {name}: 32 ticks in {wall:.2f}s "
            f"(compile+execute {prof['chunk_compile_execute']['seconds']:.2f}s"
            f", host_sync {prof['host_sync']['seconds']:.3f}s), "
            f"admitted={summary['admission']['admitted']} "
            f"grants={summary['grants']} "
            f"allocated={summary['total_allocated']}, "
            f"replay gap {worst:.3e}")
        if worst > TOL:
            raise AssertionError(f"{name}: replay gap {worst:.3e} > {TOL}")
        if not summary["grants"]:
            raise AssertionError(f"{name}: no grants in 32 ticks")


def check_round(rnd, res, label: str) -> None:
    """ε conservation and grant shape of one scheduled round."""
    import numpy as np
    cap = np.asarray(rnd.capacity, np.float64)
    consumed = np.asarray(res.consumed, np.float64)
    leftover = np.asarray(res.leftover, np.float64)
    x = np.asarray(res.x_pipeline)
    sel = np.asarray(res.selected)
    over = float(np.max(consumed - cap))
    gap = float(np.max(np.abs(cap - consumed - leftover)))
    if not np.isfinite(x).all() or over > 1e-4 or gap > 1e-4:
        raise AssertionError(f"{label}: overdraw {over:.3e}, "
                             f"conservation gap {gap:.3e}")
    if (x[~sel] != 0).any() or (x[sel] < 1.0).any():
        raise AssertionError(f"{label}: grant ratios off the selection")


def phase_fleet_round() -> None:
    import jax
    from benchmarks.bench_scheduler_scale import _round
    from repro.core import SchedulerConfig, schedule_round
    t0 = time.perf_counter()
    rnd = _round(1, 100_000, 1000, cap=0.25)
    setup = time.perf_counter() - t0
    cfg = SchedulerConfig(beta=2.2, swap_beam=BEAM)
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        res = jax.block_until_ready(schedule_round(rnd, cfg))
        times.append(time.perf_counter() - t0)
    check_round(rnd, res, "fleet round")
    log(f"  (b) N=1000 x B=100k: demand {rnd.demand.nbytes / 1e6:.0f} MB "
        f"(set-up {setup:.1f}s), first call {times[0]:.2f}s (compile "
        f"included), second {times[1]:.3f}s, certificate "
        f"{'held' if bool(res.swap_cert_ok) else 'fell back'}, "
        f"allocated {int(res.n_allocated)}")


def kernels_in(hlo: str):
    """(number of tpu_custom_calls, budget kernels named in their bodies)."""
    count, found = 0, set()
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        count += 1
        m = re.search(r'"body":"([A-Za-z0-9+/=]+)"', line)
        body = base64.b64decode(m.group(1)) if m else b""
        found.update(k for k in KERNELS if f"_{k}_kernel".encode() in body)
    return count, sorted(found)


def phase_pallas() -> None:
    import jax
    import numpy as np
    from benchmarks.bench_scheduler_scale import _round
    from repro.core import SchedulerConfig, schedule_round
    rnd = _round(6, 2000, 25)
    on = SchedulerConfig(beta=2.2, use_pallas=True, sp1_warm_start=True,
                         swap_beam=BEAM)
    off = dataclasses.replace(on, use_pallas=False)
    t0 = time.perf_counter()
    compiled = jax.jit(lambda r: schedule_round(r, on)).lower(rnd).compile()
    t_compile = time.perf_counter() - t0
    count, found = kernels_in(compiled.as_text())
    log(f"  (c) compiled paper-size round in {t_compile:.2f}s: "
        f"{count} tpu_custom_call, kernels {found}")
    if count == 0 or set(found) != set(KERNELS):
        raise AssertionError(
            f"expected all of {KERNELS} compiled, found {found}")
    t0 = time.perf_counter()
    a = jax.block_until_ready(compiled(rnd))
    t_pallas = time.perf_counter() - t0
    b = jax.block_until_ready(schedule_round(rnd, off))
    check_round(rnd, a, "pallas round")
    same_sel = np.array_equal(np.asarray(a.selected), np.asarray(b.selected))
    gap = float(np.max(np.abs(np.asarray(a.x_pipeline, np.float64) -
                              np.asarray(b.x_pipeline, np.float64))))
    log(f"  (c) pallas round {t_pallas:.3f}s; vs jnp: selections "
        f"{'equal' if same_sel else 'DIFFER'}, max |dx| {gap:.3e}, "
        f"SP1 iterations {int(a.sp1_iters)} vs {int(b.sp1_iters)}, "
        f"certificate {bool(a.swap_cert_ok)} vs {bool(b.swap_cert_ok)}")
    if not same_sel or gap > TOL:
        raise AssertionError("pallas round disagrees with the jnp round")


def phase_four_chips() -> None:
    import jax
    import numpy as np
    from repro.core import SCHEDULER_NAMES
    from repro.service import collect_service_metrics
    from repro.service.replay import PARITY_KEYS
    from repro.shard import ShardedFlaasService, shard_mesh
    from repro.shard.state import remap_ring
    if len(jax.devices()) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, JAX sees "
                           f"{len(jax.devices())}")
    log("  sharded service: the block axis is sharded, so dual_step, "
        "boost_scan and swap_eval run as jnp (their reductions are "
        "cross-shard collectives); the default config runs no kernel")
    B = PAPER["block_slots"]
    for name in SCHEDULER_NAMES:
        one = paper_service(name)
        four = paper_service(name, ShardedFlaasService, mesh=shard_mesh(4))
        ya = collect_service_metrics(one, 16)
        t0 = time.perf_counter()
        yb = collect_service_metrics(four, 16)
        wall = time.perf_counter() - t0
        gap = max(float(np.max(np.abs(np.asarray(ya[k], np.float64) -
                                      np.asarray(yb[k], np.float64))) /
                        max(1.0, float(np.max(np.abs(ya[k])))))
                  for k in PARITY_KEYS)
        cap_one = np.asarray(one.state.block_capacity, np.float64)
        cap_four = np.asarray(four.state.block_capacity,
                              np.float64)[remap_ring(4, 1, B)]
        cap_gap = float(np.max(np.abs(cap_one - cap_four)))
        shards = [(s.device.id, s.data.nbytes)
                  for s in four.state.demand.addressable_shards]
        log(f"  4-chip {name}: 16 ticks in {wall:.2f}s, metric gap "
            f"{gap:.3e}, capacity gap {cap_gap:.3e}, grants "
            f"{one.summary()['grants']} vs {four.summary()['grants']}, "
            f"demand shard bytes by device {shards}")
        total = one.state.demand.nbytes
        if (len({d for d, _ in shards}) != 4
                or any(n != total // 4 for _, n in shards)):
            raise AssertionError(f"{name}: ledger not striped over 4 "
                                 f"devices: {shards}")
        if gap > TOL or cap_gap > TOL:
            raise AssertionError(f"{name}: sharded service off the "
                                 f"one-chip service by {max(gap, cap_gap)}")
        if one.summary()["grants"] != four.summary()["grants"]:
            raise AssertionError(f"{name}: grant counts differ")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded service on 4 chips against "
                         "the one-chip service")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    log(f"device {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {cache}")

    phases = ([("four_chips", phase_four_chips)] if args.four_chips else
              [("a_service", phase_service),
               ("b_fleet_round", phase_fleet_round),
               ("c_pallas", phase_pallas)])
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        status = "ok"
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            status = "FAILED"
        log(f"[{name}] {status} in {time.perf_counter() - t0:.1f}s")
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
