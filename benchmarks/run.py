"""Benchmark harness — one module per paper table/figure + systems benches.

Prints ``name,us_per_call,derived`` CSV; ``--json PATH`` additionally
writes a structured report whose ``meta`` records the backend and the mode
``run_fleet(mode="auto")`` resolves to on it (the data the ROADMAP's
per-backend fleet-default item needs).  BENCH_SMALL=1 shrinks workloads
(used by CI); the full run reproduces the paper's §VI comparison numbers.

``--smoke`` runs one tiny engine episode per scheduler plus a tiny
streaming-service run instead (seconds, used by CI to keep the perf entry
points importable and runnable).
"""
import argparse
import sys
import time
import traceback


def smoke() -> tuple:
    """One tiny device-resident episode per scheduler + one tiny service
    run; fails loudly if any perf entry point rots."""
    from repro.core import (SCHEDULER_NAMES, SchedulerConfig, SimConfig,
                            generate_episode, run_episode)
    from repro.service import FlaasService, ServiceConfig, make_trace
    from .common import derived, time_fn

    sim = SimConfig(n_devices=4, n_analysts=3, pipelines_per_analyst=6,
                    n_rounds=3)
    ep = generate_episode(sim)
    cfg = SchedulerConfig(beta=2.2)
    failures = 0
    rows = []
    for name in SCHEDULER_NAMES:
        try:
            out = run_episode(ep, cfg, name)   # validates conservation
            us = time_fn(lambda e: run_episode(e, cfg, name), ep, iters=2)
            rows.append((f"smoke/engine_{name}", us, derived(
                n_allocated=int(out["n_allocated"].sum()))))
        except Exception as e:
            traceback.print_exc()
            print(f"smoke/engine_{name},NaN,error={type(e).__name__}",
                  file=sys.stderr)
            failures += 1

    # sp2_swap smoke: incremental vs reference swap engine on a tiny round
    # — parity is asserted, not just reported (the full N sweep lives in
    # bench_scheduler_scale.sp2_swap).
    try:
        import dataclasses

        import numpy as np

        from repro.core import schedule_round

        from .bench_scheduler_scale import _round
        rnd = _round(3, 64, 8)
        cfg_ref = dataclasses.replace(cfg, incremental_swap=False)
        a, b = schedule_round(rnd, cfg), schedule_round(rnd, cfg_ref)
        if not (np.array_equal(np.asarray(a.selected), np.asarray(b.selected))
                and np.array_equal(np.asarray(a.x_pipeline),
                                   np.asarray(b.x_pipeline))):
            raise AssertionError("swap engine parity violated")
        us_i = time_fn(lambda r: schedule_round(r, cfg), rnd, iters=2)
        us_r = time_fn(lambda r: schedule_round(r, cfg_ref), rnd, iters=2)
        rows.append(("smoke/sp2_swap", us_i, derived(
            reference_us=round(us_r, 1), speedup=round(us_r / us_i, 2),
            parity=1)))
    except Exception as e:
        traceback.print_exc()
        print(f"smoke/sp2_swap,NaN,error={type(e).__name__}",
              file=sys.stderr)
        failures += 1

    # sp2_pruned_parity smoke: the certified pruning beam vs the full
    # compacted sweep on a small round — selections and allocations must
    # be BITWISE equal whichever way the certificate goes (that is the
    # all-or-nothing fallback contract); the candidate-reduction factor
    # (compacted cap / beam width) is what the fleet rows cash in.
    try:
        import dataclasses

        import numpy as np

        from repro.core import schedule_round, swap_candidate_cap

        from .bench_scheduler_scale import _round
        rnd = _round(3, 64, 8)
        beam = 4
        cfg_beam = dataclasses.replace(cfg, swap_beam=beam)
        a, b = schedule_round(rnd, cfg_beam), schedule_round(rnd, cfg)
        if not (np.array_equal(np.asarray(a.selected), np.asarray(b.selected))
                and np.array_equal(np.asarray(a.x_pipeline),
                                   np.asarray(b.x_pipeline))):
            raise AssertionError("pruned swap parity violated")
        us_p = time_fn(lambda r: schedule_round(r, cfg_beam), rnd, iters=2)
        us_f = time_fn(lambda r: schedule_round(r, cfg), rnd, iters=2)
        rows.append(("smoke/sp2_pruned_parity", us_p, derived(
            full_us=round(us_f, 1), parity=1,
            cert_ok=int(bool(a.swap_cert_ok)),
            candidate_reduction=round(swap_candidate_cap(
                rnd.demand.shape[1]) / beam, 1))))
    except Exception as e:
        traceback.print_exc()
        print(f"smoke/sp2_pruned_parity,NaN,error={type(e).__name__}",
              file=sys.stderr)
        failures += 1

    # service_throughput smoke: a short streaming run with recycling +
    # ledger-ring wrap on the smallest legal ring.
    try:
        trace = make_trace("paper_default", "poisson", seed=0, n_devices=4,
                           pipelines_per_analyst=6)
        svc_cfg = ServiceConfig(
            scheduler="dpf", sched=cfg, analyst_slots=4, pipeline_slots=6,
            block_slots=10 * trace.blocks_per_tick, chunk_ticks=4,
            admit_batch=8, max_pending=32)
        summary = FlaasService(svc_cfg, trace).run(12)
        rows.append(("smoke/service_dpf",
                     summary["wall_seconds"] * 1e6 / summary["ticks"],
                     derived(ticks_per_s=round(summary["ticks_per_second"], 1),
                             admitted=summary["admission"]["admitted"],
                             allocated=summary["total_allocated"])))
    except Exception as e:
        traceback.print_exc()
        print(f"smoke/service_dpf,NaN,error={type(e).__name__}",
              file=sys.stderr)
        failures += 1

    # steady_state_paged smoke: a ring-wrapping run with the paged
    # two-ring demand layout vs the full-tensor-carry fallback — bitwise
    # parity is ASSERTED (per-tick metrics over >= 2 wraps), speedup
    # reported.
    try:
        import numpy as np

        from repro.service import collect_service_metrics

        trace = make_trace("paper_default", "bursty", seed=0, n_devices=4,
                           pipelines_per_analyst=6).precompute(24)
        def paged_svc(paged):
            return FlaasService(ServiceConfig(
                scheduler="dpf", sched=cfg, analyst_slots=4,
                pipeline_slots=6, block_slots=10 * trace.blocks_per_tick,
                chunk_ticks=4, admit_batch=8, max_pending=32,
                paged=paged), trace.reset())
        t0 = time.perf_counter()
        ya = collect_service_metrics(paged_svc(True), 24)
        us_paged = (time.perf_counter() - t0) * 1e6 / 24
        t0 = time.perf_counter()
        yb = collect_service_metrics(paged_svc(False), 24)
        us_carry = (time.perf_counter() - t0) * 1e6 / 24
        for k in ("round_efficiency", "n_allocated", "leftover"):
            if not np.array_equal(np.asarray(ya[k]), np.asarray(yb[k])):
                raise AssertionError(
                    f"paged/carry parity violated on {k!r}")
        rows.append(("smoke/service_paged", us_paged, derived(
            carry_us=round(us_carry, 1),
            speedup=round(us_carry / us_paged, 2), parity=1)))
    except Exception as e:
        traceback.print_exc()
        print(f"smoke/service_paged,NaN,error={type(e).__name__}",
              file=sys.stderr)
        failures += 1

    # checkpoint_restore smoke: crash-recovery round trip — run k chunks,
    # checkpoint, restore into a fresh in-process service, finish the run;
    # bitwise summary parity vs the uninterrupted run is ASSERTED (the
    # wall-clock-stripped fingerprint), restore latency reported.
    try:
        import json as _json
        import tempfile

        from repro.checkpoint import CheckpointManager
        from repro.service import summary_fingerprint

        trace = make_trace("paper_default", "poisson", seed=0, n_devices=4,
                           pipelines_per_analyst=6)
        def ckpt_svc():
            return FlaasService(ServiceConfig(
                scheduler="dpf", sched=cfg, analyst_slots=4,
                pipeline_slots=6, block_slots=10 * trace.blocks_per_tick,
                chunk_ticks=4, admit_batch=8, max_pending=32),
                trace.reset())
        ref = ckpt_svc()
        ref.run(24)
        crashed = ckpt_svc()
        crashed.run(12)
        with tempfile.TemporaryDirectory() as ckdir:
            mgr = CheckpointManager(ckdir)
            t0 = time.perf_counter()
            crashed.save_checkpoint(mgr)
            mgr.wait()
            resumed = ckpt_svc()
            resumed.load_checkpoint(mgr)
            us_roundtrip = (time.perf_counter() - t0) * 1e6
        resumed.run(12)
        fa = _json.dumps(summary_fingerprint(ref.summary()), sort_keys=True)
        fb = _json.dumps(summary_fingerprint(resumed.summary()),
                         sort_keys=True)
        if fa != fb:
            raise AssertionError("checkpoint/restore resume parity violated")
        rows.append(("smoke/checkpoint_restore", us_roundtrip, derived(
            resumed_ticks=12, parity=1)))
    except Exception as e:
        traceback.print_exc()
        print(f"smoke/checkpoint_restore,NaN,error={type(e).__name__}",
              file=sys.stderr)
        failures += 1

    # tenancy_default_parity smoke: the default single-tier configuration
    # must be bitwise identical to the pre-tenancy service — per-tick
    # metrics AND final device state — for all four schedulers, through a
    # ring wrap.  ASSERTED, not just reported.
    try:
        import dataclasses as _dc

        import numpy as np

        from repro.service import collect_service_metrics

        trace = make_trace("paper_default", "poisson", seed=0, n_devices=4,
                           pipelines_per_analyst=6).precompute(16)
        tiered = make_trace("paper_default", "poisson", seed=0, n_devices=4,
                            pipelines_per_analyst=6,
                            tiers="single").precompute(16)
        t0 = time.perf_counter()
        for name in SCHEDULER_NAMES:
            def tier_svc(tr):
                return FlaasService(ServiceConfig(
                    scheduler=name, sched=cfg, analyst_slots=4,
                    pipeline_slots=6,
                    block_slots=10 * trace.blocks_per_tick, chunk_ticks=4,
                    admit_batch=8, max_pending=32), tr.reset())
            sa, sb = tier_svc(trace), tier_svc(tiered)
            ya = collect_service_metrics(sa, 16)
            yb = collect_service_metrics(sb, 16)
            for k in ya:
                if not np.array_equal(np.asarray(ya[k]), np.asarray(yb[k])):
                    raise AssertionError(
                        f"single-tier parity violated on {name}/{k!r}")
            for f in _dc.fields(sa.state):
                if not np.array_equal(np.asarray(getattr(sa.state, f.name)),
                                      np.asarray(getattr(sb.state, f.name))):
                    raise AssertionError(
                        f"single-tier state parity violated on "
                        f"{name}/{f.name!r}")
        us_parity = (time.perf_counter() - t0) * 1e6 / (16 * len(
            SCHEDULER_NAMES))
        rows.append(("smoke/tenancy_default_parity", us_parity, derived(
            schedulers=len(SCHEDULER_NAMES), parity=1)))
    except Exception as e:
        traceback.print_exc()
        print(f"smoke/tenancy_default_parity,NaN,error={type(e).__name__}",
              file=sys.stderr)
        failures += 1

    # obs_off_parity smoke: a service with the full observability plane
    # enabled (decision traces at level 2 + audit ledger) must be bitwise
    # identical to the bare service — per-tick metrics AND final device
    # state — and the ledger must pass the offline conservation verifier.
    # ASSERTED, not just reported.
    try:
        import dataclasses as _dc
        import tempfile as _tf
        import os as _os

        import numpy as np

        from repro.obs import verify_ledger
        from repro.service import collect_service_metrics

        trace = make_trace("paper_default", "poisson", seed=0, n_devices=4,
                           pipelines_per_analyst=6).precompute(16)
        t0 = time.perf_counter()
        with _tf.TemporaryDirectory() as obsdir:
            for name in ("dpbalance", "dpf"):
                def obs_svc(**obs):
                    return FlaasService(ServiceConfig(
                        scheduler=name, sched=cfg, analyst_slots=4,
                        pipeline_slots=6,
                        block_slots=10 * trace.blocks_per_tick,
                        chunk_ticks=4, admit_batch=8, max_pending=32,
                        **obs), trace.reset())
                ledger = _os.path.join(obsdir, f"{name}.jsonl")
                off = obs_svc()
                on = obs_svc(trace_level=2, audit_path=ledger)
                ya = collect_service_metrics(off, 16)
                yb = collect_service_metrics(on, 16)
                on.close()
                for k in ya:
                    if not np.array_equal(np.asarray(ya[k]),
                                          np.asarray(yb[k])):
                        raise AssertionError(
                            f"obs-off parity violated on {name}/{k!r}")
                for f in _dc.fields(off.state):
                    if not np.array_equal(
                            np.asarray(getattr(off.state, f.name)),
                            np.asarray(getattr(on.state, f.name))):
                        raise AssertionError(
                            f"obs-off state parity violated on "
                            f"{name}/{f.name!r}")
                report = verify_ledger(ledger)
                if not report["ok"]:
                    raise AssertionError(
                        f"audit verification failed: "
                        f"{report['violations'][:3]}")
        us_parity = (time.perf_counter() - t0) * 1e6 / (16 * 2)
        rows.append(("smoke/obs_off_parity", us_parity, derived(
            schedulers=2, parity=1)))
    except Exception as e:
        traceback.print_exc()
        print(f"smoke/obs_off_parity,NaN,error={type(e).__name__}",
              file=sys.stderr)
        failures += 1

    # obs_overhead smoke: wall-clock cost of the observability plane —
    # off vs trace level 1 vs level 2 + audit + live exporter.  Ratios
    # reported (the paper-size measurement lives in benchmarks/history/).
    try:
        import tempfile as _tf
        import os as _os

        trace = make_trace("paper_default", "poisson", seed=0, n_devices=4,
                           pipelines_per_analyst=6).precompute(24)

        def timed(**obs):
            def one_run():
                svc = FlaasService(ServiceConfig(
                    scheduler="dpf", sched=cfg, analyst_slots=4,
                    pipeline_slots=6,
                    block_slots=10 * trace.blocks_per_tick,
                    chunk_ticks=4, admit_batch=8, max_pending=32, **obs),
                    trace.reset())
                t0 = time.perf_counter()
                svc.run(24)
                us = (time.perf_counter() - t0) * 1e6 / 24
                svc.close()
                return us
            one_run()                     # warm the per-variant jit cache
            return one_run()              # steady-state wall only

        with _tf.TemporaryDirectory() as obsdir:
            us_off = timed()
            us_l1 = timed(trace_level=1)
            us_l2 = timed(trace_level=2, metrics_port=0,
                          audit_path=_os.path.join(obsdir, "l.jsonl"))
        rows.append(("smoke/obs_overhead", us_off, derived(
            level1_us=round(us_l1, 1), level2_us=round(us_l2, 1),
            level1_ratio=round(us_l1 / us_off, 3),
            level2_ratio=round(us_l2 / us_off, 3))))
    except Exception as e:
        traceback.print_exc()
        print(f"smoke/obs_overhead,NaN,error={type(e).__name__}",
              file=sys.stderr)
        failures += 1

    # sp1_warm_parity smoke: warm-started SP1 duals vs cold solves on the
    # same episodes for all four schedulers — the scale-normalized metric
    # gap must stay within 10x the solver tolerance (baselines run no SP1,
    # so they come out bitwise identical).  ASSERTED, speedup reported for
    # the one scheduler that actually solves SP1 (dpbalance).
    try:
        import dataclasses

        import numpy as np

        warm_cfg = dataclasses.replace(cfg, sp1_warm_start=True)
        tol = 10 * cfg.solver_tol
        keys = ("round_efficiency", "round_fairness", "n_allocated",
                "leftover")
        worst = 0.0
        for name in SCHEDULER_NAMES:
            ya = run_episode(ep, cfg, name)
            yb = run_episode(ep, warm_cfg, name)
            for k in keys:
                a = np.asarray(ya[k], np.float64)
                b = np.asarray(yb[k], np.float64)
                gap = float(np.max(np.abs(a - b)) /
                            max(1.0, np.max(np.abs(a))))
                worst = max(worst, gap)
                if gap > tol:
                    raise AssertionError(
                        f"warm/cold parity violated on {name}/{k!r}: "
                        f"{gap:.2e} > {tol:.2e}")
        us_c = time_fn(lambda e: run_episode(e, cfg, "dpbalance"),
                       ep, iters=2)
        us_w = time_fn(lambda e: run_episode(e, warm_cfg, "dpbalance"),
                       ep, iters=2)
        rows.append(("smoke/sp1_warm_parity", us_w, derived(
            cold_us=round(us_c, 1), speedup=round(us_c / us_w, 2),
            max_gap=float(f"{worst:.3e}"), parity=1)))
    except Exception as e:
        traceback.print_exc()
        print(f"smoke/sp1_warm_parity,NaN,error={type(e).__name__}",
              file=sys.stderr)
        failures += 1

    # shard_throughput smoke: the sharded service over however many
    # devices the runner has (1 on a plain CPU; the sharded CI job runs
    # with an 8-device emulated mesh), ring wrap included.
    try:
        import jax

        from repro.shard import ShardedFlaasService

        n_shards = min(2, len(jax.devices()))
        trace = make_trace("paper_default", "poisson", seed=0, n_devices=4,
                           pipelines_per_analyst=6)
        svc_cfg = ServiceConfig(
            scheduler="dpf", sched=cfg, analyst_slots=4, pipeline_slots=6,
            block_slots=10 * trace.blocks_per_tick, chunk_ticks=4,
            admit_batch=8, max_pending=32)
        summary = ShardedFlaasService(svc_cfg, trace,
                                      n_shards=n_shards).run(12)
        rows.append(("smoke/sharded_service_dpf",
                     summary["wall_seconds"] * 1e6 / summary["ticks"],
                     derived(n_shards=summary["sharding"]["n_shards"],
                             ticks_per_s=round(summary["ticks_per_second"], 1),
                             allocated=summary["total_allocated"])))
    except Exception as e:
        traceback.print_exc()
        print(f"smoke/sharded_service_dpf,NaN,error={type(e).__name__}",
              file=sys.stderr)
        failures += 1
    return failures, rows


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="tiny engine episode per scheduler + tiny "
                             "service run, then exit")
    parser.add_argument("--json", metavar="PATH", default=None,
                        help="also write a structured JSON report (meta: "
                             "backend, resolved auto fleet mode, ...)")
    args = parser.parse_args()

    from repro.compile_cache import enable_compile_cache

    from .common import emit, write_json
    enable_compile_cache()

    if args.smoke:
        failures, rows = smoke()
        print("name,us_per_call,derived")
        emit(rows)
        if args.json:
            write_json(args.json, rows, extra_meta={"smoke": "1"})
        sys.exit(1 if failures else 0)

    from . import (bench_fig2, bench_fig4_5, bench_fig6, bench_kernels,
                   bench_scheduler_scale, bench_service, bench_train_step)

    all_rows = []
    failed = []
    print("name,us_per_call,derived")
    for mod in (bench_fig2, bench_fig4_5, bench_fig6, bench_scheduler_scale,
                bench_service, bench_kernels, bench_train_step):
        try:
            rows = mod.run()
            emit(rows)
            all_rows.extend(rows)
        except Exception as e:  # report every table, then fail the run
            traceback.print_exc()
            print(f"{mod.__name__},NaN,error={type(e).__name__}",
                  file=sys.stderr)
            failed.append(mod.__name__)
    if args.json:
        write_json(args.json, all_rows)
    if failed:
        print(f"{len(failed)} table(s) failed: {', '.join(failed)}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
