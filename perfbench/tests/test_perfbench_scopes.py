"""Device time by named scope, launch, idle by innermost span and transfer
counts (``harness/scopes.py``), and the new per-layer readers."""
import json
from pathlib import Path

import numpy as np
import pytest

from perfbench.harness import scopes
from perfbench.harness import trace as tr
from perfbench.metrics import (admit_write_ms_per_round,
                               copy_out_ms_per_round,
                               device_wait_ms_per_round, ledger_ms_per_round, schedule_ms_per_round,
                               sp1_ms_per_round, sp2_ms_per_round,
                               transfers_per_round)

FIXTURES = Path(__file__).parent / "fixtures"
SMALL = FIXTURES / "trace_small.json"
US = 1e3                                    # ns per microsecond


def device(modules, rows):
    """A device plane in :func:`scopes.load`'s form, from op rows
    ``[hlo_module, hlo_op, start_ns, end_ns]``."""
    keys = sorted({(m, o) for m, o, _, _ in rows})
    index = {k: i for i, k in enumerate(keys)}
    return {"modules": modules, "keys": [list(k) for k in keys],
            "ops": {"key": [index[(m, o)] for m, o, _, _ in rows],
                    "start_ns": [r[2] for r in rows],
                    "end_ns": [r[3] for r in rows]}}


def hand_made():
    """One round: admission (its module runs in the write), then the
    chunk program, launched 5 us after chunk_execute opens."""
    chunk = "jit_flaas_chunk(7)"
    return {
        "window": [0.0, 100 * US],
        "spans": [
            ["flaas/admit_drain", 0, 30 * US, 4],
            ["flaas/admit_drain/write", 10 * US, 30 * US, 4],
            ["flaas/chunk_execute", 30 * US, 32 * US, 4],
            ["flaas/host_sync", 32 * US, 90 * US, 4],
            ["flaas/host_sync/device_wait", 32 * US, 80 * US, 4],
            ["flaas/host_sync/copy_out", 80 * US, 90 * US, 4],
        ],
        # the round's counts, on its last span; the next round's falls
        # after the window
        "transfers": [[90 * US, 3], [120 * US, 5]],
        "devices": [device(
            [["jit__admit_apply(3)", 15 * US, 20 * US],
             [chunk, 35 * US, 75 * US]],
            [["jit__admit_apply(3)", "scatter", 15 * US, 20 * US],
             [chunk, "while.1", 35 * US, 60 * US],      # SP1's loop
             [chunk, "fusion.2", 40 * US, 50 * US],     # inside it
             [chunk, "fusion.3", 60 * US, 70 * US],
             [chunk, "copy.4", 70 * US, 75 * US]])],    # no scope
        "op_names": {
            "jit__admit_apply(3)": {"scatter": "jit(_admit_apply)/admit/"
                                               "scatter"},
            chunk: {"while.1": "jit(flaas_chunk)/while/body/closed_call/"
                               "schedule/sp1/while",
                    "fusion.2": "jit(flaas_chunk)/while/body/schedule/sp1/"
                                "mul",
                    "fusion.3": "jit(flaas_chunk)/while/body/ledger/max",
                    "copy.4": ""},
        },
    }


def test_hand_made_scopes():
    red = scopes.reduce(hand_made())
    assert red["window_s"] == pytest.approx(100e-6)
    assert red["busy_s"] == pytest.approx(45e-6)        # 5 + 40
    s = red["scope_s"]
    assert s["admit"] == pytest.approx(5e-6)
    assert s["sp1"] == pytest.approx(25e-6)             # union, nested op
    assert s["schedule"] == pytest.approx(25e-6)        # holds sp1
    assert s["ledger"] == pytest.approx(10e-6)
    assert "sp2" not in s
    assert red["unscoped_s"] == pytest.approx(5e-6)     # copy.4
    # chunk_execute opens at 30 us; the chunk's first op starts at 35 us
    assert red["launch_s"] == [pytest.approx(5e-6)]
    assert red["transfers"] == 3                        # inside the window
    idle = red["idle_by_span"]
    # idle: [0, 15) admit_drain 10 + write 5; [20, 35): write 10,
    # chunk_execute 2, device_wait 3; [75, 100): device_wait 5,
    # copy_out 10, outside every span 10
    assert idle["admit_drain"] == pytest.approx(10e-6)
    assert idle["admit_drain/write"] == pytest.approx(15e-6)
    assert idle["chunk_execute"] == pytest.approx(2e-6)
    assert idle["host_sync/device_wait"] == pytest.approx(8e-6)
    assert idle["host_sync/copy_out"] == pytest.approx(10e-6)
    assert idle["other"] == pytest.approx(10e-6)
    assert "host_sync" not in idle                      # never innermost
    assert sum(idle.values()) == pytest.approx(55e-6)


def test_no_window_or_no_ops():
    t = hand_made()
    assert scopes.reduce(dict(t, window=None)) is None
    t["devices"] = [device([], [])]
    assert scopes.reduce(t) is None


def test_op_scopes_in_path_order():
    assert scopes.op_scopes("jit(f)/while/body/schedule/sp2/while/x") == \
        ["schedule", "sp2"]
    assert scopes.op_scopes("jit(f)/scheduler/x") == []


def test_child_spans_keep_the_idle_gaps():
    """The existing reduction charges each idle gap to the span that
    overlaps it most: children inside their parent leave that unchanged."""
    events = json.loads(SMALL.read_text())
    before = tr.reduce(events)["idle_gaps"]
    more = list(events)
    for e in events:
        if not e["name"].startswith("flaas/"):
            continue
        s, d = e["start_ns"], e["dur_ns"]
        for i, child in enumerate(("a", "b", "c")):
            more.append(dict(e, name=f"{e['name']}/{child}",
                             start_ns=s + 100 + i * d / 3,
                             dur_ns=d / 3 - 200))
    after = tr.reduce(more)["idle_gaps"]
    assert dict(after) == pytest.approx(dict(before))


# ---------------------------------------------------------------- readers
def ctx(**kw):
    base = {"rounds": 4, "window_s": 0.1, "round_s": [], "phases": {},
            "trace": None}
    base.update(kw)
    return base


def test_span_readers():
    c = ctx(phases={"host_sync/device_wait": 0.02,
                    "host_sync/copy_out": 0.008,
                    "admit_drain/write": 0.004})
    assert device_wait_ms_per_round.read(c) == pytest.approx(5.0)
    assert copy_out_ms_per_round.read(c) == pytest.approx(2.0)
    assert admit_write_ms_per_round.read(c) == pytest.approx(1.0)


@pytest.mark.parametrize("reader", [
    sp1_ms_per_round, sp2_ms_per_round, schedule_ms_per_round,
    ledger_ms_per_round, transfers_per_round,
    device_wait_ms_per_round, copy_out_ms_per_round,
    admit_write_ms_per_round])
def test_readers_quiet_without_their_records(reader, tmp_path,
                                             monkeypatch):
    """A program without the spans (the parent of this change) or a run
    without a trace: nothing to read, and no error."""
    monkeypatch.setattr(scopes, "TRACE_ROOT", str(tmp_path))
    assert reader.read(ctx()) is None
    assert reader.read(ctx(trace={"window_s": 5.0, "busy_s": 1.0},
                           phases={"host_sync": 1.0})) is None


def test_trace_readers_take_the_run_s_trace(tmp_path, monkeypatch):
    red = scopes.reduce(hand_made())
    monkeypatch.setattr(scopes, "TRACE_ROOT", str(tmp_path))
    path = tmp_path / "cell" / "plugins" / "profile" / "r" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(b"")
    monkeypatch.setattr(scopes, "reduce_file", lambda p: red)
    c = ctx(rounds=1, trace={"window_s": red["window_s"], "busy_s": 1.0})
    assert sp1_ms_per_round.read(c) == pytest.approx(0.025)
    assert schedule_ms_per_round.read(c) == pytest.approx(0.025)
    assert ledger_ms_per_round.read(c) == pytest.approx(0.010)
    assert sp2_ms_per_round.read(c) is None            # no sp2 op
    assert transfers_per_round.read(c) == 3
    # another run's trace (its window differs): nothing read
    other = ctx(rounds=1, trace={"window_s": 7.0, "busy_s": 1.0})
    assert sp1_ms_per_round.read(other) is None


# ------------------------------------------------ a recorded chip trace
# Three rounds of a traced paper_vi.dpf.live window on one TPU v5e:
# scopes.load() of the run's .xplane.pb, cut to the three rounds from one
# admit_drain span's start to the fourth's (spans, transfer counts,
# modules and ops that overlap them; op_names of the ops kept; times
# rounded to 0.1 ns).  In this trace the device clock runs about 0.25-0.5
# ms ahead of the host's, so every launch reads below zero.
SCOPED = FIXTURES / "trace_scoped.json"


@pytest.fixture(scope="module")
def recorded():
    return json.loads(SCOPED.read_text())


def test_recorded_scopes(recorded):
    red = scopes.reduce(recorded)
    s = red["scope_s"]
    assert set(s) == {"schedule", "grant_scan", "ledger", "round_metrics",
                      "admit"}                  # dpf: no SP1 or SP2
    assert s["grant_scan"] <= s["schedule"] <= red["busy_s"]
    assert red["unscoped_s"] < 0.05 * red["busy_s"]
    dev = recorded["devices"][0]
    paths = scopes._key_scopes(dev["keys"], recorded["op_names"])
    for (mod, op), path in zip(dev["keys"], paths):
        named = scopes.op_scopes(recorded["op_names"][mod][op])
        if mod.startswith("jit__admit_apply"):
            assert path == ["admit"]           # the scatter fusion too
        elif named:
            assert path == named
    assert any(mod.startswith("jit__admit_apply") and
               not recorded["op_names"][mod][op] for mod, op in dev["keys"])


def test_recorded_ticks(recorded):
    ticks = {sp[3] for sp in recorded["spans"]}
    assert None not in ticks and len(ticks) in (3, 4)
    assert sorted(ticks) == list(range(min(ticks), max(ticks) + 1))


def test_recorded_transfers(recorded):
    """Each round's counts ride its last span: 6 mint-op and 3 graft
    uploads and 14 downloads (the tick read and 13 outputs) a paged
    round, and 9 uploads more in a round that admits."""
    writes = {sp[3] for sp in recorded["spans"]
              if sp[0] == "flaas/admit_drain/write"}
    folds = {sp[1]: sp[3] for sp in recorded["spans"]
             if sp[0] == "flaas/telemetry_fold"}
    assert len(recorded["transfers"]) == 3
    for at, n in recorded["transfers"]:
        assert n == 6 + 3 + 14 + 9 * (folds[at] in writes)
    assert scopes.reduce(recorded)["transfers"] == \
        sum(n for _, n in recorded["transfers"])


def test_recorded_launch(recorded):
    """One launch a round: the round's chunk_execute span to the first op
    of the jit_flaas_chunk run nearest it, as a plain loop finds it."""
    red = scopes.reduce(recorded)
    dev = recorded["devices"][0]
    starts = sorted(dev["ops"]["start_ns"])
    want = []
    for name, s, _, _ in recorded["spans"]:
        if name != "flaas/chunk_execute":
            continue
        runs = [m for m in dev["modules"]
                if m[0].startswith("jit_flaas_chunk(")]
        near = min(runs, key=lambda m: abs(m[1] - s))
        first = min(x for x in starts if x >= near[1])
        want.append((first - s) * 1e-9)
    assert len(want) == 3
    assert red["launch_s"] == pytest.approx(want)
    assert all(abs(x) < 2e-3 for x in red["launch_s"])


def test_recorded_innermost_idle(recorded):
    """Idle time by innermost span against a 1 us grid over the window."""
    red = scopes.reduce(recorded)
    w0, w1 = recorded["window"]
    dev = recorded["devices"][0]
    grid = np.arange(w0, w1, 1e3) + 500.0
    busy = np.zeros(len(grid), bool)
    for s, e in zip(dev["ops"]["start_ns"], dev["ops"]["end_ns"]):
        busy[(grid >= s) & (grid < e)] = True
    owner = np.full(len(grid), "other", dtype=object)
    depth = np.full(len(grid), -1)
    for name, s, e, _ in recorded["spans"]:
        if not name.startswith("flaas/"):
            continue
        inside = (grid >= s) & (grid < e)
        d = name.count("/")
        deeper = inside & (depth < d)
        owner[deeper] = name[len("flaas/"):]
        depth[deeper] = d
    idle = red["idle_by_span"]
    assert sum(idle.values()) + red["busy_s"] == \
        pytest.approx(red["window_s"], rel=1e-6)
    for who in set(owner[~busy]):
        grid_s = np.sum(~busy & (owner == who)) * 1e-6
        assert idle.get(who, 0.0) == pytest.approx(grid_s, abs=2e-5), who


def test_load_reads_a_profiler_trace(tmp_path):
    """``load`` parses what the JAX profiler writes: the window, the
    ``flaas/`` spans with their ticks, the round's transfer counts, and
    each program's ``op_name``s from the metadata plane (a CPU trace has
    no TPU plane)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("sp1"):
            return jnp.sin(x) * 2.0

    x = jnp.ones(64)
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("flaas/admit_drain", tick=3):
                step(x + 1.0).block_until_ready()
            with jax.profiler.TraceAnnotation("flaas/telemetry_fold",
                                              tick=3, h2d=9, d2h=14):
                pass
    finally:
        jax.profiler.stop_trace()
    t = scopes.load(scopes.newest_xplane(str(tmp_path)))
    assert t["window"] is not None and t["window"][1] > t["window"][0]
    spans = {sp[0]: sp for sp in t["spans"]}
    assert spans["flaas/admit_drain"][3] == 3
    assert spans["flaas/telemetry_fold"][3] == 3
    ((at, n),) = t["transfers"]
    assert n == 23 and at == spans["flaas/telemetry_fold"][1]
    w0, w1 = t["window"]
    _, s, e, _ = spans["flaas/admit_drain"]
    assert w0 <= s < e <= w1
    assert t["devices"] == []
    names = [n for ops in t["op_names"].values() for n in ops.values()]
    assert any(scopes.op_scopes(n) == ["sp1"] for n in names)


# ------------------------------------------------- an engine fleet's trace
@pytest.fixture(scope="module")
def engine_op_names(tmp_path_factory):
    """``{hlo_module: {hlo_op: op_name}}`` of the sweep entry's program,
    ``engine.run_fleet`` (dpbalance, map, diagnostics) on a tiny fleet,
    as the profiler's metadata plane gives them (a CPU trace)."""
    import jax
    from perfbench.harness import sweep
    from repro.core import SchedulerConfig, engine
    d = {"n_devices": 4, "blocks_per_device": 2, "pipelines_per_analyst": 3,
         "mice_frac": 0.75, "mice_eps": [0.005, 0.015],
         "elephant_eps": [0.095, 0.105], "budget_range": [1.0, 1.5],
         "p_ten_blocks": 0.25, "p_subset_devices": 0.5, "subset_frac": 0.2,
         "arrival_rate": 1.0, "analyst_slots": 3, "pipeline_slots": 3}
    fleet = sweep._engine_fleet(sweep.fleets(d, 7, 1, 2, 3)[0], 3)
    cfg = SchedulerConfig(solver_iters=50)
    path = tmp_path_factory.mktemp("engine")
    jax.profiler.start_trace(str(path))
    try:
        engine.run_fleet(fleet, cfg, "dpbalance", diagnostics=True,
                         mode="map")
    finally:
        jax.profiler.stop_trace()
    return scopes.load(scopes.newest_xplane(str(path)))["op_names"]


def test_engine_trace_readers(engine_op_names, tmp_path, monkeypatch):
    """The sweep's per-layer readers on an engine trace: the fleet
    program's ops carry the ``sp1`` and ``sp2`` scopes of
    ``core/scheduler.py`` and no ``schedule`` scope (that one is the
    service's), so ``sp1``, ``sp2``, busy and idle read their device time
    per episode-round and ``schedule_ms_per_round`` reads nothing.  The
    device timeline is laid out by hand: 30 us of SP1 (two ops, one inside
    the other), 10 us of SP2 and 20 us of unscoped ops in a 100 us
    window."""
    from perfbench.metrics import device_busy_ms_per_round, device_idle_pct
    # the metadata plane holds every module the process has loaded (other
    # tests' service chunks too): the fleet's is map mode's ``jit__lambda``
    module, ops = max(((m, o) for m, o in engine_op_names.items()
                       if m.startswith("jit__lambda")),
                      key=lambda kv: sum("sp1" in scopes.op_scopes(n)
                                         for n in kv[1].values()))
    by = {}
    for op, name in ops.items():
        by.setdefault(tuple(scopes.op_scopes(name)), []).append(op)
    assert not any("schedule" in p for p in by)
    sp1 = [o for p, os in by.items() if p[-1:] == ("sp1",) for o in os]
    sp2 = [o for p, os in by.items() if p[-1:] == ("sp2",) for o in os]
    rest = by[()]
    assert len(sp1) >= 2 and sp2 and len(rest) >= 2
    rows = [[module, sp1[0], 0, 30 * US], [module, sp1[1], 5 * US, 25 * US],
            [module, sp2[0], 40 * US, 50 * US],
            [module, rest[0], 60 * US, 70 * US],
            [module, rest[1], 70 * US, 80 * US]]
    recorded = {"window": [0.0, 100 * US], "spans": [], "transfers": [],
                "devices": [device([[module, 0, 80 * US]], rows)],
                "op_names": engine_op_names}
    red = scopes.reduce(recorded)
    assert red["scope_s"] == {"sp1": pytest.approx(30e-6),
                              "sp2": pytest.approx(10e-6)}
    assert red["busy_s"] == pytest.approx(60e-6)
    monkeypatch.setattr(scopes, "TRACE_ROOT", str(tmp_path))
    path = tmp_path / "cell" / "plugins" / "profile" / "r" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(b"")
    monkeypatch.setattr(scopes, "reduce_file", lambda p: red)
    # 3 calls of a 4-episode fleet of 10 rounds in the window
    events = [{"plane": "/host:CPU", "line": "x", "name": "window",
               "start_ns": 0.0, "dur_ns": 100 * US}]
    events += [{"plane": "/device:TPU:0", "line": "XLA Ops",
                "name": f"%{op} = f32[] x()", "start_ns": float(s),
                "dur_ns": float(e - s)} for _, op, s, e in rows]
    c = ctx(rounds=3 * 4 * 10, trace=tr.reduce(events))
    assert c["trace"]["busy_s"] == pytest.approx(60e-6)
    per_round = 1e3 / c["rounds"]
    assert sp1_ms_per_round.read(c) == pytest.approx(30e-6 * per_round)
    assert sp2_ms_per_round.read(c) == pytest.approx(10e-6 * per_round)
    assert device_busy_ms_per_round.read(c) == \
        pytest.approx(60e-6 * per_round)
    assert device_idle_pct.read(c) == pytest.approx(40.0)
    assert schedule_ms_per_round.read(c) is None
