"""The trace reduction: busy union, idle share and gap attribution."""
import json
from pathlib import Path

import numpy as np
import pytest

from perfbench.harness import trace as tr

# The first 12 ms of a traced ledger_1k.dpbalance.live window on one TPU v5e
# (the window event cut to 12 ms; device ops and flaas/ spans overlapping
# it, op names cut to their head).
FIXTURE = Path(__file__).parent / "fixtures" / "trace_small.json"

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start_us, dur_us):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3}


def test_hand_made_trace():
    events = [
        ev(HOST, "python", "window", 0, 100),
        ev(HOST, "python", "flaas/admit_drain", 0, 30),
        ev(HOST, "python", "flaas/host_sync", 30, 60),
        ev(DEV, "XLA Ops", "fusion.1", 35, 20),
        ev(DEV, "XLA Ops", "fusion.2", 45, 20),     # overlaps fusion.1
        ev(DEV, "XLA Ops", "scatter", 80, 10),
        ev(DEV, "XLA Ops", "late", 95, 20),         # clipped at 100
        ev(DEV, "XLA Modules", "jit_step", 35, 60),  # not an op line
        ev(HOST, "python", "fusion.host", 0, 100),  # not a device plane
    ]
    red = tr.reduce(events)
    # busy: [35, 65) + [80, 90) + [95, 100) = 45 us
    assert red["busy_s"] == pytest.approx(45e-6)
    assert red["window_s"] == pytest.approx(100e-6)
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(20e-6)
    assert ops["late"] == pytest.approx(5e-6)
    assert set(ops) == {"fusion.1", "fusion.2", "scatter", "late"}
    gaps = dict(red["idle_gaps"])
    # [0, 35): admit_drain covers 30 of it; [65, 80): host_sync; [90, 95)
    # lies after host_sync ended
    assert gaps["admit_drain"] == pytest.approx(35e-6)
    assert gaps["host_sync"] == pytest.approx(15e-6)
    assert gaps["other"] == pytest.approx(5e-6)
    assert sum(gaps.values()) == pytest.approx(55e-6)


def test_no_window_or_no_device_op_reads_nothing():
    assert tr.reduce([ev(DEV, "XLA Ops", "f", 0, 1)]) is None
    assert tr.reduce([ev(HOST, "python", "window", 0, 10)]) is None


def test_recorded_trace_matches_a_grid_count():
    """The busy union of a real chip trace equals a count on a 100 ns grid,
    and busy time plus idle gaps fill the window."""
    events = json.loads(FIXTURE.read_text())
    red = tr.reduce(events)
    win = next(e for e in events if e["name"] == tr.WINDOW)
    w0, w1 = win["start_ns"], win["start_ns"] + win["dur_ns"]
    grid = np.zeros(int((w1 - w0) // 100) + 1, bool)
    for e in events:
        if e["plane"] == DEV and e["line"] == tr.OPS_LINE:
            a = int((max(e["start_ns"], w0) - w0) // 100)
            b = int((min(e["start_ns"] + e["dur_ns"], w1) - w0) // 100)
            grid[a:b] = True
    assert red["busy_s"] == pytest.approx(grid.sum() * 1e-7, rel=0.02)
    idle = sum(v for _, v in red["idle_gaps"])
    assert red["busy_s"] + idle == pytest.approx(red["window_s"], rel=1e-6)
    assert 0 < red["busy_s"] < red["window_s"]
    gaps = dict(red["idle_gaps"])
    assert max(gaps, key=gaps.get) == "admit_drain"   # the window opens there


def test_gap_attribution_matches_a_scan_of_every_span():
    """Each idle gap goes to the span that overlaps it most: the reduction,
    which looks only at spans near the gap, agrees with a scan of all."""
    rng = np.random.default_rng(7)
    events, t = [ev(HOST, "python", "window", 0, 20000)], 0
    while t < 20000:
        d = int(rng.integers(5, 200))
        name = ["admit_drain", "plan_mints", "host_sync"][int(rng.integers(3))]
        events.append(ev(HOST, "python", f"flaas/{name}", t, d))
        t += d + int(rng.integers(0, 20))
    for s in rng.integers(0, 20000, 300):
        events.append(ev(DEV, "XLA Ops", "op", int(s), int(rng.integers(1, 50))))
    red = tr.reduce(events)
    spans = [(e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"][6:])
             for e in events if e["name"].startswith("flaas/")]
    merged = tr._union([(max(e["start_ns"], 0.0),
                         min(e["start_ns"] + e["dur_ns"], 2e7))
                        for e in events if e["plane"] == DEV])
    edges = [0.0] + [x for iv in merged for x in iv] + [2e7]
    want = {}
    for s, u in zip(edges[0::2], edges[1::2]):
        if u <= s:
            continue
        best, who = 0.0, "other"
        for ps, pt, name in spans:
            if min(u, pt) - max(s, ps) > best:
                best, who = min(u, pt) - max(s, ps), name
        want[who] = want.get(who, 0.0) + (u - s) * 1e-9
    got = dict(red["idle_gaps"])
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k])
