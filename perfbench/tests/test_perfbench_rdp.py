"""The ``privatekube_rdp.dpf.rdp_live`` cell at a size a CPU holds: a sound
run is correct; the control (the reference in bfloat16) and every fault
planted under the timed path make ``correct`` false; the two new
per-layer readers read the RDP scopes off a CPU trace's op names, and
nothing off a scalar program's."""
import json
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import check, generator_rdp, rdp_live, runner, \
    rdp_scopes, scopes, spec
from perfbench.metrics import order_fits_ms_per_round, \
    order_ledger_ms_per_round
from repro.service import FlaasService

ROOT = Path(__file__).resolve().parents[2]
CELL = "privatekube_rdp.dpf.rdp_live"
SEED = 3 * 2 ** 31 + 17
US = 1e3


def tiny_cell():
    """The cell's mix, limits and configuration over 10 devices, 4
    analysts x 25 pipelines and a 200-block ring (a wrap every 10 ticks)."""
    c = spec.cell(CELL, spec.benchmark(ROOT))
    cfg = json.loads(json.dumps(c["config"]))
    cfg["deployment"].update(n_devices=10, analyst_slots=4, block_slots=200,
                             admit_batch=4, max_pending=32)
    cfg["warmup_ticks"] = 12
    return dict(c, config=cfg)


def measure(cell, service_cls=None, seconds=0.4):
    return runner.measure(cell, SEED, seconds, False, time.perf_counter(),
                          service_cls=service_cls)


class Planted(FlaasService):
    """The program with one fault planted in what a tick hands back, at
    tick ``AT`` (a tick past the ring's first wrap)."""

    AT = 15

    def plant(self, state, final, ys):
        raise NotImplementedError

    def _compiled_step(self, n_ticks, mode):
        step = super()._compiled_step(n_ticks, mode)

        def run(state, ops):
            final, ys = step(state, ops)
            if int(state.tick) == self.AT:
                final, ys = self.plant(state, final, ys)
            return final, ys
        return run


class DropGrant(Planted):
    """The tick's first grant is reported as not granted; its debit
    stays."""

    def plant(self, state, final, ys):
        sel = ys["selected"]
        i = np.argwhere(np.asarray(sel))
        if len(i):
            sel = sel.at[tuple(i[0])].set(False)
        return final, dict(ys, selected=sel)


class MoveCapacity(Planted):
    """One block's capacity at one order moved by 1e-3 epsilon."""

    def plant(self, state, final, ys):
        cap = final[-1]
        return final[:-1] + (cap.at[3, 5].add(1e-3),), ys


class SkipOrderDebit(Planted):
    """One order's debit skipped: that order keeps the capacity it had
    before the tick's grants (its mint kept)."""

    def plant(self, state, final, ys):
        cap = final[-1]
        T = state.tick
        spec_cap = np.asarray(self.cfg.rdp.capacity)
        # the ledger as the tick's mints left it, before the debits
        before = np.array(state.block_capacity)
        bpr = self.trace.blocks_per_tick
        B = self.cfg.block_slots
        slots = (int(T) * bpr + np.arange(bpr)) % B
        before[:, slots] = spec_cap[:, None]
        a = int(np.argmax(np.abs(np.asarray(cap) - before).sum(axis=1)))
        return final[:-1] + (cap.at[a].set(before[a]),), ys


class OverdrawBlock(Planted):
    """One live block overdrawn at every order."""

    def plant(self, state, final, ys):
        cap = final[-1]
        return final[:-1] + (cap.at[:, 7].set(-1.0),), ys


def test_sound_run_is_correct():
    cell = tiny_cell()
    out = measure(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {k for k, c in out["checks"].items()
            if c["limit"] is not None} == set(cell["limits"])
    assert out["checks"]["order_faults"]["value"] == 0.0


@pytest.mark.parametrize("fault", [DropGrant, MoveCapacity, SkipOrderDebit,
                                   OverdrawBlock], ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault):
    out = measure(tiny_cell(), service_cls=fault)
    assert not out["correct"], out["checks"]
    if fault is OverdrawBlock:      # the guarantee's own number reads it
        assert out["checks"]["order_faults"]["value"] >= 1


def test_control_in_bfloat16_fails_the_limits():
    cell = tiny_cell()
    entry = runner.entry_module(cell["traffic"]["entry"])
    assert entry.REFERENCE == "reference_rdp"
    raw = entry.control_raw(cell, SEED, 40, jnp.bfloat16)
    ok, checks = check.verdict(entry.numbers(cell, raw), cell["limits"])
    assert not ok, checks


def test_curves_are_calibrated_to_the_paper_shares():
    """Each pipeline's best share of a fresh block is its §VI draw over
    the mean device budget; mice Laplace and every Gaussian bind at order
    5, elephant Laplace at order 64."""
    cell = tiny_cell()
    arrivals, curves = rdp_live.inputs(cell, SEED)
    raw_eps = [[float(e[0]) for e in b.eps]
               for t in arrivals.ticks(6) for b in t]
    curves.attach(arrivals.events)
    got = [b.curves for t in arrivals.events for b in t]
    orders, cap = curves.orders, curves.cap
    best = set()
    for eps, cs in zip(raw_eps, got):
        for e, c in zip(eps, cs):
            share = np.asarray(c, np.float64) / cap
            assert share.min() == pytest.approx(e / 1.25, rel=1e-5)
            best.add(float(orders[np.argmin(share)]))
    assert best == {5.0, 64.0}
    assert all((np.concatenate(b.eps) == 1.0).all()
               for t in arrivals.events for b in t)
    d = cell["config"]["deployment"]
    again = rdp_live.inputs(cell, SEED)
    again[1].attach(again[0].ticks(6))
    assert all(np.array_equal(x, y) for t, u in zip(arrivals.events,
                                                     again[0].events)
               for b, c in zip(t, u) for x, y in zip(b.curves, c.curves))
    assert generator_rdp.usable(cell["config"]["rdp"])[0][0] == 3.0
    assert d["n_devices"] == 10


# ------------------------------------------------------- the two readers
@pytest.fixture(scope="module")
def rdp_op_names(tmp_path_factory):
    """``{hlo_module: {hlo_op: op_name}}`` of a traced CPU run of the
    cell's program (the profiler's metadata plane)."""
    cell = tiny_cell()
    path = tmp_path_factory.mktemp("rdp_trace")
    rdp_live.run(cell, SEED, 0.2, True, time.perf_counter(), str(path))
    return scopes.load(scopes.newest_xplane(str(path)))["op_names"]


def _device(module, rows):
    keys = sorted({(module, o) for o, _, _ in rows})
    index = {k: i for i, k in enumerate(keys)}
    return {"modules": [[module, 0, 100 * US]],
            "keys": [list(k) for k in keys],
            "ops": {"key": [index[(module, o)] for o, _, _ in rows],
                    "start_ns": [r[1] for r in rows],
                    "end_ns": [r[2] for r in rows]}}


def test_readers_on_a_cpu_trace(rdp_op_names, tmp_path, monkeypatch):
    """The chunk program's ops carry ``order_fits`` (inside ``schedule``)
    and ``order_ledger`` (inside ``ledger``); laid out by hand, 30 us of
    the first (two ops, one inside the other) and 10 us of the second in
    a 100 us window of 4 rounds read 0.0075 and 0.0025 ms per round; a
    trace of ops without those scopes reads nothing."""
    # the metadata plane holds every module the process has loaded (other
    # tests' scalar chunks too): take the chunk with the RDP scopes
    module, ops = max(((m, o) for m, o in rdp_op_names.items()
                       if m.startswith("jit_flaas_chunk")),
                      key=lambda kv: sum("order_fits" in n.split("/")
                                         for n in kv[1].values()))
    fits = [o for o, n in ops.items() if "order_fits" in n.split("/")]
    ledger = [o for o, n in ops.items() if "order_ledger" in n.split("/")]
    assert len(fits) >= 2 and ledger
    assert all("schedule" in ops[o].split("/") for o in fits)
    assert all("ledger" in ops[o].split("/") for o in ledger)
    rows = [[fits[0], 0, 30 * US], [fits[1], 5 * US, 25 * US],
            [ledger[0], 40 * US, 50 * US]]
    recorded = {"window": [0.0, 100 * US], "spans": [], "transfers": [],
                "devices": [_device(module, rows)], "op_names": rdp_op_names}
    monkeypatch.setattr(scopes, "TRACE_ROOT", str(tmp_path))
    monkeypatch.setattr(scopes, "load", lambda p: recorded)
    path = tmp_path / "cell" / "plugins" / "profile" / "r" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(b"")
    rdp_scopes._scope_s.cache_clear()
    ctx = {"rounds": 4, "trace": {"window_s": 100e-6}}
    assert order_fits_ms_per_round.read(ctx) == pytest.approx(0.0075)
    assert order_ledger_ms_per_round.read(ctx) == pytest.approx(0.0025)
    # a program without the RDP ledger (the scalar chunk's names)
    rdp_scopes._scope_s.cache_clear()
    recorded["op_names"] = {module: {o: n.replace("order_", "x_")
                                     for o, n in ops.items()}}
    assert order_fits_ms_per_round.read(ctx) is None
    assert order_ledger_ms_per_round.read(ctx) is None
    rdp_scopes._scope_s.cache_clear()
    assert order_fits_ms_per_round.read({"rounds": 4, "trace": None}) is None
