"""Round spans, transfer and compile counters, the per-round ring, and the
named device scopes of the service tick loop (``repro.obs.profiler``).

* the span tree: ``<parent>/<child>`` names, parents, the round's tick on
  every span, self time = duration minus the children's;
* the leaf spans cover ``run_chunk``; the ring keeps a fixed number of
  rounds;
* the transfer counters agree with a count read off the code, and a new
  padded admission size's compiles are charged to ``admit_drain/write``;
* every named scope reaches the compiled chunk and admission programs,
  and ``profile_annotations`` leaves the chunk program unchanged.
"""
import time

import jax
import numpy as np
import pytest

from repro.core import SchedulerConfig
from repro.obs import MetricsRegistry, PhaseProfiler
from repro.obs.profiler import RING_ROUNDS
from repro.service import FlaasService, ServiceConfig, make_trace
from repro.service import state as state_mod
from repro.service.server import ROUND_SPANS

# 8 blocks a tick into a 56-slot ring (a geometry no other test file
# compiles, so this file sees its own compilations): the ring wraps at
# tick 7 and every later round runs the paged chunk program.
SIZE = dict(n_devices=4, pipelines_per_analyst=6)
RING, TICKS = 56, 24
# outputs the paged chunk returns (tick_out's ten, expired, the two
# paging counters), all in one packed device->host copy
PAGED_YS = 13
# mint-op uploads of a paged chunk, graft uploads, admission uploads
# (one packed buffer), and the operands that buffer carries
PAGED_UPLOADS, GRAFT_UPLOADS, ADMIT_OPERANDS = 6, 3, 1
ADMIT_PACKED = 9


def service(scheduler="dpbalance", **over):
    trace = make_trace("paper_default", "poisson", seed=2,
                       **SIZE).precompute(TICKS + 2)
    cfg = ServiceConfig(scheduler=scheduler, sched=SchedulerConfig(beta=2.2),
                        analyst_slots=3, pipeline_slots=6, block_slots=RING,
                        chunk_ticks=1, admit_batch=8, max_pending=64, **over)
    return FlaasService(cfg, trace.reset())


@pytest.fixture
def cold_compiles():
    """Every program compiles afresh: compilations are counted from XLA's
    compile events, which neither an in-process cache hit nor a
    persistent-cache hit raises."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


# ================================================================ spans
class TestSpanTree:
    def test_nesting_parents_ticks_self_time(self):
        prof = PhaseProfiler(ring_spans=("a", "a/b", "a/c"))
        with prof.round(7):
            with prof.phase("a"):
                with prof.phase("b"):
                    time.sleep(0.002)
                with prof.phase("c"):
                    time.sleep(0.001)
                time.sleep(0.001)
        recs = {r.name: r for r in prof.round_spans()}
        assert list(recs) == ["a/b", "a/c", "a"]     # close order
        assert recs["a/b"].parent == "a" and recs["a/c"].parent == "a"
        assert recs["a"].parent is None
        assert {r.tick for r in recs.values()} == {7}
        a = recs["a"]
        assert a.self_seconds == pytest.approx(
            a.seconds - recs["a/b"].seconds - recs["a/c"].seconds)
        assert a.self_seconds >= 0.001
        # leaves have no children: self time is their duration
        assert recs["a/b"].self_seconds == recs["a/b"].seconds
        # the parent's totals cover its children
        assert prof.seconds["a"] >= prof.seconds["a/b"] + prof.seconds["a/c"]
        row = prof.rounds()[-1]
        assert row["tick"] == 7
        assert row["a"] == pytest.approx(a.self_seconds)
        assert row["wall_s"] >= a.seconds
        assert prof.leaves() == ["a/b", "a/c"]

    def test_spans_outside_a_round_carry_no_tick(self):
        prof = PhaseProfiler()
        with prof.phase("checkpoint_save"):
            pass
        (rec,) = prof.round_spans()
        assert rec.tick is None and rec.parent is None
        assert prof.rounds().shape == (0,)

    def test_service_span_tree(self):
        svc = service()
        svc.run(TICKS)
        recs = svc.profiler.round_spans()
        names = {r.name for r in recs}
        assert names <= set(ROUND_SPANS)
        for r in recs:
            assert r.tick == TICKS - 1
            assert r.parent == (r.name.rsplit("/", 1)[0]
                                if "/" in r.name else None)
        # the spans every round opens (admission may place nothing)
        assert {"admit_drain/poll", "admit_drain/queue", "plan_mints/plan",
                "plan_mints/upload", "chunk_execute", "state_graft",
                "host_sync/device_wait", "host_sync/copy_out", "recycle",
                "telemetry_fold"} <= names
        svc.close()

    def test_leaves_cover_run_chunk(self):
        svc = service()
        svc.run(TICKS)
        prof = svc.profiler
        rows = prof.rounds()
        assert len(rows) == TICKS
        assert list(rows["tick"]) == list(range(TICKS))
        leaves = prof.leaves()
        assert "admit_drain" not in leaves and "host_sync" not in leaves
        covered = sum(rows[s] for s in leaves)
        outside = rows["wall_s"] - covered
        assert np.all(outside >= 0.0)
        # what no leaf covers: the parents' own lines and the spans'
        # bookkeeping, a small share of a round
        assert np.median(outside / rows["wall_s"]) < 0.1
        # the round span's one duration feeds the wall total and the
        # chunk-seconds histogram
        assert svc._wall == pytest.approx(float(rows["wall_s"].sum()))
        cell = svc.registry.histogram("flaas_chunk_seconds", "")._cell(())
        assert cell["n"] == TICKS
        assert cell["sum"] == pytest.approx(svc._wall)
        svc.close()


class TestRing:
    def test_fixed_size(self):
        prof = PhaseProfiler(ring_spans=("x",))
        nbytes = prof._ring.nbytes
        n = RING_ROUNDS + 12
        for t in range(n):
            with prof.round(t):
                with prof.phase("x"):
                    pass
        rows = prof.rounds()
        assert len(rows) == RING_ROUNDS and prof._ring.nbytes == nbytes
        assert list(rows["tick"]) == list(range(12, n))     # oldest first
        assert np.all(rows["x"] > 0.0)

    def test_slow_rounds_name_the_largest_leaf(self):
        prof = PhaseProfiler(ring_spans=("p", "p/fast", "p/slow"))
        for t, pause in ((0, 0.0), (1, 0.03)):
            with prof.round(t):
                with prof.phase("p"):
                    with prof.phase("fast"):
                        pass
                    with prof.phase("slow"):
                        time.sleep(pause)
        (slow,) = prof.slow_rounds(0.02)
        assert slow[0] == 1 and slow[1] >= 30.0 and slow[2] == "p/slow"

    def test_raising_round_writes_no_row(self):
        prof = PhaseProfiler()
        with pytest.raises(RuntimeError):
            with prof.round(3):
                raise RuntimeError
        assert len(prof.rounds()) == 0


# ============================================================= counters
class TestCounters:
    def test_transfer_counts_match_the_code(self):
        svc = service()
        svc.run(TICKS)
        rows = svc.profiler.rounds()
        paged = rows[rows["tick"] >= RING // 8]        # the ring has wrapped
        assert len(paged) > 10
        admitted = paged["admitted"] > 0
        assert admitted.any() and (~admitted).any()
        # one tick read + one packed copy of every chunk output
        assert np.all(paged["d2h"] == 1 + 1)
        assert np.all(paged["packed_outputs"] == PAGED_YS)
        assert np.all(paged["h2d"] == PAGED_UPLOADS + GRAFT_UPLOADS
                      + ADMIT_OPERANDS * admitted)
        assert np.all(paged["packed_inputs"] == ADMIT_PACKED * admitted)
        # graft uploads: block budgets and births [B] + the tick
        assert np.all(paged["h2d_bytes"] >= 8 * RING + 4)
        tot = svc.profiler.transfers
        assert tot["h2d"][0] >= int(rows["h2d"].sum())
        assert tot["d2h"][0] >= int(rows["d2h"].sum())
        svc.close()

    def test_new_admission_size_compiles_in_write(self, monkeypatch,
                                                  cold_compiles):
        sizes = []
        apply = state_mod._admit_apply

        def spy(state, buf):
            sizes.append(buf.shape[0])        # one length per padded size
            return apply(state, buf)

        monkeypatch.setattr(state_mod, "_admit_apply", spy)
        svc = service()
        svc.run(TICKS)
        prof = svc.profiler
        assert len(sizes) >= 2
        # each new padded size compiles one program inside the write,
        # the admission (_admit_apply): its operands arrive as one packed
        # uint32 buffer, cast on the host
        assert prof.compiles["admit_drain/write"] == len(set(sizes))
        assert set(prof.compiles) == {"admit_drain/write",
                                      "chunk_compile_execute"}
        rows = prof.rounds()
        assert int(rows["compiles"].sum()) == sum(prof.compiles.values())
        # the round that first wrote each padded size compiled
        first = {}
        for i, n in enumerate(sizes):
            first.setdefault(n, i)
        admits = np.flatnonzero(rows["admitted"] > 0)
        for i in first.values():
            assert rows["compiles"][admits[i]] >= 1
        svc.close()

    def test_publish_and_checkpoint_state(self):
        svc = service()
        svc.run(4)
        prof = svc.profiler
        reg = MetricsRegistry()
        prof.publish(reg)
        xfer = reg.counter("flaas_transfers_total", "", ("direction",))
        xb = reg.counter("flaas_transfer_bytes_total", "", ("direction",))
        assert xfer.value(("h2d",)) == prof.transfers["h2d"][0] > 0
        assert xb.value(("d2h",)) == prof.transfers["d2h"][1] > 0
        packed = reg.counter("flaas_packed_outputs_total", "")
        assert packed.value() == prof.packed_outputs > 0
        packed_in = reg.counter("flaas_packed_inputs_total", "")
        assert packed_in.value() == prof.packed_inputs > 0
        clone = PhaseProfiler()
        clone.load_state_dict(prof.state_dict())
        assert clone.transfers == prof.transfers
        assert clone.compiles == prof.compiles
        assert clone.packed_outputs == prof.packed_outputs
        assert clone.packed_inputs == prof.packed_inputs
        svc.close()

    def test_annotations_carry_ticks_and_transfers(self, monkeypatch):
        seen = []

        class Recorder:
            def __init__(self, name, **kw):
                seen.append((name, kw))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        svc = service(profile_annotations=True)
        svc.run(2)
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
        svc.run_chunk(1)
        names = [n for n, _ in seen]
        assert "flaas/host_sync/device_wait" in names
        assert "flaas/admit_drain" in names
        assert not any(n == "flaas/round" for n in names)
        assert all(n.startswith("flaas/") for n in names)   # spans only
        assert {kw["tick"] for _, kw in seen} == {2}
        # the round's last span carries its transfer counts, all of them
        row = svc.profiler.rounds()[-1]
        counted = [kw for n, kw in seen if "h2d" in kw]
        assert names[-1] == "flaas/telemetry_fold"
        assert counted == [seen[-1][1]]
        assert counted[0]["h2d"] == row["h2d"] > 0
        assert counted[0]["d2h"] == row["d2h"] > 0
        svc.close()


# ========================================================= device scopes
SCOPES = {"dpbalance": ("schedule", "sp1", "sp2", "ledger", "round_metrics"),
          "dpf": ("schedule", "grant_scan", "ledger", "round_metrics")}


def _op_names(hlo_text):
    import re
    return {part for m in re.findall(r'op_name="([^"]*)"', hlo_text)
            for part in m.split("/")}


@pytest.mark.parametrize("scheduler", sorted(SCOPES))
def test_scopes_in_compiled_chunk(scheduler):
    svc = service(scheduler)
    svc.run(RING // 8 + 1)                     # past the wrap: paged
    _, mode, ops, step = svc._plan_chunk(int(svc.state.tick), 1)
    assert mode == "paged"
    names = _op_names(step.lower(svc.state, ops).compile().as_text())
    assert set(SCOPES[scheduler]) <= names
    other = set().union(*SCOPES.values()) - set(SCOPES[scheduler])
    assert not (other & names)
    svc.close()


def test_admit_scope_in_compiled_admission():
    svc = service()
    M, N = 3, 6
    z = np.zeros((M, N))
    idx = np.zeros(4, np.int32)
    buf = state_mod.pack_admission(
        svc.state, z.astype(bool), z, z, z.astype(np.int32), idx, idx, idx,
        idx.astype(np.float32), weight=np.ones(M, np.float32))
    low = state_mod._admit_apply.lower(svc.state, buf)
    assert "admit" in _op_names(low.compile().as_text())


def test_chunk_program_identical_with_annotations():
    texts = []
    for annotate in (False, True):
        svc = service(profile_annotations=annotate)
        svc.run(RING // 8 + 1)
        _, _, ops, step = svc._plan_chunk(int(svc.state.tick), 1)
        texts.append(step.lower(svc.state, ops).compile().as_text())
        svc.close()
    assert texts[0] == texts[1]
