"""Durable service-plane checkpoint/restore.

Acceptance (see docs/service.md "Durability"):

* **Bitwise resume** — for all four schedulers, in paged AND carry
  residency modes, a service checkpointed at a chunk boundary and restored
  into a fresh process continues bit-for-bit: identical final device state
  and identical telemetry summary (modulo wall-clock keys) versus the
  uninterrupted run, through >= 2 ring wraps.
* **Elastic hand-off** — a checkpoint taken at shard count S restores onto
  an S'-shard mesh (striped-ring remap of the block axis) and the
  continued run matches the unsharded oracle to 1e-5.
* The crash-recovery seams this exposed: oversize submissions must be
  rejected at ``offer()`` (not crash ``drain()``), head-of-line deferrals
  are counted, and the host state_dicts round-trip exactly.
"""
import json
import pickle

import jax
import numpy as np
import pytest

from repro.core import SCHEDULER_NAMES, SchedulerConfig
from repro.checkpoint import CheckpointManager
from repro.service import (AdmissionQueue, FlaasService, ServiceConfig,
                           SlotTable, collect_service_metrics, make_trace,
                           summary_fingerprint)
from repro.service.traces import Submission
from repro.shard import (ShardedFlaasService, remap_ring, ring_slots,
                         shard_mesh)

N_DEV = len(jax.devices())
multi_device = pytest.mark.skipif(
    N_DEV < 4, reason="needs >= 4 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

# 4 devices x 2 blocks/tick = 8 blocks/tick; the 80-slot ring covers 10
# ticks, so 24 ticks wrap it twice (retirement in both run halves).
SIZE = dict(n_devices=4, pipelines_per_analyst=6)
RING = 80
HALF, TOTAL = 12, 24


def small_trace(seed=2):
    return make_trace("paper_default", "poisson", seed=seed, **SIZE)


def make_service(scheduler="dpbalance", *, paged=True, n_shards=None,
                 seed=2):
    cfg = ServiceConfig(scheduler=scheduler, sched=SchedulerConfig(beta=2.2),
                        analyst_slots=3, pipeline_slots=6, block_slots=RING,
                        chunk_ticks=4, admit_batch=8, max_pending=64,
                        paged=paged)
    if n_shards is None:
        return FlaasService(cfg, small_trace(seed))
    return ShardedFlaasService(cfg, small_trace(seed), n_shards=n_shards)


def fingerprint(service):
    """Wall-clock-stripped summary as a canonical string (NaN-safe)."""
    return json.dumps(summary_fingerprint(service.summary()), sort_keys=True)


def assert_states_equal(a, b):
    la, lb = jax.tree.leaves(a.state), jax.tree.leaves(b.state)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestRemapRing:
    """The striped-ring permutation behind elastic shard hand-off."""

    @pytest.mark.parametrize("s_from", [1, 2, 4])
    @pytest.mark.parametrize("s_to", [1, 2, 4])
    def test_moves_every_bid_class_home(self, s_from, s_to):
        """idx gathers each block's old slot into its new-layout slot —
        for every bid in several ring generations."""
        idx = remap_ring(s_from, s_to, RING)
        assert sorted(idx.tolist()) == list(range(RING))   # permutation
        for bid in range(3 * RING):
            assert idx[ring_slots(bid, s_to, RING)] == \
                ring_slots(bid, s_from, RING)

    def test_identity_when_layout_unchanged(self):
        for s in (1, 2, 4, 8):
            np.testing.assert_array_equal(remap_ring(s, s, RING),
                                          np.arange(RING))

    def test_rejects_indivisible(self):
        with pytest.raises(ValueError):
            remap_ring(1, 3, RING)
        with pytest.raises(ValueError):
            remap_ring(3, 1, RING)
        with pytest.raises(ValueError):
            remap_ring(0, 1, RING)


class TestBitwiseResume:
    """Checkpoint at a chunk boundary, restore into a fresh service,
    continue: bit-identical to never having crashed."""

    def _roundtrip(self, tmp_path, scheduler, paged):
        ref = make_service(scheduler, paged=paged)
        ref.run(TOTAL)

        crashed = make_service(scheduler, paged=paged)
        crashed.run(HALF)
        mgr = CheckpointManager(str(tmp_path))
        step = crashed.save_checkpoint(mgr)
        mgr.wait()
        assert step == HALF

        resumed = make_service(scheduler, paged=paged)
        assert resumed.load_checkpoint(mgr) == HALF
        resumed.run(TOTAL - HALF)
        return ref, resumed

    @pytest.mark.parametrize("scheduler", SCHEDULER_NAMES)
    def test_paged_mode(self, tmp_path, scheduler):
        ref, resumed = self._roundtrip(tmp_path, scheduler, paged=True)
        assert_states_equal(ref, resumed)
        assert fingerprint(ref) == fingerprint(resumed)

    @pytest.mark.parametrize("scheduler", SCHEDULER_NAMES)
    def test_carry_mode(self, tmp_path, scheduler):
        ref, resumed = self._roundtrip(tmp_path, scheduler, paged=False)
        assert_states_equal(ref, resumed)
        assert fingerprint(ref) == fingerprint(resumed)

    def test_resume_crosses_ring_wraps(self):
        """The geometry actually exercises retirement in both halves: the
        ring wraps before the checkpoint and again after the restore."""
        blocks_per_tick = small_trace().blocks_per_tick
        assert HALF * blocks_per_tick > RING            # wrap pre-crash
        assert TOTAL * blocks_per_tick > 2 * RING       # wrap post-restore

    def test_restore_requires_host_payload(self, tmp_path):
        svc = make_service()
        svc.run(4)
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(4, svc.state)                  # arrays only, no host state
        fresh = make_service()
        with pytest.raises(ValueError, match="no service host state"):
            fresh.load_checkpoint(mgr)

    def test_restore_rejects_geometry_mismatch(self, tmp_path):
        svc = make_service()
        svc.run(4)
        mgr = CheckpointManager(str(tmp_path))
        svc.save_checkpoint(mgr)
        mgr.wait()
        other = ServiceConfig(analyst_slots=4, pipeline_slots=6,
                              block_slots=RING, chunk_ticks=4)
        fresh = FlaasService(other, small_trace())
        with pytest.raises(ValueError, match="geometry"):
            fresh.load_checkpoint(mgr)

    @pytest.mark.parametrize("case", ["rdp_paged", "rdp_carry",
                                      "v4_is_scalar"])
    def test_ledger_kinds(self, tmp_path, case, monkeypatch):
        """A Rényi-DP service (its [A, B] ledger and [M, N, A] curves)
        round trips bitwise; a checkpoint from before version 5 restores
        as the scalar ledger it was, and an RDP service refuses it."""
        from test_rdp_service import CurveTrace, SPEC, rdp_config

        def rdp_service(paged):
            cfg = rdp_config("dpf", analyst_slots=3, pipeline_slots=6,
                             block_slots=RING, chunk_ticks=4, paged=paged)
            return FlaasService(cfg, CurveTrace(small_trace(), SPEC.orders,
                                                7))

        if case == "v4_is_scalar":
            real = FlaasService.checkpoint_host_state

            def v4(self):
                host = real(self)
                host.pop("rdp")
                return dict(host, version=4)
            monkeypatch.setattr(FlaasService, "checkpoint_host_state", v4)
            svc = make_service("dpf")
            svc.run(HALF)
            mgr = CheckpointManager(str(tmp_path))
            svc.save_checkpoint(mgr)
            mgr.wait()
            resumed = make_service("dpf")
            assert resumed.load_checkpoint(mgr) == HALF
            assert_states_equal(svc, resumed)
            with pytest.raises(ValueError, match="Rényi-DP"):
                rdp_service(True).load_checkpoint(mgr)
            return
        paged = case == "rdp_paged"
        ref = rdp_service(paged)
        ref.run(TOTAL)
        crashed = rdp_service(paged)
        crashed.run(HALF)
        mgr = CheckpointManager(str(tmp_path))
        crashed.save_checkpoint(mgr)
        mgr.wait()
        with pytest.raises(ValueError, match="Rényi-DP"):
            make_service("dpf").load_checkpoint(mgr)
        resumed = rdp_service(paged)
        assert resumed.load_checkpoint(mgr) == HALF
        resumed.run(TOTAL - HALF)
        assert resumed.state.block_capacity.shape == (len(SPEC.orders), RING)
        assert_states_equal(ref, resumed)
        assert fingerprint(ref) == fingerprint(resumed)
        assert ref.summary()["rdp"]["ticks"] == TOTAL

    def test_missing_checkpoint_raises(self, tmp_path):
        fresh = make_service()
        with pytest.raises(ValueError, match="no checkpoint"):
            fresh.load_checkpoint(CheckpointManager(str(tmp_path)))


@multi_device
class TestElasticRemap:
    """Restore a checkpoint onto a different shard count: the block axis is
    permuted between striped-ring layouts and the continued run matches the
    unsharded oracle."""

    TOL = 1e-5   # float reassociation in psum partial sums

    def _elastic_gap(self, tmp_path, s_from, s_to):
        oracle = make_service()
        ref = collect_service_metrics(oracle, TOTAL)

        first = make_service(n_shards=s_from)
        m1 = collect_service_metrics(first, HALF)
        mgr = CheckpointManager(str(tmp_path))
        first.save_checkpoint(mgr)
        mgr.wait()

        second = make_service(n_shards=s_to)
        second.load_checkpoint(mgr)
        m2 = collect_service_metrics(second, TOTAL - HALF)

        worst = 0.0
        for k in ref:
            a = np.asarray(ref[k], np.float64)
            b = np.concatenate([np.asarray(m1[k], np.float64),
                                np.asarray(m2[k], np.float64)])
            worst = max(worst, float(np.max(np.abs(a - b)) /
                                     max(1.0, np.max(np.abs(a)))))
        return worst

    def test_scale_out_1_to_4(self, tmp_path):
        assert self._elastic_gap(tmp_path, 1, 4) <= self.TOL

    def test_scale_in_4_to_1(self, tmp_path):
        assert self._elastic_gap(tmp_path, 4, 1) <= self.TOL

    def test_same_shard_count_is_bitwise(self, tmp_path):
        """S -> S restore goes through the identity permutation and stays
        exact (the sharded plane's own crash-recovery path)."""
        ref = make_service(n_shards=4)
        ref.run(TOTAL)
        crashed = make_service(n_shards=4)
        crashed.run(HALF)
        mgr = CheckpointManager(str(tmp_path))
        crashed.save_checkpoint(mgr)
        mgr.wait()
        resumed = make_service(n_shards=4)
        assert resumed.load_checkpoint(mgr) == HALF
        resumed.run(TOTAL - HALF)
        assert_states_equal(ref, resumed)
        assert fingerprint(ref) == fingerprint(resumed)

    def test_checkpoint_records_layout(self, tmp_path):
        svc = make_service(n_shards=4)
        svc.run(4)
        host = svc.checkpoint_host_state()
        assert host["layout_shards"] == 4
        assert shard_mesh(4) is not None


def _submission(analyst, n_pipelines, tick=0):
    bids = [np.arange(4, dtype=np.int64) for _ in range(n_pipelines)]
    eps = [np.full(4, 0.1, np.float32) for _ in range(n_pipelines)]
    return Submission(analyst=analyst, submit_tick=tick, bids=bids, eps=eps,
                      loss=np.full(n_pipelines, 0.8, np.float32))


class TestAdmissionSeams:
    """The two crash-recovery seams the durability work exposed: oversize
    submissions used to IndexError the server loop out of ``drain()``, and
    head-of-line deferrals were invisible in telemetry."""

    def test_oversize_submission_rejected_at_offer(self):
        """A submission with more pipelines than a row can ever hold is
        rejected up front — deferring it would head-of-line block the
        FIFO forever; admitting it used to crash commit() with an
        IndexError."""
        table = SlotTable(2, 4)
        q = AdmissionQueue(max_pending=8, max_pipelines=4)
        assert q.offer([_submission(0, 5)]) == 1
        assert q.stats.rejected == 1
        assert q.stats.rejected_oversize == 1
        assert q.depth == 0
        # drain with nothing queued: no crash, no placements
        assert q.drain(table, 8) == []

    def test_oversize_row_for_defers_instead_of_crashing(self):
        """Regression: row_for(analyst, n_pipes > N) returned
        list(range(n_pipes)) and the commit IndexError'd.  It now reports
        unplaceable, so an unguarded queue defers instead of dying."""
        table = SlotTable(2, 4)
        assert table.row_for(7, 5) is None
        q = AdmissionQueue(max_pending=8)          # no structural guard
        q.offer([_submission(0, 5), _submission(1, 2)])
        placements = q.drain(table, 8)             # must not raise
        assert placements == []                    # head-of-line deferral
        assert q.depth == 2
        assert q.stats.deferred == 1

    def test_deferred_counter_and_rate(self):
        table = SlotTable(1, 4)
        q = AdmissionQueue(max_pending=8, max_pipelines=4)
        q.offer([_submission(0, 3), _submission(1, 3)])
        placed = q.drain(table, 8)
        assert len(placed) == 1                    # second analyst: no row
        assert q.stats.deferred == 1
        q.drain(table, 8)
        assert q.stats.deferred == 2               # counted per boundary
        # invariant the service summary relies on
        assert q.stats.offered == q.stats.admitted + q.stats.rejected + \
            q.depth

    def test_deferral_rate_in_summary(self):
        svc = make_service()
        svc.run(8)
        s = svc.summary()
        assert "deferral_rate" in s
        assert s["deferral_rate"] >= 0.0
        assert s["admission"]["deferred"] == svc.queue.stats.deferred


class TestHostStateDicts:
    """Exact round-trips of every host-side state_dict through pickle —
    the serialization path save_checkpoint actually uses."""

    def test_slot_table_roundtrip(self):
        table = SlotTable(3, 4)
        for analyst, n in ((5, 2), (9, 3), (1, 4)):
            placed = table.row_for(analyst, n)
            table.commit(analyst, placed[0], placed[1], submit_tick=2)
        done = np.zeros((3, 4), bool)
        done[0, 0] = True
        table.release_done(done)
        blob = pickle.dumps(table.state_dict())
        fresh = SlotTable(3, 4)
        fresh.load_state_dict(pickle.loads(blob))
        np.testing.assert_array_equal(fresh.occupied, table.occupied)
        np.testing.assert_array_equal(fresh.row_owner, table.row_owner)
        np.testing.assert_array_equal(fresh.submit_tick, table.submit_tick)
        assert fresh._free_rows == table._free_rows

    def test_slot_table_rejects_wrong_shape(self):
        table = SlotTable(3, 4)
        with pytest.raises(ValueError, match="slot-table checkpoint"):
            SlotTable(2, 4).load_state_dict(table.state_dict())

    def test_queue_roundtrip_preserves_fifo(self):
        q = AdmissionQueue(max_pending=8, max_pipelines=6)
        q.offer([_submission(i, 2, tick=i) for i in range(3)])
        blob = pickle.dumps(q.state_dict())
        fresh = AdmissionQueue(max_pending=8, max_pipelines=6)
        fresh.load_state_dict(pickle.loads(blob))
        assert [s.analyst for s in fresh.pending] == [0, 1, 2]
        assert fresh.stats.snapshot() == q.stats.snapshot()

    def test_trace_cursor_roundtrip_is_bitwise(self):
        a = small_trace(seed=11)
        for t in range(5):
            a.step(t)
        blob = pickle.dumps(a.state_dict())
        b = small_trace(seed=11)
        b.load_state_dict(pickle.loads(blob))
        for t in range(5, 10):
            sa, sb = a.step(t), b.step(t)
            assert len(sa) == len(sb)
            for x, y in zip(sa, sb):
                assert x.analyst == y.analyst
                for ba, bb in zip(x.bids, y.bids):
                    np.testing.assert_array_equal(ba, bb)
                for ea, eb in zip(x.eps, y.eps):
                    np.testing.assert_array_equal(ea, eb)

    def test_trace_rejects_mismatched_identity(self):
        a, b = small_trace(seed=1), small_trace(seed=2)
        with pytest.raises(ValueError, match="does not match"):
            b.load_state_dict(a.state_dict())

    def test_telemetry_rejects_unknown_field(self):
        svc = make_service()
        svc.run(4)
        d = svc.telemetry.state_dict()
        d["not_a_field"] = 1
        with pytest.raises(ValueError, match="unknown telemetry"):
            svc.telemetry.load_state_dict(d)
