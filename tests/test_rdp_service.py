"""Rényi-DP block ledgers on the live service (``ServiceConfig.rdp``).

The service's RDP path (DPF and FCFS over per-order capacity curves) is
compared with the plain reference :mod:`repro.privacy.rdp_dpf_reference`
on seeded random curves, through the wrap-free, paged and carry chunk
bodies and several ring wraps; the order-axis grant scan with a plain
numpy loop; the one-order identity ledger with the scalar service, bit
for bit; and the schedulers with no RDP form must refuse it.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SchedulerConfig
from repro.core.baselines import dpk_round
from repro.core.blockaxis import grant_fits_scan
from repro.core.demand import RoundInputs
from repro.core.scheduler import schedule_round
from repro.obs.audit import verify_ledger
from repro.obs.registry import absorb_summary, MetricsRegistry
from repro.privacy.rdp import RdpSpec, capacity_curve, laplace_rdp
from repro.privacy.rdp_dpf_reference import RdpDpfReference
from repro.service import FlaasService, ServiceConfig, make_trace

SIZE = dict(n_devices=4, pipelines_per_analyst=6)
RING = 80                       # 8 blocks a tick: a wrap every 10 ticks
TICKS = 28                      # 2.8 passes of the ring
# eps_G = 1, delta_G = 0.5: 16 usable orders of DEFAULT_ORDERS, from
# capacity 0.076 at order 1.75 to 0.989 at order 64
SPEC = RdpSpec(eps_g=1.0, delta_g=0.5)


def random_curves(rng, orders, n):
    """Half Gaussian-shaped (``u * a / 8``, cheap at low orders), half
    flat (``u``, cheap at high orders): pipelines bind at different orders
    of the same block."""
    u = rng.uniform(0.5, 2.0, n)
    gauss = rng.random(n) < 0.5
    return [(ui * orders / 8.0 if g else np.full_like(orders, ui))
            .astype(np.float32) for ui, g in zip(u, gauss)]


def identity_curves(rng, orders, n):
    return [np.ones(len(orders), np.float32) for _ in range(n)]


class CurveTrace:
    """A service trace whose submissions carry seeded demand curves
    (``draw(rng, orders, n_pipelines)``, the rng seeded by the trace seed
    and the analyst, so a restored trace draws the same), recording every
    tick's submissions for the reference."""

    def __init__(self, trace, orders, seed, draw=random_curves):
        self._trace = trace
        self.orders = np.asarray(orders, np.float32)
        self.curve_seed = seed
        self.draw = draw
        for k in ("sim", "pattern", "seed", "tiers", "device_budget",
                  "blocks_per_device", "blocks_per_tick"):
            setattr(self, k, getattr(trace, k))
        self.events = []

    def step(self, tick):
        subs = self._trace.step(tick)
        for sub in subs:
            rng = np.random.default_rng((self.curve_seed, sub.analyst))
            sub.curves = self.draw(rng, self.orders, len(sub.bids))
        self.events.append(subs)
        return subs

    def arrival_seconds(self, tick):
        return self._trace.arrival_seconds(tick)

    def state_dict(self):
        return self._trace.state_dict()

    def load_state_dict(self, d):
        self._trace.load_state_dict(d)


def rdp_config(scheduler="dpf", spec=SPEC, **kw):
    base = dict(scheduler=scheduler, sched=SchedulerConfig(beta=2.2),
                analyst_slots=3, pipeline_slots=6, block_slots=RING,
                chunk_ticks=4, admit_batch=8, max_pending=64, rdp=spec)
    base.update(kw)
    return ServiceConfig(**base)


def recording(base):
    """``base`` keeping every chunk's selections and its ledger after."""

    class Recording(base):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.selected, self.capacity = [], []

        def _compiled_step(self, n_ticks, mode):
            step = super()._compiled_step(n_ticks, mode)

            def run(state, ops):
                final, ys = step(state, ops)
                self.selected.append(np.asarray(ys["selected"]))
                self.capacity.append(np.asarray(final[-1]))
                return final, ys
            return run

    return Recording


Recording = recording(FlaasService)


def run_service(scheduler, paged=True, seed=3, ticks=TICKS, spec=SPEC):
    trace = CurveTrace(make_trace("paper_default", "poisson", seed=seed,
                                  **SIZE), spec.orders, seed)
    svc = Recording(rdp_config(scheduler, spec, paged=paged), trace)
    svc.run(ticks)
    return svc, trace


# ------------------------------------------------------ the grant scan
def scan_loop(dems, curves, act, rem, norm, feas):
    """The order-axis grant-if-fits sweep as a plain loop."""
    rem = np.array(rem, np.float64)
    taken = []
    for d, c, a in zip(dems, curves, act):
        g = d[None, :] * c[:, None] / norm
        ok = bool(a) and all(d[k] == 0 or (g[:, k] <= rem[:, k] + feas).any()
                             for k in range(len(d)))
        if ok:
            rem = rem - g
        taken.append(ok)
    return rem, np.asarray(taken)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_order_scan_matches_a_plain_loop(seed):
    rng = np.random.default_rng(seed)
    V, A, K = 24, 5, 12
    norm = rng.uniform(0.5, 2.0, (A, K)).astype(np.float32)
    dems = (rng.uniform(0.05, 0.4, (V, K)) *
            (rng.random((V, K)) < 0.4)).astype(np.float32)
    curves = rng.uniform(0.1, 3.0, (V, A)).astype(np.float32)
    act = rng.random(V) < 0.8
    rem = (norm * rng.uniform(0.2, 1.0, (A, K))).astype(np.float32) / norm
    # block 0 is exhausted at every order; only pipelines that demand it
    # may be refused for it
    rem[:, 0] = -1.0
    dems[: V // 2, 0] = 0.0
    want_rem, want = scan_loop(dems, curves, act, rem, norm, 1e-6)
    got_rem, got = grant_fits_scan(
        jnp.asarray(dems), jnp.asarray(act), jnp.asarray(rem), 1e-6,
        curves=jnp.asarray(curves), norm=jnp.asarray(norm))
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_allclose(np.asarray(got_rem), want_rem, atol=1e-5)
    assert want[: V // 2][act[: V // 2]].any()    # undemanded: still granted
    assert not want[V // 2:][dems[V // 2:, 0] > 0].any()


# ------------------------------------------- the service vs the reference
@pytest.mark.parametrize("paged", [True, False], ids=["paged", "carry"])
@pytest.mark.parametrize("scheduler", ["dpf", "fcfs"])
def test_service_matches_reference(scheduler, paged):
    svc, trace = run_service(scheduler, paged)
    modes = svc.telemetry.mode_ticks
    assert modes["wrapfree"] > 0
    assert modes["paged" if paged else "carry"] > 0
    ref = RdpDpfReference(
        analyst_slots=3, pipeline_slots=6, block_slots=RING,
        blocks_per_tick=trace.blocks_per_tick, admit_batch=8,
        max_pending=64, capacity=SPEC.capacity, scheduler=scheduler,
        chunk_ticks=4, events=trace.events)
    sel = np.concatenate(svc.selected)
    caps = []
    for t in range(TICKS):
        own, _, _, invalid, cap = ref.step()
        np.testing.assert_array_equal(own, sel[t], err_msg=f"tick {t}")
        assert invalid == 0
        caps.append(cap)
    for c, (got) in enumerate(svc.capacity):
        np.testing.assert_allclose(got, caps[4 * c + 3], atol=1e-5)
    assert sel.sum() > 20
    # some order went past its capacity on a block that still lives
    final = np.asarray(svc.state.block_capacity)
    assert (final < 0).any() and ((final >= 0).any(axis=0)).all()
    np.testing.assert_array_equal(svc.table.row_owner, ref.owner)


def test_summary_and_registry_carry_the_order_counters():
    svc, _ = run_service("dpf")
    rdp = svc.summary()["rdp"]
    assert rdp["ticks"] == TICKS and rdp["exhausted_blocks"] == 0
    assert 1.0 <= rdp["orders_alive"] < len(SPEC.orders)
    reg = MetricsRegistry()
    absorb_summary(reg, svc.summary())
    names = {m.name for m in reg.metrics()}
    assert {"flaas_rdp_exhausted_blocks", "flaas_rdp_orders_alive"} <= names


def test_one_order_identity_ledger_is_the_scalar_service():
    """A = 1, capacity 1 at that order (delta_G = 1), every curve 1: the
    RDP program is the scalar one, bit for bit."""
    spec = RdpSpec(orders=(2.0,), eps_g=1.0, delta_g=1.0)
    assert spec.capacity.tolist() == [1.0]
    size = dict(SIZE, budget_range=(1.0, 1.0))

    def ys_of(rdp):
        trace = make_trace("paper_default", "poisson", seed=5, **size)
        if rdp is not None:
            trace = CurveTrace(trace, spec.orders, 0, identity_curves)
        svc = Recording(rdp_config("dpf", rdp), trace)
        out = []
        for _ in range(TICKS // 4):
            out.append(svc.run_chunk())
        return svc, out

    scalar, ys_s = ys_of(None)
    rdp, ys_r = ys_of(spec)
    for a, b in zip(scalar.selected, rdp.selected):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(scalar.capacity, rdp.capacity):
        np.testing.assert_array_equal(a, b[0])
    for a, b in zip(ys_s, ys_r):
        for k in ("round_efficiency", "round_fairness", "round_jain",
                  "n_allocated", "leftover", "overdraw"):
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    assert sum(int(s.sum()) for s in scalar.selected) > 20


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("scheduler", ["dpbalance", "dpk"])
def test_schedulers_without_an_rdp_form_refuse(scheduler):
    trace = make_trace("paper_default", "poisson", seed=1, **SIZE)
    with pytest.raises(ValueError, match="no Rényi-DP form"):
        FlaasService(rdp_config(scheduler), trace)
    M, N, K, A = 2, 3, 8, 4
    rnd = RoundInputs(
        demand=jnp.ones((M, N, K)), active=jnp.ones((M, N), bool),
        arrival=jnp.zeros((M, N)), loss=jnp.ones((M, N)),
        capacity=jnp.ones((A, K)), budget_total=jnp.ones((A, K)),
        now=jnp.zeros(()), curve=jnp.ones((M, N, A)))
    round_fn = schedule_round if scheduler == "dpbalance" else dpk_round
    with pytest.raises(ValueError, match="no Rényi-DP form"):
        round_fn(rnd, SchedulerConfig())


def test_submission_without_curves_is_refused():
    trace = make_trace("paper_default", "poisson", seed=1, **SIZE)
    svc = FlaasService(rdp_config("dpf"), trace)
    with pytest.raises(ValueError, match="curves"):
        svc.run_chunk()


def test_spec_keeps_only_usable_orders():
    spec = RdpSpec(eps_g=10.0, delta_g=1e-7)
    assert spec.orders[0] == 3.0 and len(spec.orders) == 13
    assert (spec.capacity > 0).all()
    np.testing.assert_allclose(spec.capacity[[0, 2, -1]],
                               [1.9409, 5.9705, 9.7442], atol=1e-3)
    np.testing.assert_allclose(capacity_curve([2.0], 1.0, 1.0), [1.0])
    with pytest.raises(ValueError, match="no usable RDP order"):
        RdpSpec(orders=(1.5, 2.0), eps_g=1.0, delta_g=1e-9)


def test_laplace_curve_is_finite_at_small_scale():
    a = np.asarray(RdpSpec().orders, np.float64)
    for b in (0.5, 2.0, 10.0):        # Mironov '17, Prop. 6, in float64
        want = np.log(a / (2 * a - 1) * np.exp((a - 1) / b)
                      + (a - 1) / (2 * a - 1) * np.exp(-a / b)) / (a - 1)
        np.testing.assert_allclose(np.asarray(laplace_rdp(b, a)), want,
                                   rtol=1e-4)
    tiny = np.asarray(laplace_rdp(0.01, a.astype(np.float32)))
    # the plain exponentials overflow float32 here; as b -> 0 the curve
    # tends to 1/b (pure eps-DP) from below
    assert np.isfinite(tiny).all()
    assert (tiny <= 100.0 + 1e-3).all() and tiny[-1] > 90.0


# ---------------------------------------------------------------- audit
def test_audit_ledger_verifies_per_order(tmp_path):
    path = tmp_path / "audit.jsonl"
    trace = CurveTrace(make_trace("paper_default", "poisson", seed=3,
                                  **SIZE), SPEC.orders, 3)
    with FlaasService(rdp_config("dpf", audit_path=str(path)), trace) as s:
        s.run(TICKS)
    report = verify_ledger(str(path))
    assert report["ok"], report["violations"]
    assert report["grants"] > 20
    assert len(report["total_epsilon"]) == len(SPEC.orders)
    # a grant that overdraws every order of its first block is caught
    import json
    from repro.obs.audit import AuditWriter
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    meta = lines[0]["meta"]
    bad = tmp_path / "bad.jsonl"
    w = AuditWriter(str(bad), meta)
    g = next(r for r in lines if r["kind"] == "grant")
    w.grant(tick=g["tick"], analyst=0, pipeline=0, tier="default", x=1.0,
            bids=g["bids"][:1], eps=[1.0], curve=[2.0] * len(SPEC.orders))
    w.grant(tick=g["tick"], analyst=0, pipeline=1, tier="default", x=1.0,
            bids=g["bids"][:1], eps=[1.0], curve=[1.0])
    w.close()
    report = verify_ledger(str(bad))
    assert not report["ok"]
    assert any("every order" in v for v in report["violations"])
    assert any("curve of shape" in v for v in report["violations"])


# --------------------------------------------------------- four shards
_ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_four_shards_match_one():
    """ShardedFlaasService on the RDP ledger, its [A, B] capacity striped
    on B: 4 shards against 1 on the 8-device host-platform mesh, equal
    selections and capacities within 1e-5 through ring wraps."""
    script = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {os.path.join(_ROOT, "tests")!r})
        import numpy as np
        from test_rdp_service import (CurveTrace, SIZE, SPEC, TICKS,
                                      rdp_config, recording)
        from repro.service import make_trace
        from repro.shard import ShardedFlaasService, remap_ring

        def run(n):
            tr = CurveTrace(make_trace("paper_default", "poisson", seed=4,
                                       **SIZE), SPEC.orders, 4)
            svc = recording(ShardedFlaasService)(rdp_config("dpf"), tr,
                                                 n_shards=n)
            svc.run(TICKS)
            return svc, np.concatenate(svc.selected)

        one, s1 = run(1)
        four, s4 = run(4)
        np.testing.assert_array_equal(s1, s4)
        assert s1.sum() > 20
        c1 = np.asarray(one.state.block_capacity)
        c4 = np.asarray(four.state.block_capacity)
        idx = remap_ring(4, 1, c1.shape[-1])
        gap = float(np.max(np.abs(c1 - c4[:, idx])))
        assert gap <= 1e-5, gap
        spec = four.state.block_capacity.sharding.spec
        assert tuple(spec) == (None, "shard"), spec
        assert four.summary()["rdp"] == one.summary()["rdp"]
        print("OK", gap)
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
