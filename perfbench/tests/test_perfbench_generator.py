"""The benchmark's arrival generator reproduces its recorded draws."""
import json
from pathlib import Path

import numpy as np

from perfbench.harness.generator import Arrivals

FIXTURE = Path(__file__).parent / "fixtures" / "arrivals_tiny.json"
TINY = {"n_devices": 6, "blocks_per_device": 2, "pipelines_per_analyst": 3,
        "mice_frac": 0.75, "mice_eps": [0.005, 0.015],
        "elephant_eps": [0.095, 0.105], "budget_range": [1.0, 1.5],
        "p_ten_blocks": 0.25, "p_subset_devices": 0.5, "subset_frac": 0.2,
        "arrival_rate": 1.5}
SEED = 2 ** 31 + 12345          # seeds are wider than 32 signed bits
N_TICKS = 8


def test_generator_matches_recorded_fixture():
    rec = json.loads(FIXTURE.read_text())
    assert rec["deployment"] == TINY and rec["seed"] == SEED
    a = Arrivals(TINY, SEED)
    ticks = a.ticks(N_TICKS)
    np.testing.assert_array_equal(a.device_budget, rec["device_budget"])
    assert [len(t) for t in ticks] == [len(t) for t in rec["ticks"]]
    for got_t, want_t in zip(ticks, rec["ticks"]):
        for got, want in zip(got_t, want_t):
            assert (got.analyst, got.tick) == (want["analyst"], want["tick"])
            for b, wb in zip(got.bids, want["bids"]):
                np.testing.assert_array_equal(b, wb)
            for e, we in zip(got.eps, want["eps"]):
                np.testing.assert_array_equal(e, np.float32(we))
            np.testing.assert_array_equal(got.loss, np.float32(want["loss"]))


def test_longer_prefix_keeps_the_shorter_one():
    short = Arrivals(TINY, SEED).ticks(3)
    a = Arrivals(TINY, SEED)
    a.ticks(2)
    long = a.ticks(6)
    for s, l in zip(short, long[:3]):
        assert [b.analyst for b in s] == [b.analyst for b in l]
        for bs, bl in zip(s, l):
            for x, y in zip(bs.eps, bl.eps):
                np.testing.assert_array_equal(x, y)


def test_demand_shape_follows_the_deployment():
    a = Arrivals(TINY, 7)
    for t, tick in enumerate(a.ticks(N_TICKS)):
        for b in tick:
            assert len(b.bids) == TINY["pipelines_per_analyst"]
            for bids, eps in zip(b.bids, b.eps):
                assert bids.max() < (t + 1) * 12          # minted by tick t
                lo = eps.min() >= 0.005 and eps.max() <= 0.105
                assert lo and bids.size == eps.size
