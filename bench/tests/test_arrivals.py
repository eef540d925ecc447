import json
import os

import numpy as np
import pytest

from bench import arrivals, spec

POISSON = {"kind": "poisson", "rate_hz": 1500.0, "gap_seed": 0}
BURSTS = {"kind": "bursts", "burst_size": 512, "gap_s": 0.5, "gap_seed": 0}
SEEDS = (0, 7, 2 ** 31 + 12345, 2 ** 40 + 3)


@pytest.mark.parametrize("traffic", [POISSON, BURSTS])
@pytest.mark.parametrize("seed", SEEDS)
def test_a_seed_gives_the_same_schedule(traffic, seed):
    a = arrivals.schedule(traffic, seed, 10.0, 256)
    b = arrivals.schedule(traffic, seed, 10.0, 256)
    np.testing.assert_array_equal(a.offsets, b.offsets)
    np.testing.assert_array_equal(a.pool_index, b.pool_index)


@pytest.mark.parametrize("traffic", [POISSON, BURSTS])
def test_every_seed_gets_the_same_work_in_another_order(traffic):
    runs = [arrivals.schedule(traffic, s, 10.0, 256) for s in SEEDS]
    gaps = [np.sort(np.diff(r.offsets, prepend=0.0)) for r in runs]
    for r, g in zip(runs[1:], gaps[1:]):
        assert len(r.offsets) == len(runs[0].offsets)
        np.testing.assert_allclose(g, gaps[0], rtol=0, atol=1e-12)
    assert not np.array_equal(runs[0].pool_index, runs[1].pool_index)
    if traffic["kind"] == "poisson":
        assert not np.allclose(runs[0].offsets, runs[1].offsets)


def test_schedule_stays_inside_the_window_at_the_stated_rate():
    s = arrivals.schedule(POISSON, 3, 10.0, 256)
    assert s.offsets.min() >= 0 and s.offsets.max() < 10.0
    assert np.all(np.diff(s.offsets) >= 0)
    assert abs(len(s.offsets) / 10.0 - 1500.0) < 1500.0 * 0.05
    assert set(np.unique(s.pool_index)) == set(range(256))


def test_bursts_hold_whole_bursts_at_the_gap():
    s = arrivals.schedule(BURSTS, 3, 10.0, 256)
    assert len(s.offsets) == 20 * 512
    np.testing.assert_array_equal(np.unique(s.offsets), np.arange(20) * 0.5)


def test_copies_match_the_generators_they_were_copied_from():
    from repro.core import scheduler
    np.testing.assert_array_equal(
        arrivals.poisson_arrivals(100.0, 50, seed=4),
        scheduler.poisson_arrivals(100.0, 50, seed=4))
    np.testing.assert_array_equal(
        arrivals.bursty_arrivals(40, 16, 0.2, intra_s=0.01, seed=4),
        scheduler.bursty_arrivals(40, 16, 0.2, intra_s=0.01, seed=4))


def test_every_traffic_file_parses_into_a_schedule():
    folder = os.path.join(spec.BENCH_DIR, "traffic")
    names = [f for f in os.listdir(folder) if f.endswith(".json")]
    assert names
    for name in names:
        with open(os.path.join(folder, name)) as f:
            s = arrivals.schedule(json.load(f), 1, 10.0, 256)
        assert len(s.offsets) > 100, name


def test_unknown_traffic_kind_is_refused():
    with pytest.raises(ValueError):
        arrivals.schedule({"kind": "zipf"}, 1, 10.0, 256)
