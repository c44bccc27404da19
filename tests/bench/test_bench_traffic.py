"""The one traffic generator draws everything from the seed: the same seed
sends the same work, other seeds the same load in another order."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchkit  # noqa: E402

from bench.traffic import Traffic, load_mix  # noqa: E402

SEEDS = [0, 7, benchkit.BIG_SEED, 2**40 + 3]


@pytest.mark.parametrize("seed", SEEDS)
def test_closed_loop_seeds_are_fixed_and_distinct(seed):
    mix = load_mix(benchkit.ROOT, "traces")
    a, b = Traffic(mix, seed), Traffic(mix, seed)
    calls = [a.trace_seeds(i) for i in range(-1, 4)]
    assert all(np.array_equal(x, b.trace_seeds(i))
               for x, i in zip(calls, range(-1, 4)))
    flat = np.concatenate(calls)
    assert flat.dtype == np.uint32 and len(set(flat.tolist())) == len(flat)
    rows = a.check_rows(2)
    assert len(set(rows.tolist())) == mix["check_rows_per_call"]
    assert rows.min() >= 0 and rows.max() < mix["batch"]
    assert np.array_equal(rows, b.check_rows(2))


def test_other_seeds_send_other_traces():
    mix = load_mix(benchkit.ROOT, "traces")
    assert not np.array_equal(Traffic(mix, 1).trace_seeds(0),
                              Traffic(mix, 2).trace_seeds(0))


def test_explore_keeps_the_first_call_and_a_share_of_the_rest():
    k = load_mix(benchkit.ROOT, "explore")["check_calls"]
    counts = np.zeros(40)
    for seed in range(400):
        t = Traffic({}, seed)
        slots = {}
        for i in range(40):
            slot = t.reservoir_slot(i, k)
            assert slot is None or 0 <= slot < k
            if slot is not None:
                slots[slot] = i
        assert len(slots) == k
        counts[list(slots.values())] += 1
        again = Traffic({}, seed)
        assert [again.reservoir_slot(i, k) for i in range(40)] == \
            [t.reservoir_slot(i, k) for i in range(40)]
    # every call of the window is as likely to be checked as any other
    assert counts.sum() == 400 * k
    quarters = counts.reshape(4, 10).sum(axis=1)
    assert np.all(np.abs(quarters - 200 * k / 2) < 0.2 * 200 * k / 2)


def test_open_loop_sends_the_same_gaps_in_another_order():
    mix = dict(load_mix(benchkit.ROOT, "serve_poisson"), rate_per_s=50)
    runs = [Traffic(mix, s).arrivals(20.0) for s in SEEDS]
    for offsets, seeds in runs:
        assert len(offsets) == 1000 and np.isclose(offsets[-1], 20.0)
        assert np.all(np.diff(offsets) > 0) and offsets[0] > 0
        assert len(set(seeds.tolist())) == len(seeds)
    gaps = [np.diff(o, prepend=0.0) for o, _ in runs]
    assert all(np.allclose(np.sort(g), np.sort(gaps[0])) for g in gaps)
    assert not np.allclose(gaps[0], gaps[1])
    # exponential gaps: the coefficient of variation is near 1
    g = gaps[0]
    assert 0.9 < g.std() / g.mean() < 1.1
    t = Traffic(mix, 5)
    picked = t.check_requests(1000)
    assert len(set(picked.tolist())) == mix["check_requests"]
    assert np.array_equal(picked, Traffic(mix, 5).check_requests(1000))


def test_unknown_arrival_is_an_error():
    with pytest.raises(FileNotFoundError):
        Traffic({"arrival": "bursty", "rate_per_s": 1}, 0).arrivals(1.0)
