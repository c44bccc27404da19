"""The reduction from a profiler trace to busy time, top operations and
idle gaps named by the benchmark's host spans."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchkit  # noqa: E402, F401  (puts the checkout on sys.path)

from bench import tracereduce  # noqa: E402

MS = 1_000_000
RECORDED = Path(__file__).resolve().parent / "data" / "tpu_small.xplane.pb"


def test_busy_gaps_and_ops_of_a_small_trace():
    spans = [("bench.window", 0, 100 * MS),
             ("bench.call", 0, 40 * MS), ("bench.call", 40 * MS, 80 * MS),
             ("bench.sleep", 80 * MS, 100 * MS),
             ("unrelated", 0, 100 * MS)]
    ops = {0: [("gather", 5 * MS, 30 * MS),
               ("fusion", 30 * MS, 35 * MS),
               ("gather", 45 * MS, 75 * MS),
               ("before", -10 * MS, 2 * MS)]}      # clipped to the window
    r = tracereduce.reduce(spans, ops)
    assert r["window_s"] == pytest.approx(0.1)
    # union: [0,2] + [5,35] + [45,75] = 62 ms
    assert r["busy_s"] == pytest.approx(0.062)
    assert r["device_ops"][0] == ["gather", pytest.approx(0.055)]
    assert [name for name, _ in r["device_ops"]] == ["gather", "fusion",
                                                     "before"]
    # [75,100] under the sleep, [35,45] between the calls, [2,5] in one
    assert r["idle_gaps"] == [["bench.sleep", pytest.approx(0.025)],
                              ["bench.call", pytest.approx(0.010)],
                              ["bench.call", pytest.approx(0.003)]]


def test_nested_operations_count_their_self_time():
    spans = [("bench.window", 0, 100 * MS)]
    ops = {0: [("while", 10 * MS, 90 * MS), ("body", 20 * MS, 50 * MS),
               ("inner", 30 * MS, 40 * MS), ("body", 60 * MS, 70 * MS)]}
    r = tracereduce.reduce(spans, ops)
    assert r["busy_s"] == pytest.approx(0.080)
    assert r["device_ops"] == [["while", pytest.approx(0.040)],
                               ["body", pytest.approx(0.030)],
                               ["inner", pytest.approx(0.010)]]


def test_busy_averages_over_chips():
    spans = [("bench.window", 0, 10 * MS)]
    ops = {0: [("a", 0, 10 * MS)], 1: [("a", 0, 5 * MS)]}
    r = tracereduce.reduce(spans, ops)
    assert r["busy_s"] == pytest.approx(0.0075)
    assert r["device_ops"] == [["a", pytest.approx(0.0075)]]


def test_a_trace_without_window_or_ops_is_an_error():
    with pytest.raises(ValueError):
        tracereduce.reduce([("bench.call", 0, 1)], {0: [("a", 0, 1)]})
    with pytest.raises(ValueError):
        tracereduce.reduce([("bench.window", 0, 1)], {})


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5e: three calls of two small jitted
    programs inside ``bench.window``, each followed by a 5 ms sleep."""
    spans, ops = tracereduce.extract(RECORDED)
    names = [s[0] for s in spans]
    assert names.count("bench.window") == 1 and names.count("bench.call") == 3
    assert list(ops) == [0] and ops[0]
    r = tracereduce.reduce(spans, ops)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["idle_gaps"][0][0] == "bench.sleep"
    assert r["idle_gaps"][0][1] >= 0.004
