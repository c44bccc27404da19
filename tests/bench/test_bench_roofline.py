"""The yardstick's arithmetic: required work, least time, peaks, and the
union of compile intervals."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchkit  # noqa: E402

from bench import roofline  # noqa: E402
from bench.compile_timing import covered  # noqa: E402


def test_traces_call_work_counts_outputs_not_candidates():
    ops, nbytes = roofline.traces_call_work(
        batch=256, steps=32, neurons=32768, rules=65536, synapses=131072)
    out = 256 * 32 * (32768 * 4 + 6)
    assert nbytes == out + 32768 * 4 + 256 * 8 + 65536 * 21 + 131072 * 8
    assert ops == 256 * 32 * (65536 + 131072)
    # the (B, T, m) candidate block would be 32 times the output
    assert nbytes < 2 * out


def test_least_time_takes_the_larger_bound():
    peaks = roofline.peaks_for("TPU v5 lite", benchkit.ROOT)
    assert peaks["hbm_bytes_per_s"] == 819e9
    t, bound = roofline.least_time(1, 819e9, peaks)
    assert (t, bound) == (1.0, "memory")
    t, bound = roofline.least_time(393e12 * 2, 1, peaks)
    assert (t, bound) == (2.0, "compute")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu", benchkit.ROOT)


def test_compile_intervals_are_a_union():
    assert covered([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4
    assert covered([]) == 0
