"""The plain reference agrees with the program at small sizes, and its
lower-precision control (bfloat16 state) fails the same comparison."""

import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchkit  # noqa: E402

from bench import reference, systems  # noqa: E402

SEEDS = np.arange(12, dtype=np.uint32) + np.uint32(4_000_000_000)
power_law = systems.generator("power_law")


@pytest.mark.parametrize("max_in", [16, None])
def test_traces_match_the_program(max_in, monkeypatch, tmp_path):
    from repro.core import run_traces
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    plain = power_law(benchkit.BIG_SEED, 300, 4, max_in=max_in)
    out = run_traces(systems.to_program(plain), steps=24, seeds=SEEDS,
                     policy="random", max_branches=32, backend="sparse")
    ref = reference.Reference(plain).traces(SEEDS, 24, 32)
    for got, want in zip(out, ref):
        np.testing.assert_array_equal(np.asarray(got), want)


def test_explore_matches_the_program(monkeypatch, tmp_path):
    from repro.core import explore
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    plain = power_law(3, 300, 4)
    kw = dict(max_steps=6, frontier_cap=48, visited_cap=8192,
              max_branches=32)
    res = explore(systems.to_program(plain), backend="sparse", **kw)
    ref = reference.Reference(plain).explore(**kw)
    np.testing.assert_array_equal(res.configs, ref.configs)
    assert (res.steps, res.branch_overflow, res.frontier_overflow,
            res.visited_overflow) == ref[1:]


def test_explore_from_another_init_matches_the_program(monkeypatch,
                                                      tmp_path):
    from repro.core import explore
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    plain = power_law(benchkit.BIG_SEED, 300, 4)
    init = np.random.default_rng(5).permutation(plain.init)
    kw = dict(max_steps=6, frontier_cap=48, visited_cap=1024,
              max_branches=32)
    res = explore(systems.to_program(plain), backend="sparse", init=init,
                  **kw)
    ref = reference.Reference(plain).explore(init=init, **kw)
    np.testing.assert_array_equal(res.configs, ref.configs)
    np.testing.assert_array_equal(ref.configs[0], init)
    assert (res.steps, res.branch_overflow, res.frontier_overflow,
            res.visited_overflow) == ref[1:]


def test_traces_from_one_init_per_trace():
    """``init`` of shape ``(k, m)`` gives each trace its own start: row
    ``r`` equals the trace of a system whose initial spikes are that
    row's; ``(m,)`` starts them all there; ``None`` is the system's."""
    plain = power_law(benchkit.BIG_SEED, 300, 4, max_in=16)
    seeds = SEEDS[:5]
    rows = np.stack([np.random.default_rng(i).permutation(plain.init)
                     for i in range(len(seeds))])
    got = reference.Reference(plain).traces(seeds, 16, 32, init=rows)
    for r, row in enumerate(rows):
        own = reference.Reference(dataclasses.replace(plain, init=row))
        want = own.traces(seeds[r:r + 1], 16, 32)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g[r], w[0])
    one = reference.Reference(plain).traces(seeds, 16, 32, init=rows[1])
    np.testing.assert_array_equal(
        one.configs[1], got.configs[1])
    default = reference.Reference(plain).traces(seeds, 16, 32)
    same = reference.Reference(plain).traces(seeds, 16, 32, init=plain.init)
    for d, s in zip(default, same):
        np.testing.assert_array_equal(d, s)


def test_control_fails_traces():
    plain = power_law(benchkit.BIG_SEED, 300, 4, max_in=16)
    exact = reference.Reference(plain).traces(SEEDS, 32, 32)
    low = reference.Reference(plain, state="bfloat16").traces(SEEDS, 32, 32)
    assert exact.configs.max() > 256
    wrong = np.any(exact.configs != low.configs, axis=(1, 2))
    assert wrong.sum() >= len(SEEDS) // 2


def test_control_fails_explore():
    plain = power_law(2**31 + 5, 512, 4)
    kw = dict(max_steps=16, frontier_cap=32, visited_cap=4096,
              max_branches=32)
    exact = reference.Reference(plain).explore(**kw)
    low = reference.Reference(plain, state="bfloat16").explore(**kw)
    n = min(len(exact.configs), len(low.configs))
    assert np.any(exact.configs[:n] != low.configs[:n])


def test_unknown_precision_is_an_error():
    with pytest.raises(ValueError):
        reference.Reference(power_law(0, 16, 2), state="int4")
