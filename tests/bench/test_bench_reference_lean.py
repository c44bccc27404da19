"""``bench/reference_lean.py`` is ``bench/reference.py`` without the stored
zeros: the same ``M_Π``, per-neuron counts and ranks, traces and archives,
at both precisions, on the power-law and the sorting systems."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchkit  # noqa: E402,F401  (puts the root and src on sys.path)

from bench.generators import power_law, sort  # noqa: E402
from bench.reference import Reference  # noqa: E402
from bench.reference_lean import Reference as Lean  # noqa: E402

SYSTEMS = {"powerlaw-300": lambda: power_law.generate(5, 300, max_in=16),
           "powerlaw-200-hubs": lambda: power_law.generate(7, 200),
           "sort-12": lambda: sort.generate(0, 12)}


@pytest.mark.parametrize("state", [None, "bfloat16"])
@pytest.mark.parametrize("name", sorted(SYSTEMS))
def test_lean_reference_matches_reference(name, state):
    plain = SYSTEMS[name]()
    full, lean = Reference(plain, state), Lean(plain, state)
    assert (full.M != lean.M).nnz == 0
    assert lean.M.nnz <= full.M.nnz
    rng = np.random.default_rng(1)
    C = rng.integers(0, 14, (9, plain.num_neurons))
    for a, b in zip(full._info(C), lean._info(C)):
        np.testing.assert_array_equal(a, b)
    seeds = np.arange(6, dtype=np.uint32) + np.uint32(3_000_000_000)
    rows = rng.integers(0, 14, (6, plain.num_neurons))
    for init in (None, rows):
        for a, b in zip(full.traces(seeds, 16, 8, init=init),
                        lean.traces(seeds, 16, 8, init=init)):
            np.testing.assert_array_equal(a, b)
    # Reference.explore raises on a wave that finds nothing new, which
    # the deterministic sort system reaches (PERF.md §7)
    if name != "sort-12":
        kw = dict(max_steps=5, frontier_cap=16, visited_cap=64,
                  max_branches=8)
        a, b = full.explore(**kw), lean.explore(**kw)
        np.testing.assert_array_equal(a.configs, b.configs)
        assert a[1:] == b[1:]


def test_sort_inputs_and_sorted_outputs():
    """The inputs plugin draws rows whose only spikes are on the inputs,
    and the outputs it expects are each row's inputs in descending
    order, on the output neurons."""
    plain = sort.generate(0, 9)
    rows = sort.draw_inputs(plain, np.random.default_rng(4), 5, 30)
    assert rows.shape == (5, 27) and not rows[:, 9:].any()
    assert rows.max() <= 30 and rows.min() >= 0
    want = sort.sorted_outputs(plain, rows)
    np.testing.assert_array_equal(want, -np.sort(-rows[:, :9], axis=1))
    assert sort.output_neurons(plain) == slice(18, 27)
    out = Lean(plain).traces(np.arange(5, dtype=np.uint32), 32, 4,
                             init=rows)
    np.testing.assert_array_equal(out.configs[:, -1, 18:], want)
