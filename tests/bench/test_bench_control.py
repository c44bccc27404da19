"""The control, the plain reference with bfloat16 state put in the
program's place under the timed path, makes every cell's run come out not
correct through the harness's own check; it stands in for every program
entry point a cell can drive."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchkit  # noqa: E402

from bench import control, reference, spec, systems  # noqa: E402

CELLS = [c["name"] for c in spec.load_spec(benchkit.ROOT)["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchkit.tiny_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(root, workload):
    res = benchkit.run_tiny(root, workload, plant="control:bfloat16")
    assert res["failed"] == 0
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_exact_reference_in_place_is_correct(root, workload):
    """The same planting at full precision passes: the control fails for
    its precision, not for being planted."""
    res = benchkit.run_tiny(root, workload, plant="control:exact")
    assert res["correct"] is True, res["checks"]


CONFIG = {"generator": "power_law",
          "args": {"m": 300, "attach": 4, "max_in": 16}}


def test_control_plants_run_traces_with_init():
    """``engine.run_traces(init=...)`` computes with the reference, from
    one initial row per trace or one row for all."""
    from repro.core import engine
    seeds = np.arange(6, dtype=np.uint32) + np.uint32(3_000_000_000)
    with control.planted():
        plain = systems.build(CONFIG, benchkit.BIG_SEED)
        rows = np.stack([np.random.default_rng(i).permutation(plain.init)
                         for i in range(len(seeds))])
        low = reference.Reference(plain, state="bfloat16")
        for init in (rows, rows[2], None):
            out = engine.run_traces(None, steps=12, seeds=seeds,
                                    policy="random", max_branches=16,
                                    init=init)
            want = low.traces(seeds, 12, 16, init=init)
            for got, ref in zip(out, want):
                np.testing.assert_array_equal(np.asarray(got), ref)


def test_control_plants_explore_distributed():
    """``distributed.explore_distributed`` under a neuron-axis plan
    computes with the reference: a global frontier, the visited capacity
    per shard, the program's result fields."""
    from repro.core import distributed, engine
    from repro.sharding import neuron_axis
    kw = dict(max_steps=6, frontier_cap=16, max_branches=8)
    with control.planted():
        plain = systems.build(CONFIG, benchkit.BIG_SEED)
        init = np.random.default_rng(3).permutation(plain.init)
        res = distributed.explore_distributed(
            None, plan=neuron_axis(4), visited_cap=64, init=init, **kw)
        with pytest.raises(ValueError):
            distributed.explore_distributed(None, visited_cap=64, **kw)
    want = reference.Reference(plain, state="bfloat16").explore(
        visited_cap=256, init=init, **kw)
    assert isinstance(res, engine.ExploreResult)
    assert res.num_discovered == len(want.configs) == len(res.configs)
    np.testing.assert_array_equal(res.configs, want.configs)
    assert (res.steps, res.branch_overflow, res.frontier_overflow,
            res.visited_overflow) == want[1:]
