"""Every cell of ``BENCHMARK.json`` runs end to end on the CPU at a tiny size
and comes out correct; with the timed path broken underneath, the same
run comes out not correct.  A cell on several chips runs on as many
virtual devices, in a subprocess."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchkit  # noqa: E402

from bench import spec  # noqa: E402

SPEC = spec.load_spec(benchkit.ROOT)
CELLS = [c["name"] for c in SPEC["workloads"]]
MULTI_CHIP = [c["name"] for c in SPEC["workloads"] if c["chips"] > 1]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchkit.tiny_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(root, workload):
    res = benchkit.run_tiny(root, workload)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    cells = spec.load_spec(root)
    cell = spec.find_cell(cells, workload)
    want = {m["name"] for m in spec.end_to_end_for(cells, cell)}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["checks"].values())
    assert res["device"]["count"] >= cell["chips"]


# -- faults of the timed path ------------------------------------------------


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(root, workload, fault):
    res = benchkit.run_tiny(root, workload, plant=f"fault:{fault}")
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("workload", MULTI_CHIP)
def test_exchange_left_out_is_not_correct(root, workload):
    """The halo ``all_to_all`` between chips delivers nothing."""
    res = benchkit.run_tiny(root, workload, plant="fault:no_exchange")
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
