"""The control, the plain reference with bfloat16 state put in the
program's place under the timed path, makes every cell's run come out not
correct through the harness's own check."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchkit  # noqa: E402

from bench import control, spec  # noqa: E402

CELLS = [c["name"] for c in spec.load_spec(benchkit.ROOT)["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchkit.tiny_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(root, workload, monkeypatch):
    with control.planted():
        res = benchkit.run_tiny(root, workload, monkeypatch)
    assert res["failed"] == 0
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_exact_reference_in_place_is_correct(root, workload, monkeypatch):
    """The same planting at full precision passes: the control fails for
    its precision, not for being planted."""
    with control.planted(state=None):
        res = benchkit.run_tiny(root, workload, monkeypatch)
    assert res["correct"] is True, res["checks"]
