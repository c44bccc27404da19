"""``BENCHMARK.json`` meets the benchmark's contract, and the harness finds
configurations, traffic mixes, generators, entry points, arrival processes
and per-layer metrics by name, so a new one is new files plus new
entries."""

import json
import re
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchkit  # noqa: E402

from bench import spec  # noqa: E402

SPEC = spec.load_spec(benchkit.ROOT)
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert all((benchkit.ROOT / p).is_dir() for p in SPEC["paths"])
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_lines():
    entries = (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
               + SPEC["per_layer"])
    for e in entries:
        assert NAME.fullmatch(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_finds_its_files_and_metrics(cell):
    config = spec.load_config(SPEC, benchkit.ROOT, cell["config"])
    assert config["name"] == cell["config"]
    mix = spec.load_mix(benchkit.ROOT, cell["traffic"])
    assert callable(spec.entry_class(benchkit.ROOT, mix["entry"]))
    assert callable(spec.plugin(benchkit.ROOT, "generators",
                                config["generator"]).generate)
    if mix["loop"] == "open":
        assert callable(spec.plugin(benchkit.ROOT, "arrivals",
                                    mix["arrival"]).offsets)
    e2e = {m["name"] for m in spec.end_to_end_for(SPEC, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = spec.per_layer_for(SPEC, cell)
    assert layer and all(m["moves"] in e2e for m in layer)
    for m in layer:
        assert callable(spec.metric_reader(benchkit.ROOT, m["name"]))


RING = '''
import numpy as np
from bench.systems import PlainSystem


def generate(seed, m):
    """A ring: neuron i synapses onto i + 1; two covering rules each."""
    own = np.repeat(np.arange(m), 2)
    return PlainSystem(
        name=f"ring-{m}", init=np.random.default_rng(seed).integers(0, 4, m),
        rule_neuron=own, consume=np.tile([1, 2], m), produce=np.ones(2 * m,
        int), base=np.tile([1, 2], m), period=np.zeros(2 * m, int),
        covering=np.ones(2 * m, bool), src=np.arange(m),
        dst=(np.arange(m) + 1) % m, out_neuron=m - 1)
'''

EVEN = '''
import numpy as np


def offsets(mix, seconds, rng):
    n = max(1, round(mix["rate_per_s"] * seconds))
    return (np.arange(n) + 1) * (seconds / n)
'''

SINGLE = '''
import time

import numpy as np

from bench import entrykit
from bench.reference import Reference


class Entry(entrykit.Entry):
    """Open loop of one-trace run_traces calls, without the service."""

    def setup(self):
        self.be, self.plan, self.comp = entrykit.plan_and_compile(
            self.system, (1, self.mix["max_branches"]))
        self._call(0)

    def _call(self, seed):
        from repro.core import engine
        return engine.run_traces(self.comp, steps=self.mix["steps"],
                                 seeds=np.asarray([seed], np.uint32),
                                 policy="random",
                                 max_branches=self.mix["max_branches"],
                                 backend=self.be, plan=self.plan)

    def window(self, seconds):
        offsets, self.seeds = self.traffic.arrivals(seconds)
        lat, self.got = [], []
        t0 = time.perf_counter()
        for due, seed in zip(offsets, self.seeds):
            time.sleep(max(0.0, t0 + due - time.perf_counter()))
            self.got.append([np.asarray(a)[0] for a in self._call(seed)])
            lat.append(time.perf_counter() - t0 - due)
        self.attempted = len(self.seeds)
        return {"single_p95_ms": float(np.percentile(lat, 95)) * 1e3}

    def check(self):
        ref = Reference(self.plain).traces(self.seeds, self.mix["steps"],
                                           self.mix["max_branches"])
        wrong = sum(any(not np.array_equal(g, r[i])
                        for g, r in zip(got, ref))
                    for i, got in enumerate(self.got))
        return {"requests_wrong": (wrong, 0)}
'''


def test_new_config_mix_and_metric_are_new_files_only(tmp_path,
                                                      monkeypatch):
    """A new configuration, mix and metric, and a new kind of cell (its own
    generator, entry point and arrival process), are new files and new
    ``BENCHMARK.json`` entries: no file under ``bench/`` changes."""
    root = benchkit.tiny_root(tmp_path / "checkout")
    bench = root / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs" / "powerlaw-300.json").write_text(json.dumps({
        "name": "powerlaw-300", "generator": "power_law",
        "args": {"m": 300, "attach": 3}, "reduced": []}))
    (bench / "traffic" / "short_traces.json").write_text(
        json.dumps({"entry": "run_traces", "loop": "closed", "batch": 8,
                    "steps": 5, "max_branches": 8, "policy": "random",
                    "check_rows_per_call": 8}))
    (bench / "metrics" / "calls_seen.py").write_text(
        "def read(r):\n    return r.trace['window_s'] or None\n")
    (bench / "generators" / "ring.py").write_text(RING)
    (bench / "arrivals" / "even.py").write_text(EVEN)
    (bench / "entries" / "single_traces.py").write_text(SINGLE)
    (bench / "configs" / "ring-64.json").write_text(json.dumps({
        "name": "ring-64", "generator": "ring", "args": {"m": 64},
        "reduced": []}))
    (bench / "traffic" / "even_singles.json").write_text(json.dumps({
        "entry": "single_traces", "loop": "open", "arrival": "even",
        "rate_per_s": 20, "steps": 6, "max_branches": 4}))
    cells = json.loads((root / "BENCHMARK.json").read_text())
    cells["configs"] += [
        {"name": "powerlaw-300", "source": "test",
         "file": "bench/configs/powerlaw-300.json", "reduced": [],
         "why": "test"},
        {"name": "ring-64", "source": "test",
         "file": "bench/configs/ring-64.json", "reduced": [], "why": "test"}]
    cells["workloads"] += [
        {"name": "pl300.short", "config": "powerlaw-300",
         "traffic": "short_traces", "chips": 1, "why": "test"},
        {"name": "ring.singles", "config": "ring-64",
         "traffic": "even_singles", "chips": 1, "why": "test"}]
    next(m for m in cells["end_to_end"]
         if m["name"] == "trace_steps_per_s")["workloads"].append(
             "pl300.short")
    cells["end_to_end"].append({
        "name": "single_p95_ms", "unit": "ms", "better": "lower",
        "bound": 0.05, "source": "host_clock", "workloads": ["ring.singles"]})
    cells["per_layer"].append({
        "name": "calls_seen", "unit": "s", "better": "higher",
        "source": "device_trace", "layer": "device",
        "moves": "trace_steps_per_s", "workloads": ["pl300.short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(cells))

    assert {p: p.read_bytes() for p in before} == before
    cell = spec.find_cell(cells, "pl300.short")
    assert [m["name"] for m in spec.per_layer_for(cells, cell)] == \
        ["compile_s", "calls_seen"]
    read = spec.metric_reader(root, "calls_seen")
    assert read(SimpleNamespace(trace={"window_s": 2.5})) == 2.5
    res = benchkit.run_tiny(root, "pl300.short", monkeypatch)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"setup_s", "trace_steps_per_s"}
    res = benchkit.run_tiny(root, "ring.singles", monkeypatch)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] == 20
    assert set(res["metrics"]) == {"setup_s", "single_p95_ms"}


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        spec.find_cell(SPEC, "no.such.cell")
    with pytest.raises(KeyError):
        spec.load_config(SPEC, benchkit.ROOT, "no-such-config")
    with pytest.raises(FileNotFoundError):
        spec.load_mix(benchkit.ROOT, "no_such_mix")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader(benchkit.ROOT, "no_such_metric")
    with pytest.raises(FileNotFoundError):
        spec.entry_class(benchkit.ROOT, "no_such_entry")
    for kind in ("generators", "arrivals"):
        with pytest.raises(FileNotFoundError):
            spec.plugin(benchkit.ROOT, kind, "no_such_plugin")
