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
    assert mix.get("shards", 1) == cell["chips"]
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


def _add_config(src, name, generator, args, tiny_args=None):
    (src / "bench" / "configs" / f"{name}.json").write_text(json.dumps({
        "name": name, "generator": generator, "args": args, "reduced": []}))
    if tiny_args is not None:
        benchkit.tiny_file(src, "configs", name).write_text(
            json.dumps(tiny_args))
    return {"name": name, "source": "test",
            "file": f"bench/configs/{name}.json", "reduced": [],
            "why": "test"}


def _add_mix(src, name, mix, tiny_mix=None):
    (src / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    if tiny_mix is not None:
        benchkit.tiny_file(src, "traffic", name).write_text(
            json.dumps(tiny_mix))


def _cell(name, config, traffic):
    return {"name": name, "config": config, "traffic": traffic, "chips": 1,
            "why": "test"}


def _snapshot(root):
    return {p.relative_to(root): p.read_bytes()
            for sub in ("bench", benchkit.TINY) for p in (root / sub).rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_config_mix_and_metric_are_new_files_only(tmp_path):
    """A new configuration, mix and metric, and a new kind of cell (its own
    generator, entry point and arrival process), are new files and new
    ``BENCHMARK.json`` entries: no file under ``bench/`` or its tiny sizes
    changes.  Entered in ``BENCHMARK.json`` before the tiny root is made,
    with their tiny files, they run at their tiny sizes, and every
    existing cell reads the same tiny files as without them."""
    src = benchkit.copy_checkout(tmp_path / "src")
    bench = src / "bench"
    before = _snapshot(src)
    cells = json.loads((src / "BENCHMARK.json").read_text())
    cells["configs"] += [
        _add_config(src, "powerlaw-300", "power_law",
                    {"m": 3000, "attach": 3}, {"m": 300}),
        _add_config(src, "ring-64", "ring", {"m": 4096}, {"m": 64})]
    _add_mix(src, "short_traces",
             {"entry": "run_traces", "loop": "closed", "batch": 256,
              "steps": 5, "max_branches": 8, "policy": "random",
              "check_rows_per_call": 8}, {"batch": 8})
    _add_mix(src, "even_singles",
             {"entry": "single_traces", "loop": "open", "arrival": "even",
              "rate_per_s": 200, "steps": 6, "max_branches": 4},
             {"rate_per_s": 20})
    (bench / "metrics" / "calls_seen.py").write_text(
        "def read(r):\n    return r.trace['window_s'] or None\n")
    (bench / "generators" / "ring.py").write_text(RING)
    (bench / "arrivals" / "even.py").write_text(EVEN)
    (bench / "entries" / "single_traces.py").write_text(SINGLE)
    cells["workloads"] += [_cell("pl300.short", "powerlaw-300",
                                 "short_traces"),
                           _cell("ring.singles", "ring-64", "even_singles")]
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
    (src / "BENCHMARK.json").write_text(json.dumps(cells))
    assert {p: b for p, b in _snapshot(src).items() if p in before} == before

    root = benchkit.tiny_root(tmp_path / "checkout", src=src)
    plain = benchkit.tiny_root(tmp_path / "plain")
    files = {c["name"]: c["file"] for c in SPEC["configs"]}
    for cell in SPEC["workloads"]:
        for path in (files[cell["config"]],
                     f"bench/traffic/{cell['traffic']}.json"):
            assert (root / path).read_bytes() == (plain / path).read_bytes()
    assert spec.load_config(cells, root, "ring-64")["args"] == {"m": 64}
    assert spec.load_mix(root, "short_traces")["batch"] == 8
    cell = spec.find_cell(cells, "pl300.short")
    assert [m["name"] for m in spec.per_layer_for(cells, cell)] == \
        ["compile_s", "calls_seen"]
    read = spec.metric_reader(root, "calls_seen")
    assert read(SimpleNamespace(trace={"window_s": 2.5})) == 2.5
    res = benchkit.run_tiny(root, "pl300.short")
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"setup_s", "trace_steps_per_s"}
    res = benchkit.run_tiny(root, "ring.singles")
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] == 20
    assert set(res["metrics"]) == {"setup_s", "single_p95_ms"}
    res = benchkit.run_tiny(root, "pl8k.traces")
    assert res["correct"] is True, res["checks"]


def test_cell_without_tiny_files_fails_alone(tmp_path):
    """A configuration or mix without its tiny file fails the cells that
    use it, naming the file; the tiny root is made and the other cells
    run."""
    src = benchkit.copy_checkout(tmp_path / "src")
    cells = json.loads((src / "BENCHMARK.json").read_text())
    cells["configs"].append(_add_config(src, "powerlaw-300", "power_law",
                                        {"m": 300, "attach": 3}))
    _add_mix(src, "long_traces", {
        "entry": "run_traces", "loop": "closed", "batch": 4, "steps": 64,
        "max_branches": 8, "policy": "random", "check_rows_per_call": 4})
    cells["workloads"] += [
        _cell("pl300.traces", "powerlaw-300", "traces"),
        _cell("pl8k.long", "powerlaw-8k-hubs", "long_traces")]
    (src / "BENCHMARK.json").write_text(json.dumps(cells))

    root = benchkit.tiny_root(tmp_path / "checkout", src=src)
    for workload, missing in (
            ("pl300.traces", "tests/bench/tiny/configs/powerlaw-300.json"),
            ("pl8k.long", "tests/bench/tiny/traffic/long_traces.json")):
        with pytest.raises(FileNotFoundError, match=re.escape(missing)):
            benchkit.run_tiny(root, workload)
    res = benchkit.run_tiny(root, "pl8k.traces")
    assert res["correct"] is True, res["checks"]


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
