"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration (``BENCHMARK.json``'s ``configs`` entry, whose
``file`` names its generator and sizes) and a traffic mix
(``bench/traffic/<mix>.json``, which names its entry point and, when open,
its arrival process).  Everything else is a plugin file, found by the name
the data gives it, so a new kind of cell is new files only:

* ``bench/generators/<generator>.py``: ``generate(seed, **args)`` returns
  the :class:`~bench.systems.PlainSystem` of a configuration;
* ``bench/entries/<entry>.py``: ``Entry``, the program's entry point as a
  mix drives it (the interface of :class:`bench.entrykit.Entry`);
* ``bench/arrivals/<arrival>.py``: ``offsets(mix, seconds, rng)`` returns
  the arrival times of an open-loop window;
* ``bench/metrics/<metric>.py``: ``read(readings)`` returns a per-layer
  metric, or ``None`` where the run has nothing to read.

Which metrics a cell reports follows from the entries alone: an
end-to-end metric without ``workloads`` belongs to every cell, and a
per-layer metric without ``workloads`` to every cell that reports the
metric it ``moves``.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, List

from bench.traffic import load_mix

__all__ = ["load_spec", "find_cell", "load_config", "load_mix",
           "end_to_end_for", "per_layer_for", "plugin", "metric_reader",
           "entry_class"]

_PLUGINS: dict = {}


def load_spec(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: "
                   f"{[c['name'] for c in spec['workloads']]})")


def load_config(spec: dict, root: Path, name: str) -> dict:
    for entry in spec["configs"]:
        if entry["name"] == name:
            return json.loads((Path(root) / entry["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def _applies(metric: dict, cell: dict) -> bool:
    return "workloads" not in metric or cell["name"] in metric["workloads"]


def end_to_end_for(spec: dict, cell: dict) -> List[dict]:
    return [m for m in spec["end_to_end"] if _applies(m, cell)]


def per_layer_for(spec: dict, cell: dict) -> List[dict]:
    moved = {m["name"] for m in end_to_end_for(spec, cell)}
    return [m for m in spec["per_layer"]
            if m["moves"] in moved and _applies(m, cell)]


def plugin(root: Path, kind: str, name: str):
    """The module ``bench/<kind>/<name>.py`` under ``root``, loaded once."""
    path = (Path(root) / "bench" / kind / f"{name}.py").resolve()
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} plugin {name!r} at {path}")
    if path not in _PLUGINS:
        tag = "".join(c if c.isalnum() else "_" for c in f"{kind}_{name}")
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_{tag}_{len(_PLUGINS)}", path)
        module = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(module)
        _PLUGINS[path] = module
    return _PLUGINS[path]


def metric_reader(root: Path, name: str) -> Callable:
    """``read`` of ``bench/metrics/<name>.py``."""
    return plugin(root, "metrics", name).read


def entry_class(root: Path, name: str):
    """``Entry`` of ``bench/entries/<name>.py``."""
    return plugin(root, "entries", name).Entry
