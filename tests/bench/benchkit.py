"""Helpers of the benchmark's tests: a checkout-like root at tiny sizes.

``tiny_root`` copies ``BENCHMARK.json`` and the data and plugin files
under ``bench/`` into a directory and shrinks every size, so a test
drives the same harness, generators, entries and reference as a chip run,
on the CPU.
"""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {"powerlaw-32k-bounded": {"m": 256, "max_in": 16},
               "powerlaw-8k-hubs": {"m": 512}}
TINY_MIX = {"traces": {"batch": 16, "check_rows_per_call": 4},
            "explore": {"frontier_cap": 32, "visited_cap": 1024},
            "serve_poisson": {"rate_per_s": 40, "check_requests": 16}}
BIG_SEED = 2**31 + 12345


def tiny_root(dst: Path) -> Path:
    """A copy of the benchmark's files under ``dst`` at tiny sizes."""
    (dst / "bench").mkdir(parents=True)
    for sub in (ROOT / "bench").iterdir():
        if sub.is_dir() and sub.name != "__pycache__":
            shutil.copytree(sub, dst / "bench" / sub.name,
                            ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "bench" / "peaks.json", dst / "bench" / "peaks.json")
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    spec = json.loads((dst / "BENCHMARK.json").read_text())
    for entry in spec["configs"]:
        path = dst / entry["file"]
        cfg = json.loads(path.read_text())
        cfg["args"].update(TINY_CONFIG[entry["name"]])
        path.write_text(json.dumps(cfg))
    for mix, sizes in TINY_MIX.items():
        path = dst / "bench" / "traffic" / f"{mix}.json"
        path.write_text(json.dumps({**json.loads(path.read_text()),
                                    **sizes}))
    return dst


def run_tiny(root: Path, workload: str, monkeypatch, seconds: float = 1.0,
             seed: int = BIG_SEED) -> dict:
    """One run of ``workload`` under ``root`` on the CPU: the harness
    without its look for a chip and without the persistent compile
    cache."""
    from bench import harness, spec
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(root / "autotune.json"))
    monkeypatch.setattr(harness, "_enable_compile_cache", lambda: "off")
    cells = spec.load_spec(root)
    return harness.run_cell(cells, spec.find_cell(cells, workload),
                            seed=seed, seconds=seconds, trace=False,
                            root=root, t_start=time.perf_counter())
