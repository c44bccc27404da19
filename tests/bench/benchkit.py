"""Helpers of the benchmark's tests: a checkout-like root at tiny sizes.

``tiny_root`` copies ``BENCHMARK.json``, the data and plugin files under
``bench/`` and the tiny sizes under ``tests/bench/tiny/`` into a
directory and shrinks every configuration and mix that has a tiny file,
so a test drives the same harness, generators, entries and reference as
a chip run, on the CPU.  The tiny sizes are data found by name:

* ``tests/bench/tiny/configs/<config>.json``: keys that replace the
  configuration file's ``args``;
* ``tests/bench/tiny/traffic/<mix>.json``: keys that replace the mix's.

A cell whose configuration or mix has no tiny file fails alone, in
:func:`run_tiny`, with the missing file named.  A cell on more than one
chip runs in a subprocess that sees as many virtual CPU devices
(``python tests/bench/benchkit.py <root> <workload> ...``); whatever the
test plants under the timed path is named by a string, so the subprocess
plants it too:

* ``control:bfloat16`` / ``control:exact``: ``bench.control.planted``;
* ``fault:<name>``: :func:`break_timed_path`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = Path("tests") / "bench" / "tiny"
BIG_SEED = 2**31 + 12345


def copy_checkout(dst: Path, src: Path = ROOT) -> Path:
    """``BENCHMARK.json``, the data and plugin files under ``bench/`` and
    the tiny sizes of ``src``, copied to ``dst`` as they are."""
    (dst / "bench").mkdir(parents=True)
    for sub in (src / "bench").iterdir():
        if sub.is_dir() and sub.name != "__pycache__":
            shutil.copytree(sub, dst / "bench" / sub.name,
                            ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(src / "bench" / "peaks.json", dst / "bench" / "peaks.json")
    shutil.copy(src / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(src / TINY, dst / TINY)
    return dst


def tiny_file(root: Path, kind: str, name: str) -> Path:
    """Where ``root`` keeps the tiny sizes of configuration or mix
    ``name`` (``kind`` is ``configs`` or ``traffic``)."""
    return Path(root) / TINY / kind / f"{name}.json"


def _shrink(path: Path, tiny: Path, key: str = None) -> None:
    if tiny.is_file():
        data = json.loads(path.read_text())
        (data[key] if key else data).update(json.loads(tiny.read_text()))
        path.write_text(json.dumps(data))


def tiny_root(dst: Path, src: Path = ROOT) -> Path:
    """A copy of the benchmark's files of ``src`` under ``dst`` at tiny
    sizes."""
    copy_checkout(dst, src)
    spec = json.loads((dst / "BENCHMARK.json").read_text())
    for entry in spec["configs"]:
        _shrink(dst / entry["file"], tiny_file(dst, "configs", entry["name"]),
                "args")
    for mix in {cell["traffic"] for cell in spec["workloads"]}:
        _shrink(dst / "bench" / "traffic" / f"{mix}.json",
                tiny_file(dst, "traffic", mix))
    return dst


def _entry_of(root: Path, workload: str) -> str:
    from bench import spec
    cell = spec.find_cell(spec.load_spec(root), workload)
    return spec.load_mix(root, cell["traffic"])["entry"]


# -- faults of the timed path ------------------------------------------------


def _break_traces(monkeypatch, fault):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import engine
    from repro.serve import snp_service
    orig = engine.run_traces

    def run(comp, *, steps, seeds, **kw):
        if fault == "half_batch":
            seeds = np.asarray(seeds)
            half = orig(comp, steps=steps, seeds=seeds[:len(seeds) // 2],
                        **kw)
            return type(half)(*(jnp.concatenate(
                [a, jnp.zeros((len(seeds) - a.shape[0],) + a.shape[1:],
                              a.dtype)]) for a in half))
        out = orig(comp, steps=steps, seeds=seeds, **kw)
        if fault == "unchanged":
            return out._replace(configs=jnp.broadcast_to(
                comp.init_config, out.configs.shape))
        return out._replace(configs=out.configs.at[:, -1, 0].add(1))

    monkeypatch.setattr(engine, "run_traces", run)
    monkeypatch.setattr(snp_service, "run_traces", run)
    if fault == "half_batch":
        # the service pads its batch: leave out half of its requests
        run_batch = snp_service.SNPTraceService._run_batch

        def half_requests(self, comp, policy, max_branches, tickets, reqs,
                          backend=None):
            out = run_batch(self, comp, policy, max_branches, tickets, reqs,
                            backend)
            for t in tickets[(len(tickets) + 1) // 2:]:
                r = out[t]
                out[t] = dataclasses.replace(
                    r, configs=np.zeros_like(r.configs))
            return out

        monkeypatch.setattr(snp_service, "run_traces", orig)
        monkeypatch.setattr(snp_service.SNPTraceService, "_run_batch",
                            half_requests)


def _break_explore(monkeypatch, fault):
    from repro.core import distributed, engine

    def broken(orig):
        def run(comp, **kw):
            if fault == "half_batch":
                kw["frontier_cap"] //= 2
                return orig(comp, **kw)
            res = orig(comp, **kw)
            if fault == "unchanged":
                return dataclasses.replace(res, configs=res.configs[:1],
                                           num_discovered=1)
            configs = res.configs.copy()
            configs[-1, 0] += 1
            return dataclasses.replace(res, configs=configs)
        return run

    monkeypatch.setattr(engine, "explore", broken(engine.explore))
    monkeypatch.setattr(distributed, "explore_distributed",
                        broken(distributed.explore_distributed))


def _leave_out_exchange(monkeypatch):
    """Every ``all_to_all`` between chips delivers zeros: a shard never
    hears the spikes its neighbours send across the cut."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setattr(jax.lax, "all_to_all",
                        lambda x, *args, **kw: jnp.zeros_like(x))


def break_timed_path(monkeypatch, entry: str, fault: str) -> None:
    """Plant ``fault`` under the program entry point that ``entry``
    drives: a step that returns its state unchanged, half of the batch
    left out, an answer altered where it is produced, or (across chips)
    the exchange between chips left out."""
    if fault == "no_exchange":
        _leave_out_exchange(monkeypatch)
    elif entry in ("explore", "explore_distributed"):
        _break_explore(monkeypatch, fault)
    else:
        _break_traces(monkeypatch, fault)


# -- one run -------------------------------------------------------------------


def _run_here(root: Path, workload: str, seconds: float, seed: int,
              plant: str | None, compile_cache: bool = False) -> dict:
    import pytest

    from bench import control, harness, spec
    cells = spec.load_spec(root)
    with contextlib.ExitStack() as stack:
        mp = stack.enter_context(pytest.MonkeyPatch.context())
        if not compile_cache:
            mp.setattr(harness, "_enable_compile_cache", lambda: "off")
        if plant is not None:
            kind, arg = plant.split(":")
            if kind == "control":
                stack.enter_context(control.planted(
                    state=None if arg == "exact" else arg))
            elif kind == "fault":
                break_timed_path(mp, _entry_of(root, workload), arg)
            else:
                raise ValueError(f"unknown plant {plant!r}")
        return harness.run_cell(cells, spec.find_cell(cells, workload),
                                seed=seed, seconds=seconds, trace=False,
                                root=root, t_start=time.perf_counter())


def run_tiny(root: Path, workload: str, seconds: float = 1.0,
             seed: int = BIG_SEED, plant: str | None = None) -> dict:
    """One run of ``workload`` under ``root`` on the CPU, with ``plant``
    under its timed path: the harness without its look for a chip.  A
    cell on one chip runs in this process, without the persistent compile
    cache; a cell on ``chips`` > 1 runs in a subprocess with that many
    virtual devices, whose programs the next such subprocess finds in
    ``<root>/.jax_cache``."""
    from bench import spec
    root = Path(root)
    cell = spec.find_cell(spec.load_spec(root), workload)
    for kind, name in (("configs", cell["config"]),
                       ("traffic", cell["traffic"])):
        path = tiny_file(root, kind, name)
        if not path.is_file():
            raise FileNotFoundError(
                f"cell {workload!r} has no tiny sizes for {kind} {name!r}: "
                f"add {path}")
    if cell["chips"] == 1:
        return _run_here(root, workload, seconds, seed, plant)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"),
               XLA_FLAGS=("--xla_force_host_platform_device_count="
                          f"{cell['chips']}"))
    proc = subprocess.run(
        [sys.executable, __file__, str(root), workload, repr(seconds),
         str(seed), plant or ""],
        env=env, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"{workload} on {cell['chips']} virtual devices "
                           f"exited {proc.returncode}:\n{proc.stderr[-4000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


if __name__ == "__main__":
    root_arg, workload_arg, seconds_arg, seed_arg, plant_arg = sys.argv[1:]
    result = _run_here(Path(root_arg), workload_arg, float(seconds_arg),
                       int(seed_arg), plant_arg or None, compile_cache=True)
    print(json.dumps(result), flush=True)
