"""One run of one cell: set-up, the measured window, the check, the result.

:func:`run_cell` takes everything it needs from ``BENCHMARK.json`` and the
files it names (:mod:`bench.spec`).  The caller has checked the device;
tests call it on the CPU with the timed path broken underneath.
"""

from __future__ import annotations

import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

from bench import spec as specs
from bench import systems
from bench.compile_timing import BACKEND, CompileClock
from bench.traffic import Traffic

__all__ = ["run_cell", "device_info"]


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs[:chips]]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


def _prepare_out(root: Path, workload: str) -> Path:
    out = Path(root) / ".bench_out" / workload
    out.mkdir(parents=True, exist_ok=True)
    # the planner plans from the committed seeds alone, never a user cache
    cache = out / "autotune.json"
    cache.unlink(missing_ok=True)
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(cache)
    return out


def _enable_compile_cache() -> str:
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    # every program of a cell, however quick to compile, is kept, so a
    # second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(spec: dict, cell: dict, *, seed: int, seconds: float,
             trace: bool, root: Path, t_start: float,
             peaks: dict | None = None, rate_per_s: float | None = None
             ) -> dict:
    """Run ``cell`` once and return the result line's object.

    ``t_start`` is the ``perf_counter`` reading at which the process
    started its set-up; ``peaks`` (from ``bench/peaks.json``) is needed
    only for a traced run; ``rate_per_s`` replaces an open-loop mix's
    arrival rate (``bench/sweep.py`` looks for the knee with it)."""
    import jax
    from jax.profiler import TraceAnnotation

    out = _prepare_out(root, cell["name"])
    _log(f"compile_cache={_enable_compile_cache()}")
    config = specs.load_config(spec, root, cell["config"])
    mix = specs.load_mix(root, cell["traffic"])
    if rate_per_s is not None:
        mix["rate_per_s"] = rate_per_s
    e2e = specs.end_to_end_for(spec, cell)
    layer = specs.per_layer_for(spec, cell)

    with CompileClock() as clock:
        with TraceAnnotation("bench.setup.generate"):
            plain = systems.build(config, seed, root)
            program_system = systems.to_program(plain)
        _log(f"system {plain.name} neurons={plain.num_neurons} "
             f"rules={plain.num_rules} synapses={plain.num_synapses} "
             f"max_in={int(plain.in_degrees().max())}")
        entry = specs.entry_class(root, mix["entry"])(
            plain, program_system, mix, Traffic(mix, seed, root))
        entry.setup()
        t_window = time.perf_counter()
        setup_s = t_window - t_start
        trace_dir = out / "trace"
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
        try:
            with TraceAnnotation("bench.window"):
                values = entry.window(seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        t_end = time.perf_counter()
    window_compiles = clock.count(t_window, t_end, BACKEND)
    _log(f"setup_s={setup_s!r} compile_s={clock.seconds(t_start, t_window)!r}"
         f" window_s={t_end - t_window!r} "
         f"backend_compiles_in_window={window_compiles}")
    device = device_info(cell["chips"])
    entry.release()

    result = {"correct": None, "attempted": entry.attempted,
              "failed": entry.failed, "metrics": {}, "device": device}
    values["setup_s"] = setup_s
    if trace:
        from bench import tracereduce
        t0 = time.perf_counter()
        spans, ops = tracereduce.extract(tracereduce.latest_xplane(trace_dir))
        reduced = tracereduce.reduce(
            spans, {c: ops[c] for c in sorted(ops)[:cell["chips"]]})
        _log(f"trace_reduce_s={time.perf_counter() - t0!r} "
             f"device_events={sum(len(v) for v in ops.values())}")
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        readings = SimpleNamespace(
            entry=mix["entry"], trace=reduced,
            compile_s=clock.seconds(t_start, t_window),
            least_time_s=entry.least_time_s(peaks),
            waves=entry.waves(), counters=entry.counters())
        for metric in layer:
            value = specs.metric_reader(root, metric["name"])(readings)
            if value is not None:
                result["metrics"][metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        for metric in e2e:
            if values.get(metric["name"]) is not None:
                result["metrics"][metric["name"]] = {
                    "value": values[metric["name"]], "unit": metric["unit"]}

    with TraceAnnotation("bench.check"):
        t0 = time.perf_counter()
        checks = entry.check()
        _log(f"check_s={time.perf_counter() - t0!r}")
    missing = [m["name"] for m in (layer if trace else e2e)
               if m["name"] not in result["metrics"]]
    if missing:
        _log(f"metrics not read: {missing}")
    result["correct"] = all(v <= limit for v, limit in checks.values())
    result["checks"] = {name: {"value": v, "limit": limit}
                        for name, (v, limit) in checks.items()}
    return result
