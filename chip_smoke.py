#!/usr/bin/env python3
"""Bring-up smoke test: the SNP simulator's main path on one TPU chip.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the two distributed
                                      # comparisons, and nothing else

One process drives the chip through the entry points a user calls, with
the query planner picking the backend wherever the caller would leave it
open.  Phases, in order:

* ``paper_pi``  — ``explore`` of the paper's Π (planner's pick, then each
  registered backend pinned by name: identical archives) and the ℕ∖{1}
  check of ``examples/quickstart.py``;
* ``exact``     — a system whose produce (> 2^8) and spike counts (> 2^8)
  are not bf16-exact: every backend's archive equals the integer
  ``sparse`` path's;
* ``step``      — ``run_traces`` on ``power_law(32768, 4, max_in=64)``
  (256 traces × 32 steps, the large benchmark tier's system): the
  planner's pick, the hybrid ELL+COO encoding, and every Pallas backend
  that lowers at that shape equal ``sparse`` bit for bit; then the dense
  kernel against ``sparse`` at m = 2048, a width it compiles at;
* ``explore``   — ``explore`` on the unbounded-hub ``power_law(8192, 4)``
  with a 65536-row archive (2 GiB of int32 on the device plus the dedup
  table), checked against ``sparse`` and the dense ``ref`` oracle;
* ``serve``     — an async ``SNPTraceService`` on a one-device mesh serves
  64 requests; every future resolves to the direct ``run_traces`` rows.

Each run prints one line: backend, ``interpret`` flag, compile seconds
(the time covered by JAX's trace, lowering and compile events inside the
call), run seconds (the rest of the call's wall time) and the device's
``peak_bytes_in_use`` so far.  A planner degradation
(``repro.core.failover``) fails the run: on the chip every kernel must
run as picked.  The last line is one JSON object naming the device; it is
printed only when every phase passed.  Without a TPU the script exits 2
before any phase.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"
sys.path.insert(0, str(ROOT / "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every size the phases run at; :data:`CHIP` is the real run,
    tests use smaller ones on the CPU."""

    trace_m: int = 32768          # step: power_law(m, 4, seed=2, max_in=64)
    traces: int = 256
    trace_steps: int = 32
    trace_branches: int = 32
    kernel_m: int = 2048          # step: where the dense kernel compiles
    kernel_traces: int = 64
    explore_m: int = 8192         # explore: power_law(m, 4, seed=2)
    visited_cap: int = 65536
    frontier_cap: int = 256
    explore_branches: int = 32
    explore_steps: int = 4
    serve_m: int = 2048           # serve: power_law(m, 4, seed=3, max_in=64)
    requests: int = 64
    serve_steps: int = 32
    dist_frontier_cap: int = 1024  # --chips 4: overflow-free for 2 levels
    dist_steps: int = 2


CHIP = Sizes()

# JAX's compile-path events: tracing, lowering to MLIR, backend compile.
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_spans: list[tuple[float, float]] = []


def _on_duration(event: str, duration: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        end = time.perf_counter()
        _compile_spans.append((end - duration, end))


def _covered(spans) -> float:
    """Seconds covered by the union of ``spans``: traces nest (a kernel
    body or an inner ``jit`` is traced inside its caller's trace), so a
    plain sum would count them twice."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(spans):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def _peak(device=None):
    """``peak_bytes_in_use`` of ``device`` (default: the first), or
    ``None`` where the backend keeps no memory statistics."""
    import jax
    stats = (device or jax.devices()[0]).memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def _interpret(backend):
    """The ``interpret`` flag a backend's kernel resolves to here, or
    ``None`` for a backend without a kernel."""
    from repro.core.plan import resolve_interpret
    if not hasattr(backend, "interpret"):
        return None
    return resolve_interpret(backend.interpret)


def _digest(a) -> str:
    a = np.ascontiguousarray(np.asarray(a))
    return hashlib.sha256(a.tobytes()).hexdigest()[:16]


def timed(phase: str, backend, fn, **fields):
    """Run ``fn()`` once and print its line; returns ``fn``'s result."""
    n0, t0 = len(_compile_spans), time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    comp = _covered(_compile_spans[n0:])
    be_name = getattr(backend, "name", backend)
    print(f"phase={phase} backend={be_name} "
          f"interpret={_interpret(backend) if backend else None} "
          f"compile_s={comp:.3f} run_s={wall - comp:.3f} "
          f"peak_bytes_in_use={_peak()} "
          + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)
    return out


def planner_pick(system, workload):
    """The backend the entry points resolve ``backend=None`` to."""
    from repro.core.backend import resolve_entry_info
    be, _, _ = resolve_entry_info(system, None, None, workload=workload)
    return be


def refusal(name: str, system) -> str | None:
    """Why the registered backend ``name`` refuses ``system`` on this
    platform (its kernel's ``compile_refusal``), or ``None``."""
    from repro.core.backend import get_backend
    check = getattr(get_backend(name), "compile_refusal", None)
    return check(system.num_neurons, system.num_rules) if check else None


def _same(a, b, what: str) -> None:
    for x, y, field in zip(a, b, ("configs", "emissions", "alive",
                                  "branch_overflow")):
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            raise AssertionError(f"{what}: {field} differ")


def exact_system():
    """Four neurons whose produce (257..40001) and spike counts leave
    bf16's exact integer range (2^8) after one step: a dense step that
    rounded its operands to bf16 would disagree with the integer path."""
    from repro.core import Rule, SNPSystem
    rules = (
        Rule(0, 1, 1001, 1, covering=True),
        Rule(0, 2, 40001, 2, covering=True),
        Rule(1, 1, 257, 1, covering=True),
        Rule(1, 3, 3, 3),
        Rule(2, 1000, 513, 1001, covering=True),
        Rule(3, 1, 300, 1, covering=True),
        Rule(3, 2, 0, 2),
    )
    return SNPSystem(num_neurons=4, initial_spikes=(2, 1, 0, 0), rules=rules,
                     synapses=((0, 1), (0, 2), (1, 0), (2, 3), (3, 0)),
                     output_neuron=3, name="exact")


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------


def phase_paper_pi(sizes: Sizes):
    """Paper Π: planner's pick, then every backend pinned by name."""
    from repro.core import (available_backends, compile_system,
                            emission_gaps, explore, get_backend, paper_pi)
    system = paper_pi(covering=True)
    kw = dict(max_steps=16, frontier_cap=128, visited_cap=2048,
              max_branches=16)
    pick = planner_pick(system, (kw["frontier_cap"], kw["max_branches"]))
    base = timed("paper_pi", pick, lambda: explore(system, **kw),
                 planner=True)
    results = {"planner": base}
    for name in available_backends():
        why = refusal(name, system)
        if why:
            print(f"phase=paper_pi backend={name} refused: {why}")
            continue
        res = timed("paper_pi", get_backend(name),
                    lambda: explore(system, backend=name, **kw))
        if not np.array_equal(res.configs, base.configs):
            raise AssertionError(f"paper_pi: {name} archive differs")
        results[name] = res
    gaps = timed("paper_pi", None, lambda: emission_gaps(
        compile_system(paper_pi(covering=False)), max_time=25, max_gap=12),
        check="N\\{1}")
    if 1 in gaps or not set(range(2, 12)) <= gaps:
        raise AssertionError(f"paper_pi: gaps {sorted(gaps)} are not N\\{{1}}")
    print(f"phase=paper_pi ok configs={base.num_discovered} "
          f"backends={sorted(results)}", flush=True)
    return results


def phase_exact(sizes: Sizes):
    """Values past bf16's exact range: every backend equals ``sparse``."""
    from repro.core import available_backends, explore, get_backend
    system = exact_system()
    kw = dict(max_steps=5, frontier_cap=256, visited_cap=4096,
              max_branches=16)
    base = timed("exact", get_backend("sparse"),
                 lambda: explore(system, backend="sparse", **kw))
    if base.configs.max() <= 256 or base.frontier_overflow \
            or base.visited_overflow:
        raise AssertionError("exact: the run must stay overflow-free and "
                             "reach counts above 2^8")
    results = {"sparse": base}
    for name in available_backends():
        if name == "sparse":
            continue
        why = refusal(name, system)
        if why:
            print(f"phase=exact backend={name} refused: {why}")
            continue
        res = timed("exact", get_backend(name),
                    lambda: explore(system, backend=name, **kw))
        if not np.array_equal(res.configs, base.configs):
            raise AssertionError(f"exact: {name} archive differs")
        results[name] = res
    print(f"phase=exact ok configs={base.num_discovered} "
          f"max_spikes={int(base.configs.max())} backends={sorted(results)}",
          flush=True)
    return results


def phase_step(sizes: Sizes):
    """``run_traces`` at real size: planner's pick and every Pallas backend
    that lowers there equal ``sparse``; the dense kernel at a width it
    compiles at equals ``sparse`` too."""
    from repro.core import SystemPlan, get_backend, run_traces
    from repro.core.generators import power_law
    results = {}
    for label, m, B in (("large", sizes.trace_m, sizes.traces),
                        ("kernel", sizes.kernel_m, sizes.kernel_traces)):
        system = power_law(m, 4, seed=2, max_in=64)
        kw = dict(steps=sizes.trace_steps, seeds=np.arange(B),
                  policy="random", max_branches=sizes.trace_branches)
        ref = timed("step", get_backend("sparse"),
                    lambda: run_traces(system, backend="sparse", **kw),
                    m=m, traces=B)
        runs = {"sparse": ref}
        if label == "large":
            pick = planner_pick(system, (B, sizes.trace_branches))
            runs["planner"] = timed(
                "step", pick, lambda: run_traces(system, **kw),
                m=m, traces=B, planner=True)
            # the hub-capped encoding: its COO tail is a segment-sum
            hybrid = SystemPlan(encoding="hybrid")
            runs["sparse_hybrid"] = timed(
                "step", get_backend("sparse"),
                lambda: run_traces(system, backend="sparse", plan=hybrid,
                                   **kw), m=m, traces=B, encoding="hybrid")
        for name in ("pallas", "sparse_pallas"):
            why = refusal(name, system)
            if why:
                print(f"phase=step m={m} backend={name} refused: {why}")
                continue
            runs[name] = timed(
                "step", get_backend(name),
                lambda: run_traces(system, backend=name, **kw),
                m=m, traces=B)
        for name, out in runs.items():
            _same(out, ref, f"step m={m} {name} vs sparse")
        results[label] = runs
        print(f"phase=step m={m} ok backends={sorted(runs)} "
              f"configs_digest={_digest(ref.configs)}", flush=True)
    return results


def phase_explore(sizes: Sizes):
    """``explore`` with the archive and dedup table at real size."""
    from repro.core import SystemPlan, explore, get_backend, resolve_dedup
    from repro.core.generators import power_law
    system = power_law(sizes.explore_m, 4, seed=2)
    kw = dict(max_steps=sizes.explore_steps, frontier_cap=sizes.frontier_cap,
              visited_cap=sizes.visited_cap,
              max_branches=sizes.explore_branches, dedup="auto")
    dedup = resolve_dedup("auto", frontier_cap=sizes.frontier_cap,
                          visited_cap=sizes.visited_cap,
                          max_branches=sizes.explore_branches)
    pick = planner_pick(system, (sizes.frontier_cap, sizes.explore_branches))
    plan = SystemPlan.for_system(
        system, workload=(sizes.frontier_cap, sizes.explore_branches),
        mode="auto")
    base = timed("explore", pick, lambda: explore(system, **kw),
                 planner=True, dedup=dedup, encoding=plan.encoding)
    archive_bytes = sizes.visited_cap * sizes.explore_m * 4
    peak = _peak()
    if peak is not None and peak < archive_bytes:
        raise AssertionError(f"explore: peak {peak} B is below the "
                             f"{archive_bytes} B archive")
    results = {"planner": base}
    # The pinned sparse run keeps the planner's encoding: pure ELL pads
    # every row to the top hub's in-degree.
    pinned = (("sparse", SystemPlan(encoding=plan.encoding,
                                    hub_threshold=plan.hub_threshold)),
              ("ref", None))
    for name, p in pinned:
        res = timed("explore", get_backend(name),
                    lambda: explore(system, backend=name, plan=p, **kw),
                    dedup=dedup)
        if res.num_discovered != base.num_discovered \
                or _digest(res.configs) != _digest(base.configs):
            raise AssertionError(f"explore: {name} differs from the "
                                 "planner's pick")
        results[name] = res
    print(f"phase=explore ok discovered={base.num_discovered} "
          f"steps={base.steps} archive_digest={_digest(base.configs)}",
          flush=True)
    return results


def phase_serve(sizes: Sizes):
    """Async service on a one-device mesh against direct ``run_traces``."""
    import jax
    from jax.sharding import Mesh

    from repro.core import get_backend, run_traces
    from repro.core.generators import power_law
    from repro.serve import SNPTraceService, TraceRequest, make_trace_runner
    system = power_law(sizes.serve_m, 4, seed=3, max_in=64)
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    seeds = np.arange(sizes.requests)

    def serve():
        with SNPTraceService(
                async_mode=True,
                runner=make_trace_runner(mesh=mesh)) as svc:
            futs = [svc.submit(TraceRequest(
                system, steps=sizes.serve_steps, policy="random",
                seed=int(s), max_branches=32)) for s in seeds]
            return [f.result(timeout=600) for f in futs], svc.stats()

    served, stats = timed("serve", get_backend("ref"), serve,
                          requests=sizes.requests)
    direct = timed("serve", get_backend("ref"), lambda: run_traces(
        system, steps=sizes.serve_steps, seeds=seeds, policy="random",
        max_branches=32, backend="ref"), direct=True)
    for i, r in enumerate(served):
        for x, y, field in ((r.configs, direct.configs[i], "configs"),
                            (r.emissions, direct.emissions[i], "emissions"),
                            (r.alive, direct.alive[i], "alive")):
            if not np.array_equal(x, np.asarray(y)):
                raise AssertionError(f"serve: request {i} {field} differ")
    print(f"phase=serve ok requests={len(served)} "
          f"device_calls={stats['device_calls']}", flush=True)
    return served


# ---------------------------------------------------------------------------
# Four-chip phases (--chips 4)
# ---------------------------------------------------------------------------


def _mesh4():
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()[:4]
    if len(devs) < 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = Mesh(np.array(devs), ("x",))
    if set(mesh.devices.flat) != set(devs):
        raise AssertionError("the mesh does not span jax.devices()[:4]")
    return mesh


def _per_device_peaks(phase: str):
    import jax
    peaks = [_peak(d) for d in jax.devices()[:4]]
    print(f"phase={phase} per_device_peak_bytes_in_use={peaks}", flush=True)
    return peaks


def phase_explore_distributed(sizes: Sizes):
    """Neuron-axis-sharded explore over 4 devices against one device."""
    from repro.core import explore
    from repro.core.distributed import explore_distributed
    from repro.core.generators import power_law
    from repro.sharding import neuron_axis
    mesh = _mesh4()
    system = power_law(sizes.explore_m, 4, seed=2)
    kw = dict(max_steps=sizes.dist_steps,
              frontier_cap=sizes.dist_frontier_cap,
              visited_cap=sizes.visited_cap,
              max_branches=sizes.explore_branches)
    single = timed("explore_distributed", None,
                   lambda: explore(system, **kw), devices=1)
    dist = timed("explore_distributed", None, lambda: explore_distributed(
        system, mesh=mesh, plan=neuron_axis(4), **kw), devices=4)
    if single.frontier_overflow or single.visited_overflow:
        raise AssertionError("explore_distributed: the reference overflowed")
    if dist.num_discovered != single.num_discovered or \
            {tuple(r) for r in dist.configs} != \
            {tuple(r) for r in single.configs}:
        raise AssertionError("explore_distributed: sets differ")
    peaks = _per_device_peaks("explore_distributed")
    # each device keeps its (4·V, m/4) int32 slice of the global archive
    shard_archive = 4 * sizes.visited_cap * (sizes.explore_m // 4) * 4
    if all(p is not None for p in peaks) and min(peaks) < shard_archive:
        raise AssertionError("explore_distributed: a device holds less than "
                             "its archive shard")
    print(f"phase=explore_distributed ok discovered={dist.num_discovered}",
          flush=True)
    return single, dist


def phase_traces_distributed(sizes: Sizes):
    """Batch-sharded traces over 4 devices against one device."""
    from repro.core import run_traces
    from repro.core.distributed import run_traces_distributed
    from repro.core.generators import power_law
    mesh = _mesh4()
    system = power_law(sizes.trace_m, 4, seed=2, max_in=64)
    kw = dict(steps=sizes.trace_steps, seeds=np.arange(sizes.traces),
              policy="random", max_branches=sizes.trace_branches)
    single = timed("traces_distributed", None,
                   lambda: run_traces(system, **kw), devices=1)
    dist = timed("traces_distributed", None,
                 lambda: run_traces_distributed(system, mesh=mesh, **kw),
                 devices=4)
    _same(dist, single, "traces_distributed")
    _per_device_peaks("traces_distributed")
    print(f"phase=traces_distributed ok traces={sizes.traces}", flush=True)
    return single, dist


ONE_CHIP = (phase_paper_pi, phase_exact, phase_step, phase_explore,
            phase_serve)
FOUR_CHIPS = (phase_explore_distributed, phase_traces_distributed)


def run(phases, sizes: Sizes) -> bool:
    """Run ``phases`` in order with degradations counted as failures;
    True when every phase passed."""
    import jax

    from repro.core import failover
    degraded = []
    failover.add_degrade_listener(degraded.append)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    ok = True
    try:
        for phase in phases:
            try:
                phase(sizes)
            except Exception:
                ok = False
                print(f"phase={phase.__name__[6:]} FAILED", flush=True)
                traceback.print_exc()
    finally:
        failover.remove_degrade_listener(degraded.append)
        jax.monitoring.unregister_event_duration_listener(_on_duration)
    for ev in degraded:
        ok = False
        print(f"degraded: {ev}", flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip comparisons")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2

    # The planner must read only the committed seeds: a fresh autotune
    # cache under this run's output, never ~/.cache.
    OUT.mkdir(parents=True, exist_ok=True)
    cache = OUT / "autotune.json"
    cache.unlink(missing_ok=True)
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(cache)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile_cache={enable_compile_cache()}", flush=True)

    phases = FOUR_CHIPS if args.chips == 4 else ONE_CHIP
    if not run(phases, CHIP):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
