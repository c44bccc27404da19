"""Benchmark: the SNP trace-serving front end (sync vs async vs mesh).

Measures what the service adds on top of the raw ``run_traces`` scan
(EXPERIMENTS.md §Serving): grouping/padding overhead of a synchronous
``drain``, per-request completion latency (p50/p99) of the async
background-flush mode, and the mesh-sharded runner
(:func:`repro.core.distributed.run_traces_distributed`) on however many
devices are present — in single-device CI that row doubles as a shard_map
overhead measurement.

Every configuration is warmed first so the jit compile is excluded: the
service holds device shapes fixed (fixed batch, bucketed steps), so a
warmed cache is the steady state a long-lived service runs in.

A second tier, ``serve_fault/...``, measures the failure-domain machinery
(DESIGN.md §4.4): the same burst served under a deterministic
:class:`~repro.runtime.faults.FaultInjector` schedule (two transient flush
failures + one poison request) with a :class:`FaultPolicy` that retries
and bisects.  ``us_per_call`` is per *successfully served* trace — goodput
— so the row directly prices what recovery costs versus the fault-free
``serve/...`` row of the same shape.

Rows merge into ``BENCH_snp.json`` (names ``serve/...`` and
``serve_fault/...``) next to the step and tree tiers:
``PYTHONPATH=src:. python -m benchmarks.bench_serve`` (``--quick`` for the
CI smoke sweep).
"""

import argparse
import json
import os
import time

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core import compile_system, paper_pi
from repro.runtime import FaultInjector, FaultPolicy, PoisonError
from repro.serve import SNPTraceService, TraceRequest, make_trace_runner


def _requests(system, n, steps):
    return [TraceRequest(system, steps=steps, policy="random", seed=s)
            for s in range(n)]


def _bench_sync(system, n, steps, batch, runner=None, tag="sync"):
    svc = SNPTraceService(batch_size=batch, step_bucket=8, runner=runner)
    for r in _requests(system, batch, steps):   # warm the jit cache
        svc.submit(r)
    svc.drain()
    for r in _requests(system, n, steps):
        svc.submit(r)
    t0 = time.perf_counter()
    results = svc.drain()
    dt = time.perf_counter() - t0
    assert len(results) == n
    return (f"serve/{tag}/pi_N{n}_s{steps}_b{batch}", dt / n * 1e6,
            f"{n / dt:.0f}tr/s,{svc.stats()['device_calls'] - 1}calls")


def _bench_async(system, n, steps, batch, max_delay_ms):
    with SNPTraceService(batch_size=batch, step_bucket=8, async_mode=True,
                         max_delay_ms=max_delay_ms) as warm:
        [f.result() for f in
         [warm.submit(r) for r in _requests(system, batch, steps)]]
    done = {}
    with SNPTraceService(batch_size=batch, step_bucket=8, async_mode=True,
                         max_delay_ms=max_delay_ms) as svc:
        t0 = time.perf_counter()
        futs = []
        for i, r in enumerate(_requests(system, n, steps)):
            fut = svc.submit(r)
            fut.add_done_callback(
                lambda f, i=i: done.setdefault(i, time.perf_counter()))
            futs.append(fut)
        for f in futs:
            f.result()
        dt = time.perf_counter() - t0
    lat_ms = np.asarray([done[i] - t0 for i in range(n)]) * 1e3
    return (f"serve/async/pi_N{n}_s{steps}_b{batch}_d{max_delay_ms:g}ms",
            dt / n * 1e6,
            f"{n / dt:.0f}tr/s,p50={np.percentile(lat_ms, 50):.0f}ms,"
            f"p99={np.percentile(lat_ms, 99):.0f}ms")


def _fault_schedule(n):
    """The PR's acceptance schedule scaled to the burst: two transient
    flush failures (the first on the burst's first flush, so the retry
    path is on the clock; the second mid-bisection) + one poison request
    (a nonzero seed mid-burst)."""
    poison = n // 2 + 1
    inj = FaultInjector(fail_calls=(1, 4), poison_seeds=(poison,))
    pol = FaultPolicy(max_retries=2, backoff_ms=0.0, bisect=True,
                      degrade=False)
    return inj, pol, poison


def _fault_derived(svc, served, n, dt):
    s = svc.stats()
    return (f"{served / dt:.0f}tr/s,goodput={served}/{n},"
            f"retries={s['retries']},bisects={s['bisections']},"
            f"failed_calls={s['failed_calls']}")


def _bench_fault_sync(system, n, steps, batch):
    warm = SNPTraceService(batch_size=batch, step_bucket=8)
    for r in _requests(system, batch, steps):   # warm the global jit cache
        warm.submit(r)                          # fault-free so the measured
    warm.drain()                                # run sees the whole schedule
    inj, pol, _ = _fault_schedule(n)
    svc = SNPTraceService(batch_size=batch, step_bucket=8,
                          policy=pol, fault_injector=inj)
    for r in _requests(system, n, steps):
        svc.submit(r)
    t0 = time.perf_counter()
    results = svc.drain()
    dt = time.perf_counter() - t0
    assert len(results) == n - 1                # exactly the poison failed
    assert all(isinstance(e, PoisonError)
               for e in svc.last_failures.values())
    return (f"serve_fault/sync/pi_N{n}_s{steps}_b{batch}",
            dt / len(results) * 1e6, _fault_derived(svc, len(results), n, dt))


def _bench_fault_async(system, n, steps, batch, max_delay_ms):
    with SNPTraceService(batch_size=batch, step_bucket=8, async_mode=True,
                         max_delay_ms=max_delay_ms) as warm:
        [f.result() for f in
         [warm.submit(r) for r in _requests(system, batch, steps)]]
    inj, pol, _ = _fault_schedule(n)
    with SNPTraceService(batch_size=batch, step_bucket=8, async_mode=True,
                         max_delay_ms=max_delay_ms,
                         policy=pol, fault_injector=inj) as svc:
        t0 = time.perf_counter()
        futs = [svc.submit(r) for r in _requests(system, n, steps)]
        served = failed = 0
        for f in futs:
            try:
                f.result()
                served += 1
            except Exception:
                failed += 1
        dt = time.perf_counter() - t0
        assert failed == 1                      # exactly the poison failed
        row = (f"serve_fault/async/pi_N{n}_s{steps}_b{batch}"
               f"_d{max_delay_ms:g}ms",
               dt / served * 1e6, _fault_derived(svc, served, n, dt))
    return row


def rows(quick: bool = False):
    # pre-compiled so no mode pays host-side lowering inside its timed
    # window (the async measurement service is fresh and would otherwise
    # compile on its first submit, which the sync path does pre-t0)
    system = compile_system(paper_pi(covering=True))
    n = 64 if quick else 256
    steps = 32
    batch = 64 if quick else 256
    out = [
        _bench_sync(system, n, steps, batch),
        _bench_async(system, n, steps, batch, max_delay_ms=5.0),
        _bench_fault_sync(system, n, steps, batch),
        _bench_fault_async(system, n, steps, batch, max_delay_ms=5.0),
    ]
    # mesh-sharded runner over every available device (1 in plain CI; run
    # under XLA_FLAGS=--xla_force_host_platform_device_count=8 to measure
    # a faked multi-device mesh on CPU) — same 1-D layout the production
    # serving path flattens to (DESIGN.md §4.3)
    ndev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("traces",))
    out.append(_bench_sync(system, n, steps, batch,
                           runner=make_trace_runner(mesh=mesh),
                           tag=f"mesh{ndev}"))
    return out


def main(path: str = "BENCH_snp.json", quick: bool = False) -> None:
    """Merge serve rows into ``path``, preserving the other tiers."""
    payload = {"rows": []}
    if os.path.exists(path):
        with open(path) as f:
            payload = json.load(f)
    payload["rows"] = [r for r in payload.get("rows", [])
                       if not r["name"].startswith(("serve/", "serve_fault/"))]
    payload["rows"] += [
        {"name": name, "us_per_call": us, "derived": derived}
        for name, us, derived in rows(quick)
    ]
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {path} ({len(payload['rows'])} rows)")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweep for CI smoke runs")
    ap.add_argument("--out", default="BENCH_snp.json")
    args = ap.parse_args()
    main(args.out, quick=args.quick)
