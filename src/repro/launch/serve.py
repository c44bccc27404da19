"""Batched serving drivers: the LM path (prefill + streamed decode) and the
SNP trace path (mesh-backed async service).

CPU-runnable with --smoke; on a pod the same code paths serve the full
config with sequence-sharded KV caches (LM) or the whole mesh as one
data-parallel trace axis (SNP, DESIGN.md §4).

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
        --batch 4 --prompt-len 64 --gen 32

    PYTHONPATH=src python -m repro.launch.serve --snp \
        --batch 64 --requests 256 --gen 32 --max-delay-ms 5
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.smoke import reduced
from repro.data import DataConfig, make_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import build_mesh_for_available
from repro.models import init_params
from repro.serve import (SNPTraceService, TraceRequest, make_decode_step,
                         make_prefill_step, make_trace_runner)
from repro.sharding import make_plan


def serve_snp(args) -> None:
    """Stand up the mesh-backed async SNP trace service and serve a burst.

    The mesh is the plan's full device set flattened onto one ``traces``
    axis (`plan.trace_mesh()`); every flush of the service shards its
    batch over it via :func:`repro.core.distributed.run_traces_distributed`
    — bit-identical to single-device serving, so this driver doubles as a
    correctness check on whatever devices are available.
    """
    from repro.core import paper_pi
    from repro.runtime import FaultInjector, FaultPolicy

    mesh = build_mesh_for_available()
    plan = make_plan(mesh)
    trace_mesh = plan.trace_mesh()
    runner = make_trace_runner(mesh=trace_mesh)
    system = paper_pi(covering=True)

    policy = None
    if (args.max_retries is not None or args.deadline_ms is not None
            or args.max_pending is not None or args.inject):
        policy = FaultPolicy(
            max_retries=2 if args.max_retries is None else args.max_retries,
            backoff_ms=args.backoff_ms,
            deadline_ms=args.deadline_ms,
            max_pending=args.max_pending)
    injector = None
    if args.inject:
        # "fail=2,4 poison=17 slow=3:0.05" -> a deterministic schedule
        kw = {}
        for part in args.inject.split():
            k, _, v = part.partition("=")
            if k == "fail":
                kw["fail_calls"] = [int(x) for x in v.split(",") if x]
            elif k == "poison":
                kw["poison_seeds"] = [int(x) for x in v.split(",") if x]
            elif k == "slow":
                kw["slow_calls"] = {
                    int(o): float(s) for o, s in
                    (pair.split(":") for pair in v.split(","))}
            else:
                raise SystemExit(f"unknown --inject term {part!r}")
        injector = FaultInjector(**kw)

    n, G = args.requests, args.gen
    with SNPTraceService(batch_size=args.batch, step_bucket=8,
                         backend=args.backend, runner=runner,
                         async_mode=True,
                         max_delay_ms=args.max_delay_ms,
                         policy=policy, fault_injector=injector) as svc:
        print(f"[serve-snp] mesh {trace_mesh.devices.size}-device, "
              f"batch {args.batch}, max_delay {args.max_delay_ms} ms, "
              f"backend {args.backend}"
              + (f", policy {policy}" if policy else ""))
        done = {}
        t0 = time.perf_counter()
        futs = []
        for s in range(n):
            fut = svc.submit(TraceRequest(system, steps=G, policy="random",
                                          seed=s))
            # completion timestamps via callback: waiting on futs in order
            # would attribute earlier futures' wait to later ones
            fut.add_done_callback(
                lambda f, s=s: done.setdefault(s, time.perf_counter()))
            futs.append(fut)
        failed = 0
        for f in futs:
            try:
                f.result()
            except Exception as e:
                failed += 1
                print(f"[serve-snp] request failed: {type(e).__name__}: {e}")
        dt = time.perf_counter() - t0
        stats = svc.stats()
    # outside the with-block: close() joined the drain thread, so every
    # done-callback has run (result() alone doesn't guarantee the last
    # future's callback fired before the waiter woke)
    lat_ms = np.asarray([done[s] - t0 for s in range(n)]) * 1e3
    print(f"[serve-snp] {n - failed}/{n} traces x {G} steps in "
          f"{dt*1e3:.1f} ms ({n / dt:.0f} traces/s, "
          f"{stats['device_calls']} device calls)")
    if stats["queued_requests"] and stats["device_calls"]:
        print(f"[serve-snp] mean queue wait "
              f"{stats['queue_wait_us'] / stats['queued_requests'] / 1e3:.1f}"
              f" ms, mean flush "
              f"{stats['flush_us'] / stats['device_calls'] / 1e3:.1f} ms "
              f"({stats['flush_device_us'] / stats['device_calls'] / 1e3:.1f}"
              f" ms in the device call)")
    print(f"[serve-snp] completion latency p50={np.percentile(lat_ms, 50):.1f} ms "
          f"p99={np.percentile(lat_ms, 99):.1f} ms")
    if policy is not None or injector is not None:
        print("[serve-snp] fault stats: " + ", ".join(
            f"{k}={v}" for k, v in stats.items() if v))
    ok = next((f for f in futs if not f.exception()), None)
    if ok is not None:
        emis = np.asarray(ok.result().emissions)
        print(f"[serve-snp] sample spike train: {emis.tolist()}")


def serve_lm(args):
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    mesh = build_mesh_for_available()
    plan = make_plan(mesh)
    B, S, G = args.batch, args.prompt_len, args.gen
    max_len = S + G + 1

    with mesh:
        params = init_params(jax.random.PRNGKey(args.seed), cfg)
        prefill = jax.jit(make_prefill_step(cfg, max_len=max_len,
                                            constrain=plan.constrain))
        decode = jax.jit(make_decode_step(cfg,
                                          temperature=args.temperature,
                                          constrain=plan.constrain))

        batch = make_batch(cfg, DataConfig(seed=args.seed), step=0, shard=0,
                           batch=B, seq_len=S)
        batch = {k: jnp.asarray(v) for k, v in batch.items()
                 if k not in ("labels",)}

        t0 = time.time()
        logits, cache = prefill(params, batch)
        logits.block_until_ready()
        t_prefill = time.time() - t0
        print(f"[serve] prefill {B}x{S}: {t_prefill*1e3:.1f} ms "
              f"({B*S/t_prefill:.0f} tok/s)")

        last = logits[:, :, -1, :] if cfg.codebooks else logits[:, -1, :]
        tok = jnp.argmax(last, -1).astype(jnp.int32)[..., None]
        key = jax.random.PRNGKey(args.seed)
        outs = []
        t0 = time.time()
        for g in range(G):
            pos = jnp.full((B, 1), S + g, jnp.int32)
            if cfg.mrope_sections:
                pos = jnp.broadcast_to(pos[None], (3, B, 1))
            key, sub = jax.random.split(key)
            tok, logits, cache = decode(params, cache, tok, pos, sub)
            outs.append(np.asarray(tok)[..., 0])
        jax.block_until_ready(tok)
        dt = time.time() - t0
        print(f"[serve] decode {G} steps: {dt/G*1e3:.2f} ms/step "
              f"({B*G/dt:.0f} tok/s)")
        gen = np.stack(outs, -1)
        print(f"[serve] sample generations (first 16 token ids/request):")
        for b in range(min(B, 4)):
            row = gen[b] if not cfg.codebooks else gen[b, 0]
            print(f"  req{b}: {row[:16].tolist()}")
    return gen


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--snp", action="store_true",
                    help="serve SNP traces (mesh-backed async service) "
                         "instead of the LM path")
    ap.add_argument("--arch", default=None,
                    help="LM config name (required without --snp)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=None,
                    help="request batch (default: 4 for the LM path, 256 — "
                         "the service batch_size — for --snp)")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    # SNP service knobs
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--max-delay-ms", type=float, default=5.0)
    ap.add_argument("--backend", default="ref")
    # failure-domain knobs: any of these turns on the FaultPolicy path
    ap.add_argument("--max-retries", type=int, default=None,
                    help="retries per flush before degrade/bisect "
                         "(default 2 once any fault flag is set)")
    ap.add_argument("--backoff-ms", type=float, default=10.0,
                    help="base retry backoff (exponential, jittered)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline; expired requests fail "
                         "fast with DeadlineExceeded")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="admission control: reject submits past this "
                         "queue depth")
    ap.add_argument("--inject", default=None,
                    help="deterministic fault schedule, e.g. "
                         "'fail=2,4 poison=17 slow=3:0.05'")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.batch is None:
        args.batch = 256 if args.snp else 4
    if args.snp:
        return serve_snp(args)
    if args.arch is None:
        ap.error("--arch is required without --snp")
    return serve_lm(args)


if __name__ == "__main__":
    main()
