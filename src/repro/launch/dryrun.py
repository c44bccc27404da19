import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell: ``jax.jit(step, in_shardings, out_shardings,
donate).lower(*avals).compile()`` on the 16×16 single-pod mesh AND the
2×16×16 multi-pod mesh; record ``memory_analysis()`` (proves it fits),
``cost_analysis()`` + HLO-parsed collective bytes (feeds §Roofline).

This is the ONLY entry point allowed to fake 512 devices — the env var
above must run before any other import (jax locks device count on first
init).  Results stream to JSON per cell so partial runs are never lost.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch all --shape all --mesh both --out experiments/dryrun
    ... --arch smollm-360m --shape train_4k --mesh single   # one cell
    ... --snp                                                # SNP engine cells
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, get_config, list_archs
from repro.configs.base import ArchConfig, ShapeSpec
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (abstract_cache, abstract_train_state,
                                decode_input_specs, input_specs)
from repro.roofline.analysis import analyze_compiled
from repro.serve import make_decode_step, make_prefill_step
from repro.sharding import make_plan
from repro.train import AdamWConfig, make_train_step

# Per-arch training knobs chosen so activations fit 16 GB/chip under full
# remat (validated by memory_analysis; revised during §Perf iteration).
TRAIN_KNOBS: Dict[str, Dict[str, Any]] = {
    "qwen2-vl-7b":          dict(microbatches=4),
    "qwen2-moe-a2.7b":      dict(microbatches=4),
    "grok-1-314b":          dict(microbatches=16),
    "command-r-35b":        dict(microbatches=8),
    "minicpm3-4b":          dict(microbatches=4),
    "smollm-360m":          dict(microbatches=1),
    "minicpm-2b":           dict(microbatches=2),
    "jamba-1.5-large-398b": dict(microbatches=8),
    "rwkv6-7b":             dict(microbatches=4),
    "musicgen-medium":      dict(microbatches=2),
}


def _model_flops(cfg: ArchConfig, spec: ShapeSpec) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE); decode counts one
    token per sequence, no backward (2·N·D)."""
    n_active = cfg.active_param_count()
    if spec.kind == "train":
        tokens = spec.global_batch * spec.seq_len
        return 6.0 * n_active * tokens
    if spec.kind == "prefill":
        tokens = spec.global_batch * spec.seq_len
        return 2.0 * n_active * tokens
    tokens = spec.global_batch  # decode: one new token each
    return 2.0 * n_active * tokens


def run_cell(cfg: ArchConfig, shape_name: str, multi_pod: bool,
             seq_shard: bool = False,
             microbatches: Optional[int] = None,
             remat: str = "full",
             attn_impl: str = "xla",
             expert_pad: int = 0) -> Dict:
    import dataclasses as _dc
    if expert_pad:
        cfg = _dc.replace(cfg, expert_pad_multiple=expert_pad)
    spec = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    plan = make_plan(mesh, seq_shard_activations=seq_shard)
    t0 = time.time()

    with mesh:
        if spec.kind == "train":
            knobs = dict(TRAIN_KNOBS.get(cfg.name, {}))
            if microbatches is not None:
                knobs["microbatches"] = microbatches
            opt_cfg = AdamWConfig()
            step = make_train_step(cfg, opt_cfg, remat=remat,
                                   attn_impl=attn_impl,
                                   constrain=plan.constrain, **knobs)
            state = abstract_train_state(cfg, opt_cfg)
            batch = input_specs(cfg, spec)
            state_specs = jax.tree.map(
                lambda s: s, plan.param_specs(cfg, state))
            in_sh = (jax.tree.map(plan.named, state_specs),
                     jax.tree.map(plan.named, plan.batch_specs(cfg, batch)))
            jitted = jax.jit(step, in_shardings=in_sh,
                             donate_argnums=(0,))
            lowered = jitted.lower(state, batch)
        elif spec.kind == "prefill":
            pstep = make_prefill_step(cfg, max_len=spec.seq_len,
                                      attn_impl=attn_impl,
                                      constrain=plan.constrain)
            params = abstract_train_state(cfg, AdamWConfig()).params
            batch = input_specs(cfg, spec, with_labels=False)
            in_sh = (jax.tree.map(plan.named, plan.param_specs(cfg, params)),
                     jax.tree.map(plan.named, plan.batch_specs(cfg, batch)))
            jitted = jax.jit(pstep, in_shardings=in_sh)
            lowered = jitted.lower(params, batch)
        else:  # decode
            dstep = make_decode_step(cfg, constrain=plan.constrain)
            params = abstract_train_state(cfg, AdamWConfig()).params
            cache = abstract_cache(cfg, spec.global_batch, spec.seq_len)
            dbatch = decode_input_specs(cfg, spec)
            key = jax.ShapeDtypeStruct((2,), jnp.uint32)
            bspecs = jax.tree.map(plan.named,
                                  plan.batch_specs(cfg, dbatch))
            in_sh = (
                jax.tree.map(plan.named, plan.param_specs(cfg, params)),
                jax.tree.map(plan.named, plan.cache_specs(cfg, cache)),
                bspecs["tokens"],
                bspecs["positions"],
                plan.named(jax.sharding.PartitionSpec()),
            )
            jitted = jax.jit(dstep, in_shardings=in_sh,
                             donate_argnums=(1,))
            lowered = jitted.lower(params, cache, dbatch["tokens"],
                                   dbatch["positions"], key)
        compiled = lowered.compile()

    record = analyze_compiled(
        lowered, compiled, chips=chips,
        model_flops=_model_flops(cfg, spec),
        default_group=chips)
    record.update(
        arch=cfg.name, shape=shape_name,
        mesh="2x16x16" if multi_pod else "16x16", chips=chips,
        seq_shard=seq_shard, remat=remat, attn_impl=attn_impl,
        expert_pad=expert_pad,
        microbatches=(microbatches
                      or TRAIN_KNOBS.get(cfg.name, {}).get("microbatches")),
        compile_seconds=round(time.time() - t0, 1),
        param_count=cfg.param_count(),
        active_param_count=cfg.active_param_count(),
    )
    return record


def run_snp_cell(multi_pod: bool, *, neurons: int = 2048, rules: int = 4096,
                 frontier_per_dev: int = 32, max_branches: int = 64) -> Dict:
    """Dry-run of the distributed SNP exploration step on the production
    mesh (the paper's workload at 'very large system' scale)."""
    import functools
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core.backend import get_backend
    from repro.core.distributed import _dense_loop
    from repro.core.generators import random_system
    from repro.core.hashtable import table_slots
    from repro.core.matrix import compile_system

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    ndev = mesh.devices.size
    flat = Mesh(mesh.devices.reshape(-1), ("x",))
    system = random_system(neurons, max(1, rules // neurons), 8 / neurons,
                           seed=0)
    comp = compile_system(system)
    m, n = comp.num_neurons, comp.num_rules
    F, T = frontier_per_dev, max_branches
    C = max(16, (F * T) // ndev)
    V = 4096
    SL = table_slots(V)

    # The fused BFS loop explore_distributed runs: the carry is the
    # device-sharded state plus the replicated (step, total_new) scalars,
    # and the last argument is the replicated level bound.
    state_specs = (P("x"),) * 9 + (P(), P())
    step = jax.jit(
        jax.shard_map(
            functools.partial(_dense_loop, axis="x", ndev=ndev,
                              max_branches=T, send_cap=C, visited_cap=V,
                              backend=get_backend("ref")),
            mesh=flat,
            in_specs=(P(),) + state_specs + (P(),),
            out_specs=state_specs,
            check_vma=False,
        ),
        donate_argnums=tuple(range(1, 12)),
    )
    sds = jax.ShapeDtypeStruct
    args = (
        jax.eval_shape(lambda: comp),
        sds((ndev * F, m), jnp.int32), sds((ndev * F,), jnp.bool_),
        sds((ndev * SL,), jnp.uint32), sds((ndev * SL,), jnp.uint32),
        sds((ndev * SL,), jnp.int32), sds((ndev,), jnp.int32),
        sds((ndev * V, m), jnp.int32), sds((ndev,), jnp.int32),
        sds((ndev, 3), jnp.bool_), sds((), jnp.int32), sds((), jnp.int32),
        sds((), jnp.int32),
    )
    with flat:
        lowered = step.lower(*args)
        compiled = lowered.compile()
    record = analyze_compiled(lowered, compiled, chips=ndev,
                              default_group=ndev)
    record.update(arch=f"snp-{neurons}n-{n}r", shape="explore_step",
                  mesh="2x16x16" if multi_pod else "16x16", chips=ndev,
                  compile_seconds=round(time.time() - t0, 1))
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--snp", action="store_true",
                    help="also dry-run the SNP exploration step")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--attn-impl", default="xla",
                    choices=["xla", "chunked", "pallas"])
    ap.add_argument("--expert-pad", type=int, default=0)
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    results, failures = [], []

    def emit(rec):
        results.append(rec)
        path = os.path.join(
            args.out, f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=float)
        print(f"[dryrun] {rec['arch']:24s} {rec['shape']:12s} "
              f"{rec['mesh']:8s} compute={rec.get('compute_s', 0):.4f}s "
              f"memory={rec.get('memory_s', 0):.4f}s "
              f"collective={rec.get('collective_s', 0):.4f}s "
              f"bound={rec.get('bound')} "
              f"({rec['compile_seconds']}s compile)", flush=True)

    for name in archs:
        cfg = get_config(name)
        for shape in shapes:
            if shape == "long_500k" and not cfg.supports_long_context:
                print(f"[dryrun] {name:24s} long_500k    SKIP "
                      "(pure full attention, DESIGN.md §5)", flush=True)
                continue
            for multi in meshes:
                try:
                    emit(run_cell(cfg, shape, multi,
                                  seq_shard=args.seq_shard,
                                  microbatches=args.microbatches,
                                  remat=args.remat,
                                  attn_impl=args.attn_impl,
                                  expert_pad=args.expert_pad))
                except Exception as e:
                    failures.append((name, shape, multi, repr(e)))
                    print(f"[dryrun] FAIL {name} {shape} "
                          f"{'multi' if multi else 'single'}: {e}",
                          flush=True)
                    traceback.print_exc()

    if args.snp:
        for multi in meshes:
            try:
                emit(run_snp_cell(multi))
            except Exception as e:
                failures.append(("snp", "explore", multi, repr(e)))
                traceback.print_exc()

    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump({"results": results, "failures": failures}, f, indent=1,
                  default=float)
    print(f"\n[dryrun] {len(results)} cells OK, {len(failures)} failed")
    if failures:
        for f_ in failures:
            print("  FAIL:", f_)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
