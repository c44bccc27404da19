#!/usr/bin/env python3
"""The correctness check's control: the plain reference with its state held
in bfloat16, put in the program's place under the timed path, and run
through the harness like any run of the cell.  Its check has to come out
not correct.

    python3 bench/control.py --workload pl8k.explore --seconds 5 101 102 103

For each seed it prints one JSON line with ``correct`` and every number
the check compared beside its limit.  The benchmark's own runs never run
it; ``tests/bench/test_bench_control.py`` runs it at a tiny size.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

if __package__ in (None, ""):
    import run  # noqa: F401  (bench/run.py: puts the root and src on sys.path)


@contextlib.contextmanager
def planted(state: str = "bfloat16"):
    """Within the block, every program entry point a cell can drive
    (``engine.run_traces``, ``engine.explore``, the service's runner and
    ``distributed.explore_distributed``) computes with the plain
    reference at ``state`` precision, on the system the harness last
    built."""
    import jax.numpy as jnp
    import numpy as np

    from bench import systems
    from bench.reference import Reference
    from repro.core import distributed, engine
    from repro.serve import snp_service

    built = {}
    build = systems.build

    def build_and_keep(config, seed, root=systems.ROOT):
        plain = build(config, seed, root)
        built["ref"] = Reference(plain, state=state)
        return plain

    def run_traces(system, *, steps, seeds, policy="first", max_branches=64,
                   backend=None, plan=None, init=None):
        if policy != "random":
            raise ValueError(f"the control draws random traces, not {policy}")
        out = built["ref"].traces(np.asarray(seeds), steps, max_branches,
                                  init=init)
        return engine.TraceOut(
            jnp.asarray(out.configs, jnp.int32),
            jnp.asarray(out.emissions, jnp.int32),
            jnp.asarray(out.alive), jnp.asarray(out.overflow))

    def explore(system, *, max_steps, frontier_cap, visited_cap,
                max_branches, init=None, **_):
        a = built["ref"].explore(max_steps=max_steps,
                                 frontier_cap=frontier_cap,
                                 visited_cap=visited_cap,
                                 max_branches=max_branches, init=init)
        overflow = (a.branch_overflow, a.frontier_overflow,
                    a.visited_overflow)
        return engine.ExploreResult(
            configs=a.configs.astype(np.int32),
            num_discovered=len(a.configs), steps=a.steps,
            exhausted=a.steps < max_steps and not any(overflow),
            branch_overflow=overflow[0], frontier_overflow=overflow[1],
            visited_overflow=overflow[2])

    def explore_sharded(system, *, plan=None, visited_cap=2048, **kw):
        # the neuron-sharded scheme's frontier is global and its visited
        # capacity per shard; the dense-row scheme has no stand-in here
        if plan is None or plan.num_shards < 2:
            raise ValueError("the control stands in for the neuron-sharded "
                             "explore_distributed only")
        return explore(system, visited_cap=visited_cap * plan.num_shards,
                       **kw)

    patches = [(systems, "build", build_and_keep),
               (engine, "run_traces", run_traces),
               (engine, "explore", explore),
               (snp_service, "run_traces", run_traces),
               (distributed, "explore_distributed", explore_sharded)]
    saved = [(mod, name, getattr(mod, name)) for mod, name, _ in patches]
    try:
        for mod, name, fn in patches:
            setattr(mod, name, fn)
        yield
    finally:
        for mod, name, fn in saved:
            setattr(mod, name, fn)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args(argv)

    import jax

    from bench import harness, spec
    cells = spec.load_spec(run.ROOT)
    cell = spec.find_cell(cells, args.workload)
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    t_start = T_START
    for seed in args.seeds:
        with planted():
            res = harness.run_cell(cells, cell, seed=seed,
                                   seconds=args.seconds, trace=False,
                                   root=run.ROOT, t_start=t_start)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"]}), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
