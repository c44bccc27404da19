#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload pl32k.traces --seed 7 --seconds 51 --trace 0

The cell, its configuration, traffic mix and metrics come from
``BENCHMARK.json`` and the files it names under ``bench/``.  With
``--trace 0`` the result reports the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window.  Each number the correctness check compares is printed with its
limit as the last lines of standard error and under ``checks`` in the
result, which is the last line of standard output.

Without a TPU, or with fewer chips than the cell asks for, the run exits
2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# run as a script, sys.path[0] is bench/ itself: replace it by the root
if sys.path and Path(sys.path[0] or ".").resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)
elif str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import repro.core  # noqa: F401  (the system under test must be here)

    from bench import harness, roofline, spec
    cells = spec.load_spec(ROOT)
    cell = spec.find_cell(cells, args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    peaks = roofline.peaks_for(devs[0].device_kind, ROOT)

    result = harness.run_cell(cells, cell, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              root=ROOT, t_start=T_START, peaks=peaks)
    for name, check in result["checks"].items():
        print(f"check {name}={check['value']} limit={check['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
