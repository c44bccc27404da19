#!/usr/bin/env python3
"""Find the knee of an open-loop cell: run it at each given arrival rate
in one process and print one JSON line per rate.

    python3 bench/sweep.py --workload pl8k.serve --seconds 20 40 80 160

The knee is the highest rate whose 95th percentile stays near the
lowest rates' while ``served_per_s`` keeps up with the rate.  The cell's
mix file then takes about four fifths of it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402  (bench/run.py: puts the root and src on sys.path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("rates", type=float, nargs="+")
    args = ap.parse_args(argv)

    import jax

    from bench import harness, spec
    cells = spec.load_spec(run.ROOT)
    cell = spec.find_cell(cells, args.workload)
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    for rate in args.rates:
        t0 = time.perf_counter()
        res = harness.run_cell(cells, cell, seed=args.seed,
                               seconds=args.seconds, trace=False,
                               root=run.ROOT, t_start=t0, rate_per_s=rate)
        print(json.dumps({"rate_per_s": rate, **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
