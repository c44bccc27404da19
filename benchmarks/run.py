"""Benchmark harness.  One module per paper table/figure:

* bench_snp   — transition-step throughput vs system size (paper §5
  timing): the standard sweep plus the large (bounded-degree), hybrid
  (heavy-tailed power-law, ELL vs hybrid plan) and hybrid-kernel
  (sparse vs sparse_pallas on hybrid plans) tiers
* bench_tree  — full computation-tree exploration (paper §5 run / Fig. 4)
* bench_serve — trace-serving front end: sync/async/mesh (EXPERIMENTS.md
  §Serving)
* bench_lm    — LM substrate step times (framework baseline)

Prints ``name,us_per_call,derived`` CSV; ``--quick`` runs every tier's
reduced CI smoke sweep.  Roofline-based TPU projections are produced by
the dry-run (src/repro/launch/dryrun.py), not here.
"""

import argparse
import sys


def main(quick: bool = False) -> None:
    from repro.launch.compile_cache import enable_compile_cache

    from . import bench_lm, bench_paper_mode, bench_serve, bench_snp, \
        bench_tree

    enable_compile_cache()

    sweeps = [
        lambda: bench_snp.rows(quick),
        lambda: bench_snp.large_rows(quick),
        lambda: bench_snp.hybrid_rows(quick),
        lambda: bench_snp.hybrid_kernel_rows(quick),
        lambda: bench_tree.rows(quick),
        lambda: bench_serve.rows(quick),
        lambda: bench_paper_mode.rows(),
        lambda: bench_lm.rows(),
    ]
    print("name,us_per_call,derived")
    for sweep in sweeps:
        for name, us, derived in sweep():
            print(f"{name},{us:.1f},{derived}")
            sys.stdout.flush()


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweep for CI smoke runs")
    main(quick=ap.parse_args().quick)
