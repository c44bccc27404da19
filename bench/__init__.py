"""On-chip benchmark of the SNP simulator: one cell per run, driven by
``BENCHMARK.json`` and the data files under ``bench/``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
