"""``explore.readback_ms``: mean duration of the program's
``snp.explore.readback`` span (the archive's transfer to the host and the
result built from it) over the ``explore`` calls of the traced window, in
ms, from the run's trace (``bench.program_spans``)."""

from pathlib import Path

from bench import program_spans

ROOT = Path(__file__).resolve().parents[2]


def read(r):
    if r.entry not in ("explore", "explore_distributed"):
        return None
    spans = program_spans.window_spans(ROOT, r.trace["window_s"]) or []
    ns = [s.end_ns - s.start_ns for s in spans
          if s.name == "snp.explore.readback"]
    return sum(ns) / len(ns) / 1e6 if ns else None
