"""``explore.call_host_ms``: mean host time of an ``explore`` call of the
traced window outside its wait for the device loop: the program's
``snp.explore`` span less the ``snp.explore.wait`` span inside it, in ms,
from the run's trace (``bench.program_spans``)."""

from pathlib import Path

from bench import program_spans

ROOT = Path(__file__).resolve().parents[2]


def read(r):
    if r.entry not in ("explore", "explore_distributed"):
        return None
    spans = program_spans.window_spans(ROOT, r.trace["window_s"]) or []
    waits = [s for s in spans if s.name == "snp.explore.wait"]
    host = []
    for call in (s for s in spans if s.name == "snp.explore"):
        inside = sum(w.end_ns - w.start_ns for w in waits
                     if call.start_ns <= w.start_ns
                     and w.end_ns <= call.end_ns)
        host.append(call.end_ns - call.start_ns - inside)
    return sum(host) / len(host) / 1e6 if host else None
