"""The program's own host spans (``snp.*``) in the trace of a traced run.

The program names its layers with ``jax.profiler.TraceAnnotation`` spans
whose names start with ``snp.`` (a call, its plan, lowering, device wait
and readback; the service's submits and flushes).  :mod:`bench.tracereduce`
keeps only the benchmark's ``bench.*`` spans, and the readers of
``bench/metrics/`` get its reduction, so a reader of a program span finds
the run's trace file itself:

* :func:`window_spans` returns the ``snp.*`` events of every host thread,
  with their arguments, clipped to the ``bench.window`` span, from the
  newest ``.xplane.pb`` under ``<root>/.bench_out/*/trace/plugins/profile/``.
  The file is taken only if its ``bench.window`` lasts the run's
  ``window_s`` to the nanosecond, so a reader never reads another run's
  trace; otherwise it returns ``None``.
* :func:`idle_by_span` puts the first chip's idle time in the window under
  the innermost ``snp.*`` span around each idle gap's middle, or
  ``"none"``: where the host was when the device waited.  It is for
  reading a run by hand; no metric is built on it.

Each file is parsed once per process.
"""

from __future__ import annotations

import functools
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from bench.tracereduce import _union

__all__ = ["Span", "latest_trace", "window_spans", "idle_by_span"]

WINDOW = "bench.window"
PREFIX = "snp."
CHIP0 = "/device:TPU:0"
OPS_LINE = "XLA Ops"


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    args: Dict[str, object]   # the TraceAnnotation's keyword arguments


class _Trace(NamedTuple):
    window: Optional[Tuple[int, int]]
    spans: List[Span]                # every snp.* host event
    ops: List[Tuple[int, int]]       # the first chip's operation intervals


def latest_trace(root: Path) -> Optional[Path]:
    found = list(Path(root).glob(
        ".bench_out/*/trace/plugins/profile/*/*.xplane.pb"))
    return max(found, key=lambda p: p.stat().st_mtime_ns) if found else None


@functools.lru_cache(maxsize=4)
def _parse(path: str, mtime_ns: int) -> _Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    window, spans, ops = None, [], []
    for plane in data.planes:
        for line in plane.lines:
            if plane.name == CHIP0 and line.name == OPS_LINE:
                ops.extend((int(e.start_ns), int(e.start_ns + e.duration_ns))
                           for e in line.events)
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    s, t = int(e.start_ns), int(e.start_ns + e.duration_ns)
                    if e.name == WINDOW and window is None:
                        window = (s, t)
                    elif e.name.startswith(PREFIX):
                        spans.append(Span(e.name, s, t, dict(e.stats)))
    return _Trace(window, spans, ops)


def _load(path: Path) -> _Trace:
    return _parse(str(path), path.stat().st_mtime_ns)


def _accepted(root: Path, window_s: float) -> Optional[_Trace]:
    path = latest_trace(root)
    if path is None:
        return None
    trace = _load(path)
    if trace.window is None:
        return None
    w0, w1 = trace.window
    return trace if w1 - w0 == round(window_s * 1e9) else None


def _clip(spans: Sequence[Span], w0: int, w1: int) -> List[Span]:
    return [s._replace(start_ns=max(s.start_ns, w0), end_ns=min(s.end_ns, w1))
            for s in spans if s.end_ns > w0 and s.start_ns < w1]


def window_spans(root: Path, window_s: float) -> Optional[List[Span]]:
    """The ``snp.*`` spans of the run whose window lasted ``window_s``,
    clipped to that window, in start order; ``None`` without that run's
    trace."""
    trace = _accepted(root, window_s)
    if trace is None:
        return None
    return sorted(_clip(trace.spans, *trace.window),
                  key=lambda s: (s.start_ns, -s.end_ns))


def _labels(spans: Sequence[Span], mids: np.ndarray) -> List[str]:
    """The innermost span around each of ``mids`` (the rule of
    ``bench.tracereduce``'s gap labels), or ``"none"``."""
    if not spans:
        return ["none"] * len(mids)
    s = np.array([x.start_ns for x in spans], np.int64)
    e = np.array([x.end_ns for x in spans], np.int64)
    length = (e - s).astype(np.float64)
    out: List[str] = []
    for lo in range(0, len(mids), 512):
        t = mids[lo:lo + 512, None]
        inside = (s[None, :] <= t) & (t <= e[None, :])
        size = np.where(inside, length[None, :], np.inf)
        best = np.argmin(size, axis=1)
        out.extend(spans[j].name if inside[i, j] else "none"
                   for i, j in enumerate(best))
    return out


def idle_by_span(root: Path, window_s: float) -> Optional[Dict[str, float]]:
    """Seconds of the first chip's idle time in the window of the run that
    lasted ``window_s``, by the innermost ``snp.*`` span around each idle
    gap's middle (``"none"`` outside every one), longest first; ``None``
    without that run's trace or without device operations."""
    trace = _accepted(root, window_s)
    if trace is None or not trace.ops:
        return None
    w0, w1 = trace.window
    union = _union((max(s, w0), min(e, w1))
                   for s, e in trace.ops if e > w0 and s < w1)
    gaps, reach = [], w0
    for s, e in union + [(w1, w1)]:
        if s > reach:
            gaps.append((reach, s))
        reach = max(reach, e)
    mids = np.array([(a + b) / 2 for a, b in gaps], np.float64)
    out: Dict[str, float] = {}
    for (a, b), label in zip(gaps, _labels(trace.spans, mids)):
        out[label] = out.get(label, 0.0) + (b - a) / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))
