"""From a profiler trace to device busy time, idle gaps and top operations.

The benchmark writes host spans named ``bench.*`` with
``jax.profiler.TraceAnnotation``; the measured window is the span
``bench.window``.  :func:`extract` reads an ``.xplane.pb`` into plain
``(name, start_ns, end_ns)`` tuples: the host spans, and the operations
on each chip's ``XLA Ops`` line.  :func:`reduce` then works on those
tuples alone:

* busy: the union of each chip's operation intervals inside the window,
  averaged over the chips;
* device_ops: the operations' summed self time inside the window by
  name, per chip, longest first (a loop's events enclose those of its
  body, so each event counts its time less that of the events inside it);
* idle_gaps: the stretches of the window in which the first chip runs
  nothing, longest first, each named by the innermost benchmark span
  around its middle.
"""

from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Sequence, Tuple

__all__ = ["extract", "reduce", "latest_xplane"]

Event = Tuple[str, int, int]   # (name, start_ns, end_ns)

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


def latest_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _device_index(plane_name: str, prefix: str):
    tail = plane_name[len(prefix):]
    return int(tail) if plane_name.startswith(prefix) and tail.isdigit() \
        else None


def extract(path: Path, device_prefix: str = "/device:TPU:"
            ) -> Tuple[List[Event], Dict[int, List[Event]]]:
    """``(host_spans, ops_by_chip)`` from the trace file ``path``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    spans: List[Event] = []
    ops: Dict[int, List[Event]] = {}
    for plane in data.planes:
        idx = _device_index(plane.name, device_prefix)
        for line in plane.lines:
            if idx is not None and line.name == OPS_LINE:
                ops.setdefault(idx, []).extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events if e.name.startswith("bench."))
    return spans, ops


def _union(intervals) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _self_times(events: Sequence[Event]) -> Dict[str, int]:
    """Self time by name of possibly nested ``events``."""
    out: Dict[str, int] = {}
    stack: List[list] = []   # [name, end, self_ns]

    def close(item):
        out[item[0]] = out.get(item[0], 0) + item[2]

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    for item in stack:
        close(item)
    return out


def _label(spans: Sequence[Event], t: float) -> str:
    inner = [(e - s, name) for name, s, e in spans
             if s <= t <= e and name != WINDOW]
    return min(inner)[1] if inner else WINDOW


def reduce(spans: Sequence[Event], ops_by_chip: Dict[int, List[Event]],
           top: int = 10) -> dict:
    """Busy and window seconds, top operations and longest idle gaps of
    the ``bench.window`` span."""
    windows = [(s, e) for name, s, e in spans if name == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    w0, w1 = windows[0]
    chips = sorted(ops_by_chip)
    if not chips:
        raise ValueError("the trace has no device operations")
    busy, per_op = [], {}
    first_union = None
    for chip in chips:
        clipped = [(name, max(s, w0), min(e, w1))
                   for name, s, e in ops_by_chip[chip] if e > w0 and s < w1]
        union = _union((s, e) for _, s, e in clipped)
        if first_union is None:
            first_union = union
        busy.append(sum(e - s for s, e in union))
        for name, ns in _self_times(clipped).items():
            per_op[name] = per_op.get(name, 0) + ns
    gaps, reach = [], w0
    for s, e in first_union + [(w1, w1)]:
        if s > reach:
            gaps.append((s - reach, _label(spans, (s + reach) / 2)))
        reach = max(reach, e)
    n = len(chips)
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": [[name, ns / n / 1e9] for name, ns in device_ops],
        "idle_gaps": [[label, ns / 1e9]
                      for ns, label in sorted(gaps, key=lambda g: -g[0])[:top]],
    }
