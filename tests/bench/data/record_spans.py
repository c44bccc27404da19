#!/usr/bin/env python3
"""Record ``tpu_spans.xplane.pb``, a small trace of the program's spans.

    python3 tests/bench/data/record_spans.py \
        tests/bench/data/tpu_spans.xplane.pb

Run on one TPU chip.  Inside one ``bench.window`` span: three ``explore``
calls and eight requests to the async trace service, all on a 128-neuron
power-law system with in-degree at most 8 (few device operations a step,
so the file stays small), with sleeps between them so the chip idles
under known spans.  Every shape is warmed up before the profiler starts,
so nothing compiles in the trace, and the Python tracer is off (the
spans are the program's own ``TraceAnnotation``s).  The ``/host:metadata``
plane, the compiled programs' HLO that a TPU trace carries whatever the
profiler options say, is dropped from the file: no reader of the trace
uses it, and without it the file stays under 1 MB.
"""

from __future__ import annotations

import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT / "src"))

EXPLORE = dict(max_steps=4, frontier_cap=32, visited_cap=256,
               max_branches=8, backend="sparse")


def _varint(data: bytes, i: int):
    shift = value = 0
    while True:
        byte = data[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(data: bytes):
    """``(field, wire type, payload, raw bytes)`` of a protobuf message."""
    i = 0
    while i < len(data):
        start = i
        key, i = _varint(data, i)
        field, wire = key >> 3, key & 7
        payload = None
        if wire == 0:
            _, i = _varint(data, i)
        elif wire == 1:
            i += 8
        elif wire == 2:
            n, i = _varint(data, i)
            payload, i = data[i:i + n], i + n
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield field, wire, payload, data[start:i]


def drop_planes(xspace: bytes, names) -> bytes:
    """The serialized ``XSpace`` without the planes named in ``names``
    (``XSpace.planes`` is field 1, ``XPlane.name`` field 2)."""
    out = bytearray()
    for field, wire, payload, raw in _fields(xspace):
        if field == 1 and wire == 2 and any(
                f == 2 and p.decode() in names
                for f, w, p, _ in _fields(payload) if w == 2):
            continue
        out += raw
    return bytes(out)


def _serve(svc, request, seeds):
    futs = []
    for seed in seeds:
        futs.append(svc.submit(request(seed)))
        time.sleep(0.002)
    return [f.result(timeout=600) for f in futs]


def main(out: Path) -> int:
    import jax
    from jax.profiler import TraceAnnotation

    from repro.core import explore
    from repro.core.backend import get_backend
    from repro.core.generators import power_law
    from repro.serve import SNPTraceService, TraceRequest

    if jax.devices()[0].platform != "tpu":
        print("record_spans: needs a TPU", file=sys.stderr)
        return 2
    system = power_law(128, seed=2, max_in=8)
    comp = get_backend("sparse").compile(system)

    def request(seed):
        return TraceRequest(comp, steps=8, policy="random", seed=seed,
                            max_branches=8)

    with SNPTraceService(batch_size=8, backend="sparse", async_mode=True,
                         max_delay_ms=5.0) as svc:
        explore(comp, **EXPLORE)                 # warm-up, untraced
        _serve(svc, request, [100])
        with tempfile.TemporaryDirectory() as tmp:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            with jax.profiler.trace(tmp, profiler_options=options):
                with TraceAnnotation("bench.window"):
                    for _ in range(3):
                        explore(comp, **EXPLORE)
                        time.sleep(0.005)
                    _serve(svc, request, range(1, 9))
            found, = Path(tmp).glob("plugins/profile/*/*.xplane.pb")
            out.write_bytes(drop_planes(found.read_bytes(),
                                        {"/host:metadata"}))
    print(f"{out}: {out.stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1])))
