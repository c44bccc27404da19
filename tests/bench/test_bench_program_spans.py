"""The program's spans in a run's trace (``bench.program_spans``) and the
per-layer readers built on them and on the service's counters."""

import os
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchkit  # noqa: E402  (puts the checkout on sys.path)

from bench import program_spans, spec, tracereduce  # noqa: E402
from bench.program_spans import Span, _Trace  # noqa: E402

MS = 1_000_000
RECORDED = Path(__file__).resolve().parent / "data" / "tpu_spans.xplane.pb"
EXPLORE_METRICS = ("explore.readback_ms", "explore.call_host_ms")
SERVE_METRICS = ("serve.queue_wait_ms", "serve.flush_ms",
                 "serve.flush_host_pct")


@pytest.fixture
def root(tmp_path):
    """A checkout-like root holding the metric readers, whose run output
    (``.bench_out``) the readers search."""
    shutil.copytree(benchkit.ROOT / "bench" / "metrics",
                    tmp_path / "bench" / "metrics")
    return tmp_path


def _trace_file(root, cell="pl8k.explore", stamp="1"):
    path = (root / ".bench_out" / cell / "trace" / "plugins" / "profile"
            / stamp / "host.xplane.pb")
    path.parent.mkdir(parents=True)
    path.write_bytes(b"")
    return path


@pytest.fixture
def synthetic(root, monkeypatch):
    """A trace of a 100-ms window (starting at 10 ms) with two explore
    calls, and the device busy only inside their waits."""
    spans = [
        Span("snp.explore", 5 * MS, 50 * MS, {}),     # starts before it
        Span("snp.plan", 5 * MS, 6 * MS, {}),
        Span("snp.explore.wait", 20 * MS, 40 * MS, {}),
        Span("snp.explore.readback", 40 * MS, 48 * MS, {}),
        Span("snp.explore", 60 * MS, 100 * MS, {}),
        Span("snp.explore.wait", 62 * MS, 90 * MS, {}),
        Span("snp.explore.readback", 90 * MS, 94 * MS, {}),
        Span("snp.serve.submit", 104 * MS, 105 * MS, {"ticket": 7}),
        Span("snp.serve.flush", 200 * MS, 300 * MS, {"flush": 0}),  # after
    ]
    ops = [(20 * MS, 40 * MS), (62 * MS, 90 * MS)]
    trace = _Trace((10 * MS, 110 * MS), spans, ops)
    _trace_file(root)
    monkeypatch.setattr(program_spans, "_load", lambda path: trace)
    return trace


def _explore_readings(window_s):
    return SimpleNamespace(entry="explore", trace={"window_s": window_s},
                           counters={})


def test_window_spans_are_clipped_to_the_window(root, synthetic):
    spans = program_spans.window_spans(root, 0.1)
    assert spans[0] == Span("snp.explore", 10 * MS, 50 * MS, {})
    assert spans[-1] == Span("snp.serve.submit", 104 * MS, 105 * MS,
                             {"ticket": 7})
    assert "snp.serve.flush" not in {s.name for s in spans}
    assert [s.start_ns for s in spans] == sorted(s.start_ns for s in spans)


def test_another_runs_trace_is_not_read(root, synthetic):
    assert program_spans.window_spans(root, 0.1 + 1e-9) is None
    assert program_spans.idle_by_span(root, 0.099) is None
    for name in EXPLORE_METRICS:
        assert spec.metric_reader(root, name)(
            _explore_readings(0.2)) is None


def test_no_trace_and_no_window_read_nothing(root, monkeypatch):
    assert program_spans.latest_trace(root) is None
    assert program_spans.window_spans(root, 0.1) is None
    _trace_file(root)
    monkeypatch.setattr(program_spans, "_load",
                        lambda path: _Trace(None, [], []))
    assert program_spans.window_spans(root, 0.1) is None
    assert program_spans.idle_by_span(root, 0.1) is None


def test_the_newest_trace_is_read(root):
    old = _trace_file(root, "pl8k.serve", "1")
    new = _trace_file(root, "pl8k.explore", "2")
    os.utime(old, ns=(1, 1))
    assert program_spans.latest_trace(root) == new


def test_idle_goes_under_the_innermost_span(root, synthetic):
    # gaps, each named at its middle: [10,20] inside the first call,
    # [40,62] between the calls (middle 51), [90,110] (middle 100, the
    # second call's last instant)
    idle = program_spans.idle_by_span(root, 0.1)
    assert list(idle) == ["snp.explore", "none"]
    assert idle["snp.explore"] == pytest.approx(0.010 + 0.020)
    assert idle["none"] == pytest.approx(0.022)


def test_explore_readers_on_synthetic_spans(root, synthetic):
    r = _explore_readings(0.1)
    # readbacks: 8 ms and 4 ms
    assert spec.metric_reader(root, "explore.readback_ms")(r) == \
        pytest.approx(6.0)
    # calls, clipped: 40 ms less a 20-ms wait, 40 ms less a 28-ms wait
    assert spec.metric_reader(root, "explore.call_host_ms")(r) == \
        pytest.approx(16.0)


def test_explore_readers_read_only_explore(root, synthetic):
    for name in EXPLORE_METRICS:
        for entry in ("service", "run_traces"):
            r = SimpleNamespace(entry=entry, trace={"window_s": 0.1},
                                counters={})
            assert spec.metric_reader(root, name)(r) is None


def test_explore_readers_without_program_spans(root, monkeypatch):
    """A program without ``snp.*`` spans: the readers return nothing."""
    _trace_file(root)
    monkeypatch.setattr(program_spans, "_load",
                        lambda path: _Trace((0, 100 * MS), [], []))
    for name in EXPLORE_METRICS:
        assert spec.metric_reader(root, name)(_explore_readings(0.1)) is None


SERVE_COUNTERS = {"device_calls": 4, "traces_served": 600, "batch_size": 256,
                  "queued_requests": 600, "queue_wait_us": 900_000_000,
                  "flush_us": 12_800_000, "flush_device_us": 12_160_000}


def _serve(counters, entry="service"):
    return SimpleNamespace(entry=entry, trace={"window_s": 51.0},
                           counters=counters)


def test_serve_readers_on_synthetic_counters(root):
    read = {n: spec.metric_reader(root, n) for n in SERVE_METRICS}
    r = _serve(SERVE_COUNTERS)
    assert read["serve.queue_wait_ms"](r) == pytest.approx(1500.0)
    assert read["serve.flush_ms"](r) == pytest.approx(3200.0)
    assert read["serve.flush_host_pct"](r) == pytest.approx(5.0)


@pytest.mark.parametrize("name", SERVE_METRICS)
def test_serve_readers_read_only_the_service(root, name):
    read = spec.metric_reader(root, name)
    assert read(_serve(SERVE_COUNTERS, entry="explore")) is None
    # a service without the queue and flush counters
    older = {k: v for k, v in SERVE_COUNTERS.items()
             if k in ("device_calls", "traces_served", "batch_size")}
    assert read(_serve(older)) is None
    assert read(_serve({k: 0 for k in SERVE_COUNTERS})) is None


@pytest.fixture
def recorded(root):
    """The recorded TPU trace as a run's output, with the window the
    benchmark's reduction reads from it."""
    path = _trace_file(root)
    shutil.copy(RECORDED, path)
    spans, ops = tracereduce.extract(path)
    return tracereduce.reduce(spans, ops)


def _inside(spans, parent):
    return [s.name for s in spans if parent.start_ns <= s.start_ns
            and s.end_ns <= parent.end_ns and s != parent]


def test_recorded_trace_is_small():
    assert RECORDED.stat().st_size < 1 << 20


def test_recorded_explore_calls(root, recorded):
    """Three ``explore`` calls recorded on a TPU v5e, each with its phases
    in order, and the readers' readings of them."""
    spans = program_spans.window_spans(root, recorded["window_s"])
    calls = [s for s in spans if s.name == "snp.explore"]
    assert len(calls) == 3
    phases = ["snp.plan", "snp.lower", "snp.explore.init",
              "snp.explore.wait", "snp.explore.readback"]
    for call in calls:
        assert [n for n in _inside(spans, call) if n in phases] == phases
    r = SimpleNamespace(entry="explore", trace=recorded, counters={})
    readback = spec.metric_reader(root, "explore.readback_ms")(r)
    host = spec.metric_reader(root, "explore.call_host_ms")(r)
    assert 0 < readback < host < max(c.end_ns - c.start_ns
                                     for c in calls) / 1e6


def test_recorded_service_requests(root, recorded):
    """Eight requests to the async service recorded on a TPU v5e: a submit
    span per ticket, flushes that name their first ticket and size, each
    with its device call, readback and resolve."""
    spans = program_spans.window_spans(root, recorded["window_s"])
    tickets = [s.args["ticket"] for s in spans
               if s.name == "snp.serve.submit"]
    assert tickets == list(range(1, 9))
    flushes = [s for s in spans if s.name == "snp.serve.flush"]
    assert sum(f.args["n"] for f in flushes) == 8
    assert flushes[0].args["first_ticket"] == 1
    for flush in flushes:
        # the submits of the load run on another thread, and the device
        # call holds the runner's own spans (run_traces)
        inside = [n for n in _inside(spans, flush)
                  if n.startswith("snp.serve.") and n != "snp.serve.submit"]
        assert inside == ["snp.serve.device", "snp.serve.readback",
                          "snp.serve.resolve", "snp.serve.resolve"]
        assert "snp.traces.wait" in _inside(spans, flush)


def test_recorded_idle_adds_up_to_the_reductions(root, recorded):
    """``idle_by_span`` splits the same idle time the benchmark's reduction
    finds on the one chip."""
    idle = program_spans.idle_by_span(root, recorded["window_s"])
    assert sum(idle.values()) == pytest.approx(
        recorded["window_s"] - recorded["busy_s"], rel=1e-6)
    assert set(idle) <= {"none"} | {s.name for s in program_spans.
                                    window_spans(root, recorded["window_s"])}
