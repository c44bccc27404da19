"""The program's host spans and the service's queue and flush counters.

Spans are ``jax.profiler.TraceAnnotation`` events named ``snp.*``; they are
read back here from a real profiler trace with ``ProfileData``, the way a
reader of the trace sees them.  The counters are ``SNPTraceService.stats()``
keys, checked against a stub runner that sleeps a known time.
"""

import glob
import time

import jax
import numpy as np
import pytest

from repro.core import compile_system, explore, paper_pi, run_traces
from repro.core.distributed import explore_distributed, run_traces_distributed
from repro.core.generators import sorting
from repro.runtime.faults import FaultInjector, FaultPolicy
from repro.serve import SNPTraceService, TraceRequest

PI = paper_pi(True)
SLEEP_S = 0.05


def _spans(trace_dir):
    """``(name, start_ns, end_ns, args)`` of every ``snp.*`` host event."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, int(e.start_ns),
                        int(e.start_ns + e.duration_ns), dict(e.stats))
                       for e in line.events if e.name.startswith("snp."))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(spans, parent):
    """Names of the spans nested in ``parent``, in start order."""
    _, s0, e0, _ = parent
    return [n for n, s, e, _ in spans
            if s0 <= s and e <= e0 and (s, e) != (s0, e0)]


def _only(names, wanted):
    return [n for n in names if n in wanted]


EXPLORE_PHASES = ["snp.plan", "snp.lower", "snp.explore.init",
                  "snp.explore.wait", "snp.explore.readback"]
TRACES_PHASES = ["snp.plan", "snp.lower", "snp.traces.wait"]


@pytest.mark.parametrize("entry", [explore, explore_distributed],
                         ids=["explore", "explore_distributed"])
def test_explore_call_spans_nest_in_order(entry, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        results = [entry(PI, max_steps=6, frontier_cap=16, visited_cap=64,
                         max_branches=8, backend="ref") for _ in range(2)]
    assert results[0].num_discovered == results[1].num_discovered > 1
    spans = _spans(tmp_path)
    calls = [s for s in spans if s[0] == "snp.explore"]
    assert len(calls) == 2
    for call in calls:
        assert _only(_inside(spans, call), EXPLORE_PHASES) == EXPLORE_PHASES


@pytest.mark.parametrize("entry", [run_traces, run_traces_distributed],
                         ids=["run_traces", "run_traces_distributed"])
def test_traces_call_spans_nest_in_order(entry, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            entry(PI, steps=4, seeds=np.arange(5), policy="random",
                  max_branches=8, backend="ref")
    spans = _spans(tmp_path)
    calls = [s for s in spans if s[0] == "snp.traces"]
    assert [c[3] for c in calls] == [{"batch": 5, "init_rows": 1}] * 2
    for call in calls:
        assert _only(_inside(spans, call), TRACES_PHASES) == TRACES_PHASES


@pytest.mark.parametrize("entry", [run_traces, run_traces_distributed],
                         ids=["run_traces", "run_traces_distributed"])
def test_traces_span_counts_init_rows(entry, tmp_path):
    """``snp.traces`` says how many initial configurations a call got:
    one for every trace, or one per trace."""
    rows = np.tile(np.asarray(PI.initial_spikes), (5, 1))
    with jax.profiler.trace(str(tmp_path)):
        for init in (rows[0], rows):
            entry(PI, steps=4, seeds=np.arange(5), policy="random",
                  max_branches=8, backend="ref", init=init)
    calls = [s[3] for s in _spans(tmp_path) if s[0] == "snp.traces"]
    assert calls == [{"batch": 5, "init_rows": 1},
                     {"batch": 5, "init_rows": 5}]


SORT = sorting(12)


@pytest.mark.parametrize("system,backend,successors,fired,slots", [
    (PI, "sparse", 1, "table", 2), (PI, "ref", 8, "rules", 2),
    (SORT, "sparse", 1, "rules", 12)],
    ids=["sparse", "ref", "sparse-rule-heavy"])
def test_traces_wait_span_counts_successors_per_step(
        system, backend, successors, fired, slots, tmp_path):
    """``snp.traces.wait`` says how many successors each trace-step built:
    one where the backend chooses first (``step_chosen``), all ``T`` where
    the scan expands and picks; how the step found each neuron's fired
    rule, in rule space or through the per-neuron table; and the rule
    slots ``R``."""
    with jax.profiler.trace(str(tmp_path)):
        run_traces(system, steps=4, seeds=np.arange(3), policy="random",
                   max_branches=8, backend=backend)
    waits = [s[3] for s in _spans(tmp_path) if s[0] == "snp.traces.wait"]
    assert waits == [{"successors": successors, "fired": fired,
                      "rule_slots": slots}]


def _sleeping_runner(comp, *, steps, seeds, **_):
    """A runner that takes ``SLEEP_S`` and returns all-zero traces."""
    time.sleep(SLEEP_S)
    B, m = len(seeds), comp.num_neurons
    return (np.zeros((B, steps, m), np.int32), np.zeros((B, steps), np.int32),
            np.ones((B, steps), bool), np.zeros((B, steps), bool))


COMP = compile_system(PI)


def _request(seed):
    return TraceRequest(COMP, steps=4, seed=seed)


def _check_flush_counters(s, *, requests, min_wait_s, device_s):
    assert s["queued_requests"] == requests
    assert s["queue_wait_us"] >= min_wait_s * 1e6
    assert s["flush_us"] >= s["flush_device_us"] >= device_s * 1e6
    # the flush's wall time is close to its device call here: the stub's
    # host work is microseconds
    assert s["flush_us"] < (device_s + 1.0) * 1e6


def test_sync_drain_counts_queue_wait_and_flush_time():
    svc = SNPTraceService(batch_size=4, runner=_sleeping_runner)
    for seed in range(3):
        svc.submit(_request(seed))
    time.sleep(0.03)
    assert len(svc.drain()) == 3
    s = svc.stats()
    assert s["device_calls"] == 1
    _check_flush_counters(s, requests=3, min_wait_s=3 * 0.03,
                          device_s=SLEEP_S)


def test_async_flush_counts_queue_wait_and_flush_time():
    with SNPTraceService(batch_size=4, runner=_sleeping_runner,
                         async_mode=True, max_delay_ms=20.0) as svc:
        futs = [svc.submit(_request(seed)) for seed in range(3)]
        for f in futs:
            f.result(timeout=30)
    s = svc.stats()
    assert s["device_calls"] == 1
    # the flush starts no sooner than the oldest request's deadline
    _check_flush_counters(s, requests=3, min_wait_s=0.02, device_s=SLEEP_S)


@pytest.mark.parametrize("async_mode", [False, True], ids=["sync", "async"])
def test_retried_flush_counts_every_device_call(async_mode):
    # call 1 stalls, then fails; the retry after the backoff succeeds
    inj = FaultInjector(fail_calls=(1,), slow_calls={1: 0.04})
    pol = FaultPolicy(max_retries=2, backoff_ms=30.0, jitter=0.0)
    svc = SNPTraceService(batch_size=4, runner=_sleeping_runner,
                          policy=pol, fault_injector=inj,
                          async_mode=async_mode, max_delay_ms=0.0)
    with svc:
        out = svc.submit(_request(1))
        if async_mode:
            out.result(timeout=30)
        else:
            assert list(svc.drain()) == [out]
    s = svc.stats()
    assert s["retries"] == 1 and s["failed_calls"] == 1
    assert s["device_calls"] == 1
    _check_flush_counters(s, requests=1, min_wait_s=0.0,
                          device_s=SLEEP_S + 0.04)
    # the backoff is flush time outside every device call
    assert s["flush_us"] - s["flush_device_us"] >= 0.03 * 1e6


def test_service_spans_carry_their_ids(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        svc = SNPTraceService(batch_size=2, runner=_sleeping_runner)
        tickets = [svc.submit(_request(seed)) for seed in range(3)]
        svc.drain()
    spans = _spans(tmp_path)
    assert [a for n, _, _, a in spans if n == "snp.serve.submit"] == [
        {"ticket": t} for t in tickets]
    flushes = [s for s in spans if s[0] == "snp.serve.flush"]
    assert [f[3] for f in flushes] == [
        {"flush": 0, "first_ticket": 0, "n": 2, "steps": 16},
        {"flush": 1, "first_ticket": 2, "n": 1, "steps": 16}]
    for flush in flushes:
        assert _inside(spans, flush) == ["snp.serve.device",
                                         "snp.serve.readback",
                                         "snp.serve.resolve"]
        device = next(s for s in spans if s[0] == "snp.serve.device"
                      and flush[1] <= s[1] <= flush[2])
        assert device[2] - device[1] >= SLEEP_S * 1e9
