"""Async trace-service tests: futures drain, flush triggers, error
propagation, service edge cases, and the pluggable runner (mesh path).

The async-mode contract under test (DESIGN.md §4): results are
bit-identical to a synchronous ``drain()`` of the same requests — batching,
padding and flush timing must never change a trajectory — and every failure
mode surfaces through the submit futures, never a crashed drain thread.
"""

import threading
import time

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from repro.core import compile_system, paper_pi, run_trace
from repro.core.generators import nd_chain, random_system
from repro.serve import (SNPTraceService, TraceRequest, make_trace_runner)

PI = paper_pi(True)
TIMEOUT = 120  # generous future timeouts: CI boxes compile slowly


def _mixed_requests():
    chain = nd_chain(4)
    return [
        TraceRequest(PI, steps=5, policy="random", seed=7),
        TraceRequest(PI, steps=11, policy="random", seed=9),   # same group
        TraceRequest(PI, steps=6, policy="first"),
        TraceRequest(chain, steps=4, policy="random", seed=1, max_branches=32),
    ]


def _assert_result_equal(a, b):
    np.testing.assert_array_equal(a.configs, b.configs)
    np.testing.assert_array_equal(a.emissions, b.emissions)
    np.testing.assert_array_equal(a.alive, b.alive)


# ---------------------------------------------------------------------------
# async == sync
# ---------------------------------------------------------------------------

def test_async_results_bit_identical_to_sync_drain():
    reqs = _mixed_requests()
    sync = SNPTraceService(batch_size=8, step_bucket=8)
    tickets = [sync.submit(r) for r in reqs]
    expected = sync.drain()
    with SNPTraceService(batch_size=8, step_bucket=8, async_mode=True,
                         max_delay_ms=20) as svc:
        futs = [svc.submit(r) for r in reqs]
        for t, fut in zip(tickets, futs):
            _assert_result_equal(expected[t], fut.result(timeout=TIMEOUT))


def test_async_submit_returns_future_and_drain_is_rejected():
    with SNPTraceService(async_mode=True, max_delay_ms=1) as svc:
        fut = svc.submit(TraceRequest(PI, steps=3))
        assert hasattr(fut, "result")  # concurrent.futures.Future
        with pytest.raises(RuntimeError, match="sync-mode only"):
            svc.drain()
        fut.result(timeout=TIMEOUT)


# ---------------------------------------------------------------------------
# flush triggers
# ---------------------------------------------------------------------------

def test_full_group_flushes_without_deadline_or_close():
    # deadline far away: only the group-full trigger can flush these
    svc = SNPTraceService(batch_size=4, step_bucket=4, async_mode=True,
                          max_delay_ms=60_000)
    try:
        futs = [svc.submit(TraceRequest(PI, steps=3, policy="random", seed=s))
                for s in range(4)]
        for s, fut in enumerate(futs):
            got = fut.result(timeout=TIMEOUT)
            c, _, _, *_ = run_trace(PI, steps=3, policy="random", seed=s)
            np.testing.assert_array_equal(got.configs, np.asarray(c))
        assert svc.stats()["device_calls"] == 1
    finally:
        svc.close()


def test_partial_group_flushes_at_deadline():
    svc = SNPTraceService(batch_size=64, step_bucket=4, async_mode=True,
                          max_delay_ms=10)
    try:
        fut = svc.submit(TraceRequest(PI, steps=3, policy="random", seed=5))
        got = fut.result(timeout=TIMEOUT)   # << batch_size: deadline fires
        c, e, _, *_ = run_trace(PI, steps=3, policy="random", seed=5)
        np.testing.assert_array_equal(got.configs, np.asarray(c))
        np.testing.assert_array_equal(got.emissions, np.asarray(e))
    finally:
        svc.close()


def test_close_flushes_pending_and_is_idempotent():
    svc = SNPTraceService(batch_size=64, step_bucket=4, async_mode=True,
                          max_delay_ms=60_000)
    futs = [svc.submit(TraceRequest(PI, steps=3, policy="random", seed=s))
            for s in range(3)]
    svc.close()
    assert all(f.done() for f in futs)
    svc.close()  # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(TraceRequest(PI, steps=3))


def test_cancelled_future_does_not_kill_the_drain_thread():
    """fut.cancel() must be skipped at flush time, not written to (writing
    a cancelled Future raises and would kill the drain thread, hanging
    every sibling and later submission)."""
    svc = SNPTraceService(batch_size=4, step_bucket=4, async_mode=True,
                          max_delay_ms=60_000)
    try:
        futs = [svc.submit(TraceRequest(PI, steps=3, policy="random", seed=s))
                for s in range(3)]
        assert futs[1].cancel()
        futs.append(svc.submit(      # fills the group -> flush fires
            TraceRequest(PI, steps=3, policy="random", seed=3)))
        for s in (0, 2, 3):
            got = futs[s].result(timeout=TIMEOUT)   # siblings unharmed
            c, _, _, *_ = run_trace(PI, steps=3, policy="random", seed=s)
            np.testing.assert_array_equal(got.configs, np.asarray(c))
        assert futs[1].cancelled()
        # the thread survived: a later submission still serves
        late = svc.submit(TraceRequest(PI, steps=3, seed=9))
        svc.close()
        assert late.result(timeout=TIMEOUT) is not None
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# error propagation
# ---------------------------------------------------------------------------

def test_flush_error_propagates_into_futures_and_thread_survives():
    calls = {"n": 0}

    def flaky(comp, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("kaboom")
        from repro.core.engine import run_traces
        return run_traces(comp, **kw)

    with SNPTraceService(batch_size=2, async_mode=True, max_delay_ms=1,
                         runner=flaky) as svc:
        bad = svc.submit(TraceRequest(PI, steps=3, seed=1))
        err = bad.exception(timeout=TIMEOUT)
        assert isinstance(err, RuntimeError) and "kaboom" in str(err)
        # the drain thread must survive a failed flush and serve the next
        good = svc.submit(TraceRequest(PI, steps=3, seed=1))
        got = good.result(timeout=TIMEOUT)
        c, _, _, *_ = run_trace(PI, steps=3, seed=1)
        np.testing.assert_array_equal(got.configs, np.asarray(c))


# ---------------------------------------------------------------------------
# service edge cases (sync mode)
# ---------------------------------------------------------------------------

def test_drain_with_zero_pending_returns_empty():
    svc = SNPTraceService(batch_size=4)
    assert svc.drain() == {}
    assert svc.stats()["device_calls"] == 0


@pytest.mark.parametrize("failing_call", [1, 2])
def test_failed_sync_drain_keeps_all_requests_for_retry(failing_call):
    """A runner error in ANY chunk of a drain must not lose requests: the
    whole drain stays pending (all-or-nothing) and a retry serves it all —
    including chunks that already succeeded before the failing one (their
    re-run is deterministic, so nothing changes)."""
    calls = {"n": 0}

    def flaky(comp, **kw):
        calls["n"] += 1
        if calls["n"] == failing_call:
            raise RuntimeError("transient")
        from repro.core.engine import run_traces
        return run_traces(comp, **kw)

    svc = SNPTraceService(batch_size=2, step_bucket=4, runner=flaky)
    tickets = [svc.submit(TraceRequest(PI, steps=3, policy="random", seed=s))
               for s in range(4)]   # 2 chunks of 2
    with pytest.raises(RuntimeError, match="transient"):
        svc.drain()
    assert svc.pending == 4          # nothing was lost, even served chunks
    results = svc.drain()            # retry serves everything
    assert svc.pending == 0
    assert set(results) == set(tickets)
    for s, t in enumerate(tickets):
        c, _, _, *_ = run_trace(PI, steps=3, policy="random", seed=s)
        np.testing.assert_array_equal(results[t].configs, np.asarray(c))


def test_mixed_step_counts_share_one_group_and_one_call():
    svc = SNPTraceService(batch_size=8, step_bucket=16)
    reqs = [TraceRequest(PI, steps=s, policy="random", seed=s)
            for s in (1, 7, 13)]
    tickets = [svc.submit(r) for r in reqs]
    results = svc.drain()
    assert svc.stats()["device_calls"] == 1   # one group, one padded batch
    for t, r in zip(tickets, reqs):
        got = results[t]
        assert got.configs.shape[0] == r.steps   # sliced to the request
        c, e, a, *_ = run_trace(PI, steps=r.steps, policy=r.policy, seed=r.seed)
        np.testing.assert_array_equal(got.configs, np.asarray(c))
        np.testing.assert_array_equal(got.emissions, np.asarray(e))
        np.testing.assert_array_equal(got.alive, np.asarray(a))


def test_compile_cache_evicts_at_cap_and_stays_correct():
    systems = [random_system(6, 2, 0.4, seed=s) for s in range(3)]
    svc = SNPTraceService(batch_size=2, compile_cache_cap=2)
    tickets = [svc.submit(TraceRequest(s, steps=4, seed=1)) for s in systems]
    assert len(svc._compile_cache) == 2          # third compile evicted one
    assert systems[0] not in svc._compile_cache  # FIFO: oldest went first
    # resubmitting the evicted system recompiles under the cap
    t_again = svc.submit(TraceRequest(systems[0], steps=4, seed=1))
    assert len(svc._compile_cache) == 2
    results = svc.drain()
    for sysm, t in zip(systems + [systems[0]], tickets + [t_again]):
        c, _, _, *_ = run_trace(sysm, steps=4, seed=1)
        np.testing.assert_array_equal(results[t].configs, np.asarray(c))


def test_precompiled_systems_bypass_the_compile_cache():
    comp = compile_system(PI)
    svc = SNPTraceService(batch_size=2, compile_cache_cap=1)
    t = svc.submit(TraceRequest(comp, steps=4, seed=2))
    assert len(svc._compile_cache) == 0
    got = svc.drain()[t]
    c, _, _, *_ = run_trace(comp, steps=4, seed=2)
    np.testing.assert_array_equal(got.configs, np.asarray(c))


# ---------------------------------------------------------------------------
# pluggable runner: mesh-sharded flushes
# ---------------------------------------------------------------------------

def test_mesh_runner_service_matches_default_runner():
    mesh = Mesh(np.array(jax.devices()), ("traces",))
    reqs = _mixed_requests()
    plain = SNPTraceService(batch_size=8, step_bucket=8)
    tickets = [plain.submit(r) for r in reqs]
    expected = plain.drain()
    svc = SNPTraceService(batch_size=8, step_bucket=8,
                          runner=make_trace_runner(mesh=mesh))
    tickets2 = [svc.submit(r) for r in reqs]
    results = svc.drain()
    for t, t2 in zip(tickets, tickets2):
        _assert_result_equal(expected[t], results[t2])


def test_make_trace_runner_without_mesh_is_run_traces():
    from repro.core.engine import run_traces
    assert make_trace_runner() is run_traces


def test_async_mesh_service_end_to_end():
    """The launch-path composition: async drain + mesh runner together."""
    mesh = Mesh(np.array(jax.devices()), ("traces",))
    with SNPTraceService(batch_size=4, step_bucket=8, async_mode=True,
                         max_delay_ms=10,
                         runner=make_trace_runner(mesh=mesh)) as svc:
        futs = [svc.submit(TraceRequest(PI, steps=6, policy="random", seed=s))
                for s in range(6)]
        for s, fut in enumerate(futs):
            got = fut.result(timeout=TIMEOUT)
            c, e, _, *_ = run_trace(PI, steps=6, policy="random", seed=s)
            np.testing.assert_array_equal(got.configs, np.asarray(c))
            np.testing.assert_array_equal(got.emissions, np.asarray(e))


def test_submissions_from_many_threads_all_resolve():
    """Concurrent producers: every future resolves to its own trajectory."""
    with SNPTraceService(batch_size=8, step_bucket=8, async_mode=True,
                         max_delay_ms=5) as svc:
        out = {}

        def producer(seed):
            fut = svc.submit(
                TraceRequest(PI, steps=4, policy="random", seed=seed))
            out[seed] = fut.result(timeout=TIMEOUT)

        threads = [threading.Thread(target=producer, args=(s,))
                   for s in range(12)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    for seed, got in out.items():
        c, _, _, *_ = run_trace(PI, steps=4, policy="random", seed=seed)
        np.testing.assert_array_equal(got.configs, np.asarray(c))
