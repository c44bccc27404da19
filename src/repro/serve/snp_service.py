"""Batched SNP trace serving: heterogeneous requests -> padded device batches.

The engine's :func:`~repro.core.engine.run_traces` is the device-side hot
loop (one ``lax.scan``, whole batch through one transition per step, which
builds only the successor each trace keeps where the backend has
``step_chosen``); this module is the host-side front end that makes it a
service.
Architecture notes — batching/bucketing rules, the group key, the async
drain state machine, the failure-domain state machine, and the mesh
sharding layout — live in DESIGN.md §4; the short version:

* **sync mode** (default): :meth:`~SNPTraceService.submit` returns a
  ticket; :meth:`~SNPTraceService.drain` groups compatible requests, pads
  every group to a fixed batch size and step bucket, runs one jitted call
  per padded batch, and returns ``{ticket: TraceResult}``.
* **async mode** (``async_mode=True``): :meth:`submit` returns a
  :class:`concurrent.futures.Future`; a background flush thread fires as
  soon as a group fills a whole batch or the group's oldest request has
  waited ``max_delay_ms``.  Errors raised by a flush propagate into the
  affected futures; :meth:`close` flushes everything still pending and
  joins the thread.
* **failure domains** (``policy=FaultPolicy(...)``): expired-deadline
  requests fail fast with
  :class:`~repro.runtime.faults.DeadlineExceeded` before consuming
  device time; transient flush failures retry with exponential backoff +
  deterministic jitter; exhausted retries walk the encoding-compatible
  backend degrade chain (:mod:`repro.core.failover`), then **bisect the
  chunk** to isolate the poison request — re-running already-good traces
  is free by seed-determinism — so only the culprit's future carries the
  exception; ``max_pending`` admission control rejects at submit.  All
  of it observable through :meth:`stats`.  With ``policy=None`` (the
  default) the historical behavior is preserved exactly: one failure
  fails the whole co-batched flush.

Observability: :meth:`~SNPTraceService.stats` counts calls, failures,
queue wait and flush time, and each request and flush is a host span on
the profiler's clock (``snp.serve.submit``, ``snp.serve.flush`` with
``snp.serve.device``, ``snp.serve.readback`` and ``snp.serve.resolve``
inside; DESIGN.md §4.5), recorded under ``jax.profiler.trace(dir)``.

Per-trace PRNG keys mean padding/batching/flush-timing never changes a
trajectory: the result for a request is bit-identical to a solo
:func:`~repro.core.engine.run_trace` with the same seed, and async results
are bit-identical to a synchronous :meth:`drain` of the same requests —
including across retries and bisection.

The device call is pluggable via ``runner`` (a
:func:`~repro.core.engine.run_traces`-compatible callable) so the same
front end drives the single-device path or the mesh-sharded
:func:`~repro.core.distributed.run_traces_distributed`
(:func:`repro.serve.serve_step.make_trace_runner` builds either);
``fault_injector`` (:class:`~repro.runtime.faults.FaultInjector`) wraps
it with a deterministic fault schedule for tests and the ``serve_fault``
bench tier.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import failover
from repro.core.backend import BackendLike, get_backend, lower_with_backend
from repro.core.engine import run_traces
from repro.core.matrix import CompiledAny, CompiledSparseSNP, is_compiled
from repro.core.plan import SystemPlan
from repro.core.system import SNPSystem
from repro.runtime.faults import (AdmissionRejected, DeadlineExceeded,
                                  FaultInjector, FaultPolicy, InjectedFault)

__all__ = ["TraceRequest", "TraceResult", "SNPTraceService"]


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@dataclass(frozen=True)
class TraceRequest:
    """One trajectory request: which system, how long, how to branch.

    ``deadline_ms`` (serving under a :class:`FaultPolicy` only) bounds
    how long the request may wait before its device call: an expired
    request fails fast with DeadlineExceeded instead of consuming device
    time.  ``None`` falls back to the service policy's default."""

    system: SNPSystem | CompiledAny
    steps: int
    policy: str = "first"       # "first" | "random"
    seed: int = 0
    max_branches: int = 64
    deadline_ms: Optional[float] = None

    def __post_init__(self) -> None:
        if self.steps < 1:
            raise ValueError(f"steps must be >= 1, got {self.steps}")
        if self.policy not in ("first", "random"):
            raise ValueError(f"unknown policy {self.policy!r}")
        if self.deadline_ms is not None and self.deadline_ms < 0:
            raise ValueError("deadline_ms must be >= 0")


@dataclass(frozen=True)
class TraceResult:
    """One served trajectory, unpadded to the request's ``steps``.

    ``branch_overflow[t]`` flags that step t had more than the request's
    ``max_branches`` successors (only the first T were candidates) — the
    engine's truncation flag surfaced per trace, never silent."""

    configs: np.ndarray     # (steps, m) int32
    emissions: np.ndarray   # (steps,) int32 — the output spike train
    alive: np.ndarray       # (steps,) bool
    branch_overflow: np.ndarray = field(
        default_factory=lambda: np.zeros((0,), bool))  # (steps,) bool

    @property
    def truncated(self) -> bool:
        """True when any step's branching was truncated to max_branches."""
        return bool(np.any(self.branch_overflow))


_STAT_KEYS = ("device_calls", "traces_served", "retries", "bisections",
              "degraded", "deadline_exceeded", "rejected", "failed_calls",
              "failed_requests", "branch_overflow_traces",
              "queued_requests", "queue_wait_us", "flush_us",
              "flush_device_us")


class SNPTraceService:
    """Submit/drain batching front end over :func:`run_traces`.

    ``batch_size`` is the fixed device batch: every flush runs exactly this
    many traces (padded), so a service with ``batch_size=256`` serves a
    256-request burst in **one** jitted call.  ``step_bucket`` quantizes
    requested step counts upward so distinct ``steps`` values don't each
    compile a fresh scan.

    ``runner`` overrides the device call (default
    :func:`~repro.core.engine.run_traces`); pass
    :func:`repro.serve.serve_step.make_trace_runner`'s mesh-backed runner
    to shard every flush over devices.  ``async_mode`` switches
    :meth:`submit` to return futures drained by a background flush thread
    (see the module docstring and DESIGN.md §4).

    ``policy`` (:class:`~repro.runtime.faults.FaultPolicy`) turns on the
    failure-domain machinery — deadlines, retry/backoff, degrade, bisect,
    admission control (DESIGN.md §4.4); ``None`` keeps the historical
    fail-the-whole-flush behavior.  ``fault_injector`` wraps the runner
    and compile path with a deterministic fault schedule.
    """

    def __init__(self, *, batch_size: int = 256, step_bucket: int = 16,
                 backend: BackendLike = "ref",
                 max_steps: Optional[int] = None,
                 runner: Optional[Callable] = None,
                 compile_cache_cap: int = 64,
                 async_mode: bool = False,
                 max_delay_ms: float = 10.0,
                 policy: Optional[FaultPolicy] = None,
                 fault_injector: Optional[FaultInjector] = None) -> None:
        if batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        if step_bucket < 1:
            raise ValueError("step_bucket must be >= 1")
        if compile_cache_cap < 1:
            raise ValueError("compile_cache_cap must be >= 1")
        if max_delay_ms < 0:
            raise ValueError("max_delay_ms must be >= 0")
        self.batch_size = batch_size
        self.step_bucket = step_bucket
        self.max_steps = max_steps
        self.backend = get_backend(backend)
        self.policy = policy
        self.fault_injector = fault_injector
        runner = run_traces if runner is None else runner
        if fault_injector is not None:
            runner = fault_injector.runner(runner)
        self.runner = runner
        self.async_mode = async_mode
        self.max_delay_ms = max_delay_ms
        self._stats: Dict[str, int] = {k: 0 for k in _STAT_KEYS}
        #: sync-mode only, policy set: {ticket: exception} of the requests
        #: the last drain() definitively failed (replaced per drain)
        self.last_failures: Dict[int, BaseException] = {}
        self._tickets = itertools.count()
        self._flush_ids = itertools.count()
        self._pending: Dict[int, TraceRequest] = {}
        self._comp_of: Dict[int, CompiledAny] = {}   # ticket -> compiled
        # compile memoization, keyed by SNPSystem (structural equality);
        # bounded so a long-lived service can't grow without limit.  The
        # service backend is fixed at construction, so one cache per
        # service is one cache per encoding.
        self._compile_cache: Dict[SNPSystem, CompiledAny] = {}
        self._compile_cache_cap = compile_cache_cap
        # degraded-backend lowering memoization ({backend name: comp id: comp})
        self._degraded_cache: Dict[Tuple[str, int], CompiledAny] = {}
        # async state (all mutated under the one condition's lock)
        self._cv = threading.Condition()
        self._futures: Dict[int, Future] = {}
        self._submit_t: Dict[int, float] = {}
        self._closed = False
        self._thread: Optional[threading.Thread] = None
        if async_mode:
            self._thread = threading.Thread(
                target=self._drain_loop, name="snp-service-drain", daemon=True)
            self._thread.start()

    # -- observability -----------------------------------------------------

    def _count(self, key: str, n: int = 1) -> None:
        with self._cv:
            self._stats[key] += n

    def stats(self) -> Dict[str, int]:
        """Snapshot of the service counters, all ints:

        * ``device_calls`` (runner calls that returned) and
          ``traces_served``;
        * the failure-domain counters ``retries``, ``bisections``,
          ``degraded``, ``deadline_exceeded``, ``rejected``,
          ``failed_calls``, ``failed_requests`` and
          ``branch_overflow_traces``;
        * the queue and flush clocks (``time.monotonic``, microseconds):
          ``queued_requests`` (requests taken into a flush),
          ``queue_wait_us`` (their summed wait from submit to the start of
          their flush), ``flush_us`` (summed flush wall time, from the
          start to the last result or future resolved) and
          ``flush_device_us`` (summed time inside the runner, failed and
          retried calls included).

        A difference of two snapshots covers the work between them: e.g.
        ``queue_wait_us / queued_requests`` is the mean queue wait and
        ``flush_us / device_calls`` the mean flush without faults."""
        with self._cv:
            return dict(self._stats)

    # -- submission --------------------------------------------------------

    def _compile(self, request: TraceRequest) -> CompiledAny:
        if is_compiled(request.system):
            return request.system
        # SNPSystem is a frozen dataclass: equal systems (even distinct
        # objects) share one compilation and one batch group.  The
        # backend owns the lowering (dense vs. sparse encoding).  The
        # compile itself runs *outside* the lock — it may be arbitrarily
        # expensive (StepBackend.compile contract) and must not stall the
        # drain thread past other groups' max_delay_ms deadlines.  Two
        # racing submitters may both compile; first insert wins and both
        # use it (compiles of equal systems are semantically identical),
        # keeping one batch group per system.
        with self._cv:
            comp = self._compile_cache.get(request.system)
        if comp is None:
            if self.fault_injector is not None:
                self.fault_injector.on_compile(request.system)
            comp = self.backend.compile(request.system)
            with self._cv:
                if request.system not in self._compile_cache:
                    while len(self._compile_cache) >= self._compile_cache_cap:
                        self._compile_cache.pop(
                            next(iter(self._compile_cache)))
                    self._compile_cache[request.system] = comp
                comp = self._compile_cache[request.system]
        return comp

    def submit(self, request: TraceRequest):
        """Queue a request.

        Sync mode: returns an ``int`` ticket to look up in :meth:`drain`.
        Async mode: returns a :class:`~concurrent.futures.Future` resolving
        to the request's :class:`TraceResult` (or the flush's exception).
        Under a policy with ``max_pending``, raises
        :class:`~repro.runtime.faults.AdmissionRejected` when the queue
        is full — backpressure at the door, not an unbounded queue.
        """
        if self.max_steps is not None and request.steps > self.max_steps:
            raise ValueError(
                f"steps {request.steps} exceeds service max_steps "
                f"{self.max_steps}")
        pol = self.policy
        if pol is not None and pol.max_pending is not None:
            with self._cv:
                if len(self._pending) >= pol.max_pending:
                    self._stats["rejected"] += 1
                    raise AdmissionRejected(
                        f"{len(self._pending)} requests pending >= "
                        f"max_pending={pol.max_pending}")
        comp = self._compile(request)   # outside the lock: may be expensive
        with self._cv:
            if self._closed:
                raise RuntimeError("service is closed")
            if pol is not None and pol.max_pending is not None \
                    and len(self._pending) >= pol.max_pending:
                self._stats["rejected"] += 1
                raise AdmissionRejected(
                    f"{len(self._pending)} requests pending >= "
                    f"max_pending={pol.max_pending}")
            ticket = next(self._tickets)
            with TraceAnnotation("snp.serve.submit", ticket=ticket):
                self._pending[ticket] = request
                self._comp_of[ticket] = comp
                self._submit_t[ticket] = time.monotonic()
                if not self.async_mode:
                    return ticket
                fut: Future = Future()
                self._futures[ticket] = fut
                self._cv.notify_all()
                return fut

    @property
    def pending(self) -> int:
        with self._cv:
            return len(self._pending)

    # -- grouping ----------------------------------------------------------

    def _group_key(self, ticket: int) -> Tuple:
        r = self._pending[ticket]
        return (id(self._comp_of[ticket]), r.policy, r.max_branches)

    def _groups(self) -> Dict[Tuple, List[int]]:
        by_group: Dict[Tuple, List[int]] = {}
        for ticket in sorted(self._pending):
            by_group.setdefault(self._group_key(ticket), []).append(ticket)
        return by_group

    def _take(self, tickets: List[int]) -> List[TraceRequest]:
        """Remove ``tickets`` from the pending maps (lock held)."""
        reqs = [self._pending.pop(t) for t in tickets]
        for t in tickets:
            self._comp_of.pop(t)
            self._submit_t.pop(t, None)
        return reqs

    # -- synchronous draining ----------------------------------------------

    def drain(self) -> Dict[int, TraceResult]:
        """Serve every pending request; returns ``{ticket: TraceResult}``.

        One jitted :func:`run_traces` call per (group, full-batch chunk).
        Sync mode only — in async mode the background thread drains and
        results arrive through the submit futures.

        Without a policy the drain is all-or-nothing: on any failure the
        whole drain stays pending and the exception raises, so a retry
        drain() re-serves everything.  With a :class:`FaultPolicy` the
        recovery machinery (deadline / retry / degrade / bisect) runs
        per chunk; requests it definitively fails are *popped* and their
        exceptions recorded in :attr:`last_failures` (and the
        ``failed_requests`` counter) while every other ticket's result
        returns — a poison request can no longer wedge the queue.
        """
        if self.async_mode:
            raise RuntimeError(
                "drain() is sync-mode only; async results arrive via the "
                "futures returned by submit()")
        results: Dict[int, TraceResult] = {}
        with self._cv:
            batches = []
            for (_, policy, max_branches), tickets in self._groups().items():
                comp = self._comp_of[tickets[0]]
                for lo in range(0, len(tickets), self.batch_size):
                    chunk = tickets[lo:lo + self.batch_size]
                    batches.append((comp, policy, max_branches, chunk,
                                    [self._pending[t] for t in chunk]))
            born = dict(self._submit_t)
        if self.policy is None:
            for comp, policy, max_branches, chunk, reqs in batches:
                with self._flush(chunk, reqs, born):
                    results.update(self._run_batch(
                        comp, policy, max_branches, chunk, reqs))
            # all-or-nothing: requests leave the pending maps only after
            # every batch served.  If any runner call raises, the whole
            # drain stays pending and a retry drain() re-serves it —
            # re-running a chunk that already succeeded is free of harm
            # (traces are deterministic functions of their seeds), whereas
            # popping per chunk would lose served results when a later
            # chunk fails.
            with self._cv:
                for _, _, _, chunk, _ in batches:
                    self._take(chunk)
            return results
        failures: Dict[int, BaseException] = {}
        for comp, policy, max_branches, chunk, reqs in batches:
            with self._flush(chunk, reqs, born):
                res, fail = self._serve_chunk(comp, policy, max_branches,
                                              chunk, reqs, born)
            results.update(res)
            failures.update(fail)
        # under a policy every ticket was definitively resolved — served,
        # deadline-expired, or isolated-and-failed — so everything pops
        with self._cv:
            for _, _, _, chunk, _ in batches:
                self._take(chunk)
        self.last_failures = failures
        return results

    # -- the device call ---------------------------------------------------

    def _steps(self, reqs: List[TraceRequest]) -> int:
        # submit() enforces steps <= max_steps, so no clamp is needed here
        return _round_up(max(r.steps for r in reqs), self.step_bucket)

    @contextlib.contextmanager
    def _flush(self, tickets: List[int], reqs: List[TraceRequest],
               born: Dict[int, float]) -> Iterator[None]:
        """One chunk's flush: the ``snp.serve.flush`` span, and its share
        of the queue and flush counters, whether or not it succeeds."""
        start = time.monotonic()
        with TraceAnnotation("snp.serve.flush", flush=next(self._flush_ids),
                             first_ticket=tickets[0], n=len(tickets),
                             steps=self._steps(reqs)):
            try:
                yield
            finally:
                wait = sum(start - born[t] for t in tickets)
                with self._cv:
                    self._stats["queued_requests"] += len(tickets)
                    self._stats["queue_wait_us"] += round(wait * 1e6)
                    self._stats["flush_us"] += round(
                        (time.monotonic() - start) * 1e6)

    def _run_batch(self, comp: CompiledAny, policy: str, max_branches: int,
                   tickets: List[int], reqs: List[TraceRequest],
                   backend=None) -> Dict[int, TraceResult]:
        backend = self.backend if backend is None else backend
        steps = self._steps(reqs)
        seeds = np.zeros((self.batch_size,), np.uint32)   # dummy pad: seed 0
        seeds[:len(reqs)] = [r.seed for r in reqs]

        start = time.monotonic()
        try:
            with TraceAnnotation("snp.serve.device"):
                out = self.runner(
                    comp, steps=steps, seeds=seeds, policy=policy,
                    max_branches=max_branches, backend=backend)
        finally:
            self._count("flush_device_us",
                        round((time.monotonic() - start) * 1e6))
        if len(out) == 4:
            cfgs, emis, alive, ovf = out
        else:   # third-party runner predating the branch_overflow field
            cfgs, emis, alive = out
            ovf = np.zeros(np.asarray(alive).shape, bool)
        self._count("device_calls")
        self._count("traces_served", len(reqs))

        with TraceAnnotation("snp.serve.readback"):
            cfgs, emis, alive, ovf = (np.asarray(cfgs), np.asarray(emis),
                                      np.asarray(alive), np.asarray(ovf))
        with TraceAnnotation("snp.serve.resolve"):
            results = {
                t: TraceResult(configs=cfgs[i, :r.steps],
                               emissions=emis[i, :r.steps],
                               alive=alive[i, :r.steps],
                               branch_overflow=ovf[i, :r.steps])
                for i, (t, r) in enumerate(zip(tickets, reqs))
            }
            truncated = sum(1 for r in results.values() if r.truncated)
        if truncated:
            self._count("branch_overflow_traces", truncated)
        return results

    # -- failure-domain recovery (policy set) ------------------------------

    def _degraded_comps(self, comp: CompiledAny):
        """Yield ``(backend, lowered comp)`` down the encoding-compatible
        degrade chain for this service's backend (DESIGN.md §4.4).  The
        chunk's compiled encoding is reused as-is — degradation swaps the
        *step implementation*, never the encoding — so re-lowering is
        cheap and memoized."""
        if isinstance(comp, CompiledSparseSNP):
            enc = "hybrid" if comp.is_hybrid else "ell"
        else:
            enc = "dense"
        for cand, plan in failover.degrade_candidates(
                self.backend, SystemPlan(encoding=enc)):
            key = (cand.name, id(comp))
            try:
                with self._cv:
                    lowered = self._degraded_cache.get(key)
                if lowered is None:
                    lowered = lower_with_backend(cand, comp, plan)
                    with self._cv:
                        self._degraded_cache[key] = lowered
            except Exception:
                continue    # candidate can't lower this encoding: skip
            yield cand, lowered

    def _serve_chunk(self, comp: CompiledAny, policy: str, max_branches: int,
                     tickets: List[int], reqs: List[TraceRequest],
                     born: Dict[int, float], depth: int = 0,
                     ) -> Tuple[Dict[int, TraceResult],
                                Dict[int, BaseException]]:
        """Serve one chunk under the failure-domain state machine
        (DESIGN.md §4.4): deadline-filter -> run -> retry/backoff ->
        degrade -> bisect -> fail the irreducible request with the *last*
        underlying exception.  Returns ``(results, failures)``; every
        input ticket lands in exactly one of the two."""
        pol = self.policy
        results: Dict[int, TraceResult] = {}
        failures: Dict[int, BaseException] = {}

        # fail fast on expired deadlines: no device time for dead requests
        now = time.monotonic()
        live_t, live_r = [], []
        for t, r in zip(tickets, reqs):
            limit = r.deadline_ms if r.deadline_ms is not None \
                else pol.deadline_ms
            t0 = born.get(t)
            if limit is not None and t0 is not None \
                    and (now - t0) * 1e3 > limit:
                failures[t] = DeadlineExceeded(
                    f"request waited {(now - t0) * 1e3:.1f} ms "
                    f"> deadline {limit:g} ms")
                self._count("deadline_exceeded")
                continue
            live_t.append(t)
            live_r.append(r)
        if not live_t:
            return results, failures

        # retry with exponential backoff + deterministic jitter.  Bisect
        # halves (depth > 0) run once: the parent already burned the
        # retry budget, and a persistent fault never clears by retry.
        retries = pol.max_retries if depth == 0 else 0
        last: Optional[BaseException] = None
        for attempt in range(retries + 1):
            if attempt:
                self._count("retries")
                time.sleep(pol.backoff_s(attempt - 1, token=live_t[0]))
            try:
                results.update(self._run_batch(
                    comp, policy, max_branches, live_t, live_r))
                return results, failures
            except Exception as e:
                last = e
                self._count("failed_calls")
                if isinstance(e, InjectedFault) and type(e) is not \
                        InjectedFault and attempt == 0:
                    # PoisonError subclass: persistent by contract —
                    # retries never clear it, go isolate instead
                    break

        # whole-chunk backend degradation (encoding-compatible chain).
        # Injected faults model node loss, not a broken backend — a
        # degraded backend would re-run the same schedule and fail again.
        if pol.degrade and depth == 0 \
                and not isinstance(last, InjectedFault):
            for cand, lowered in self._degraded_comps(comp):
                try:
                    results.update(self._run_batch(
                        comp=lowered, policy=policy,
                        max_branches=max_branches, tickets=live_t,
                        reqs=live_r, backend=cand))
                except Exception as e:
                    last = e
                    self._count("failed_calls")
                    continue
                self._count("degraded")
                failover.record_degradation(
                    self.backend.name, cand.name, "serve", last)
                return results, failures

        # bisect: split the chunk to isolate the poison request — the good
        # half re-runs for free (seed-determinism), the bad half narrows
        if pol.bisect and len(live_t) > 1:
            self._count("bisections")
            mid = len(live_t) // 2
            for lo, hi in ((0, mid), (mid, len(live_t))):
                res, fail = self._serve_chunk(
                    comp, policy, max_branches, live_t[lo:hi],
                    live_r[lo:hi], born, depth + 1)
                results.update(res)
                failures.update(fail)
            return results, failures

        # irreducible: the request itself is the failure domain
        for t in live_t:
            failures[t] = last
            self._count("failed_requests")
        return results, failures

    # -- asynchronous draining ---------------------------------------------
    #
    # State machine (DESIGN.md §4): a group is FILLING until either
    # (a) it holds >= batch_size requests -> its full chunks flush now, or
    # (b) it's oldest request is older than max_delay_ms -> the whole group
    #     (one padded partial chunk) flushes now, or
    # (c) the service closes -> everything flushes.
    # The background thread sleeps until the earliest deadline or a submit
    # notification, whichever comes first.  _take_ready and _next_deadline
    # compare time through the *same* `submit_t + delay` expression, so a
    # group is overdue iff its remaining wait is exactly 0.0 — the thread
    # can never be told "nothing to flush" and "wait 0 seconds" at once
    # (the max_delay_ms=0 busy-spin this once risked).

    def _take_ready(self, now: float, flush_all: bool) -> List[Tuple]:
        """Pop every chunk that must flush now (lock held)."""
        delay = self.max_delay_ms / 1e3
        batches: List[Tuple] = []
        for (_, policy, max_branches), tickets in self._groups().items():
            comp = self._comp_of[tickets[0]]
            take: List[int] = []
            if flush_all or now >= self._submit_t[tickets[0]] + delay:
                take = tickets
            elif len(tickets) >= self.batch_size:
                n_full = (len(tickets) // self.batch_size) * self.batch_size
                take = tickets[:n_full]
            for lo in range(0, len(take), self.batch_size):
                chunk = take[lo:lo + self.batch_size]
                futs = [self._futures.pop(t) for t in chunk]
                born = {t: self._submit_t[t] for t in chunk}
                batches.append((comp, policy, max_branches, chunk,
                                self._take(chunk), futs, born))
        return batches

    def _next_deadline(self, now: float) -> Optional[float]:
        """Seconds until the earliest group deadline (lock held)."""
        if not self._submit_t:
            return None
        oldest = min(self._submit_t.values())
        return max(0.0, oldest + self.max_delay_ms / 1e3 - now)

    def _drain_loop(self) -> None:
        while True:
            with self._cv:
                now = time.monotonic()
                batches = self._take_ready(now, flush_all=self._closed)
                if not batches:
                    if self._closed:
                        return
                    timeout = self._next_deadline(now)
                    if timeout is not None and timeout <= 0:
                        # unreachable by construction (see the state-
                        # machine note above), but never wait(<=0): loop
                        # and re-take instead of spinning
                        continue
                    self._cv.wait(timeout=timeout)
                    continue
            for comp, policy, max_branches, tickets, reqs, futs, born \
                    in batches:
                with self._flush(tickets, reqs, born):
                    self._flush_into(futs, comp, policy, max_branches,
                                     tickets, reqs, born)

    def _flush_into(self, futs: List[Future], comp: CompiledAny, policy: str,
                    max_branches: int, tickets: List[int],
                    reqs: List[TraceRequest], born: Dict[int, float]) -> None:
        """Serve one taken chunk and resolve its futures."""
        # claim RUNNING state first: a caller-cancelled future must be
        # skipped, not written to (set_result on a cancelled Future raises
        # and would kill the drain thread); once RUNNING, cancel() can no
        # longer win the race.
        live = [fut.set_running_or_notify_cancel() for fut in futs]
        try:
            if self.policy is None:
                results = self._run_batch(
                    comp, policy, max_branches, tickets, reqs)
                failures: Dict[int, BaseException] = {}
            else:
                results, failures = self._serve_chunk(
                    comp, policy, max_branches, tickets, reqs, born)
        except BaseException as e:   # propagate into the futures
            results, failures = {}, {t: e for t in tickets}
        with TraceAnnotation("snp.serve.resolve"):
            for t, fut, ok in zip(tickets, futs, live):
                if not ok:
                    continue   # cancelled before the flush claimed it
                if t in results:
                    fut.set_result(results[t])
                else:
                    fut.set_exception(failures.get(t, RuntimeError(
                        f"request {t} left unserved by recovery")))

    # -- lifecycle ---------------------------------------------------------

    def close(self) -> None:
        """Flush everything pending and stop the drain thread (async mode);
        idempotent, and a no-op beyond marking closed in sync mode."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def __enter__(self) -> "SNPTraceService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
