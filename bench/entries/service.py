"""Open loop of single-trace requests into the async trace service, at
arrival times the mix's arrival process draws from the seed."""

from __future__ import annotations

import threading
import time
from typing import Dict

import numpy as np
from jax.profiler import TraceAnnotation

from bench import entrykit
from bench.reference import Reference


class Entry(entrykit.Entry):

    def setup(self):
        from repro.serve import snp_service
        batch = snp_service.SNPTraceService().batch_size   # its default
        self.be, self.plan, self.comp = entrykit.plan_and_compile(
            self.system, (batch, self.mix["max_branches"]))
        # the service runs the planner's backend on the planner's encoding
        self.svc = snp_service.SNPTraceService(async_mode=True,
                                               backend=self.be)
        with TraceAnnotation("bench.setup.warmup"):
            self.svc.submit(self._request(0)).result(timeout=1200)

    def _request(self, seed: int):
        from repro.serve import snp_service
        mix = self.mix
        return snp_service.TraceRequest(
            self.comp, steps=mix["steps"], policy=mix["policy"],
            seed=int(seed), max_branches=mix["max_branches"])

    def window(self, seconds: float) -> dict:
        offsets, seeds = self.traffic.arrivals(seconds)
        n = len(offsets)
        checked = set(self.traffic.check_requests(n).tolist())
        done_t = np.full(n, np.nan)
        self.late = np.zeros(n)
        self.errors: Dict[int, BaseException] = {}
        self.answers: Dict[int, tuple] = {}
        lock, all_done = threading.Lock(), threading.Event()
        remaining = [n]

        def on_done(j, fut):
            t = time.perf_counter()
            exc = fut.exception()
            with lock:
                done_t[j] = t
                if exc is not None:
                    self.errors[j] = exc
                elif j in checked:
                    r = fut.result()
                    self.answers[j] = tuple(np.array(a) for a in (
                        r.configs, r.emissions, r.alive, r.branch_overflow))
                remaining[0] -= 1
                if not remaining[0]:
                    all_done.set()

        before = self.svc.stats()
        t0 = time.perf_counter()
        for j in range(n):
            due = t0 + offsets[j]
            wait = due - time.perf_counter()
            if wait > 0:
                with TraceAnnotation("bench.sleep"):
                    time.sleep(wait)
            self.late[j] = time.perf_counter() - due
            with TraceAnnotation("bench.call", request=j):
                fut = self.svc.submit(self._request(seeds[j]))
            fut.add_done_callback(lambda f, j=j: on_done(j, f))
        close = t0 + seconds
        with TraceAnnotation("bench.drain"):
            all_done.wait(timeout=max(0.0, close + 60 - time.perf_counter()))
        after = self.svc.stats()
        self.stats = {k: after[k] - before[k] for k in after}
        self.stats["batch_size"] = self.svc.batch_size
        with lock:
            finished = done_t.copy()
            unresolved = int(np.isnan(finished).sum())
            failures = len(self.errors) + unresolved
        self.seeds, self.checked = seeds, sorted(checked)
        self.attempted, self.failed = n, failures
        lat = (finished - (t0 + offsets)) * 1e3
        bad = np.isnan(lat) | np.isin(np.arange(n), list(self.errors))
        lat = np.where(bad, np.inf, lat)
        p95 = float(np.sort(lat)[int(np.ceil(0.95 * n)) - 1])
        last = np.nanmax(finished) if n > unresolved else np.nan
        late_ms = self.late * 1e3
        entrykit.log(
            f"generator late_ms p50={float(np.median(late_ms))!r} "
            f"p95={float(np.percentile(late_ms, 95))!r} "
            f"max={float(late_ms.max())!r} requests={n} failed={failures} "
            f"device_calls={self.stats['device_calls']}")
        return {"serve_p95_ms": p95 if np.isfinite(p95) else None,
                "served_per_s": (n - failures) / (last - t0)
                if np.isfinite(last) else None}

    def counters(self):
        return self.stats

    def release(self):
        self.svc.close()
        del self.svc, self.comp

    def check(self) -> entrykit.Checks:
        have = [j for j in self.checked if j in self.answers]
        wrong = len(self.checked) - len(have)
        if have:
            ref = Reference(self.plain).traces(
                self.seeds[have], self.mix["steps"], self.mix["max_branches"])
        for i, j in enumerate(have):
            got = self.answers[j]
            wrong += int(any(not np.array_equal(g, r[i])
                             for g, r in zip(got, ref)))
        entrykit.log(f"check: {len(have)} of {len(self.checked)} sampled "
                     f"requests compared")
        return {"requests_wrong": (wrong, 0),
                "requests_failed": (self.failed, 0)}
