"""Closed loop of ``explore`` calls, each from its own initial
configuration: the system's initial spikes in an order drawn from the
seed for that call, so every call of a run explores another tree with the
same total of spikes, and no call's answer is another's."""

from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import entrykit
from bench.reference import Reference


class Entry(entrykit.Entry):

    def _kw(self):
        mix = self.mix
        return dict(max_steps=mix["max_steps"],
                    frontier_cap=mix["frontier_cap"],
                    visited_cap=mix["visited_cap"],
                    max_branches=mix["max_branches"])

    def init(self, call: int) -> np.ndarray:
        """The initial configuration of call ``call`` (-1: the warm-up)."""
        return self.traffic.rng(6, call + 1).permutation(self.plain.init)

    def setup(self):
        from repro.core import resolve_dedup
        mix = self.mix
        self.be, self.plan, self.comp = entrykit.plan_and_compile(
            self.system, (mix["frontier_cap"], mix["max_branches"]))
        entrykit.log("planner dedup=" + resolve_dedup(
            mix["dedup"], frontier_cap=mix["frontier_cap"],
            visited_cap=mix["visited_cap"], max_branches=mix["max_branches"]))
        with TraceAnnotation("bench.setup.warmup"):
            self._call(-1)
        self.results, self.kept = [], {}   # kept: slot -> (call, archive)

    def _call(self, i: int):
        from repro.core import engine
        with TraceAnnotation("bench.call", call=i):
            return engine.explore(self.comp, init=self.init(i),
                                  backend=self.be, plan=self.plan,
                                  dedup=self.mix["dedup"], **self._kw())

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        calls = found = 0
        while True:
            res = self._call(calls)
            found += res.num_discovered
            self.results.append((res.num_discovered, res.steps,
                                 res.branch_overflow, res.frontier_overflow,
                                 res.visited_overflow))
            slot = self.traffic.reservoir_slot(calls,
                                               self.mix["check_calls"])
            if slot is not None:
                self.kept[slot] = (calls, np.array(res.configs))
            del res
            calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.attempted = calls
        return {"configs_per_s": found / elapsed}

    def waves(self):
        return sum(r[1] for r in self.results)

    def release(self):
        del self.comp

    def check(self) -> entrykit.Checks:
        reference = Reference(self.plain)
        calls_wrong = rows_wrong = 0
        checked = sorted(self.kept.values(), key=lambda kept: kept[0])
        for i, got in checked:
            ref = reference.explore(init=self.init(i), **self._kw())
            want = (len(ref.configs), ref.steps, ref.branch_overflow,
                    ref.frontier_overflow, ref.visited_overflow)
            calls_wrong += int(self.results[i] != want)
            n = min(len(got), len(ref.configs))
            rows_wrong += abs(len(got) - len(ref.configs)) + int(
                np.sum(np.any(got[:n] != ref.configs[:n], axis=1)))
        entrykit.log(f"check: calls {[i for i, _ in checked]} of "
                     f"{self.attempted} "
                     f"compared whole (counts, flags, every archive row)")
        return {"calls_wrong": (calls_wrong, 0),
                "archive_rows_wrong": (rows_wrong, 0)}
