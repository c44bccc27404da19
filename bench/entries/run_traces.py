"""Closed loop of ``run_traces`` calls, one batch of trace seeds each."""

from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import entrykit
from bench.reference import Reference
from bench.roofline import least_time, traces_call_work


class Entry(entrykit.Entry):

    def setup(self):
        mix = self.mix
        self.be, self.plan, self.comp = entrykit.plan_and_compile(
            self.system, (mix["batch"], mix["max_branches"]))
        with TraceAnnotation("bench.setup.warmup"):
            self._call(-1)
        self.kept = []

    def _call(self, i: int):
        import jax.numpy as jnp
        from repro.core import engine
        mix = self.mix
        seeds = self.traffic.trace_seeds(i)
        with TraceAnnotation("bench.call", call=i):
            out = engine.run_traces(
                self.comp, steps=mix["steps"], seeds=seeds,
                policy=mix["policy"], max_branches=mix["max_branches"],
                backend=self.be, plan=self.plan)
        rows = self.traffic.check_rows(i)
        idx = jnp.asarray(rows, jnp.int32)
        kept = tuple(jnp.take(a, idx, axis=0) for a in out)
        return seeds[rows], kept

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        calls = 0
        while True:
            self.kept.append(self._call(calls))
            calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
        import jax
        jax.block_until_ready([k for _, k in self.kept])
        elapsed = time.perf_counter() - t0
        self.calls = calls
        self.attempted = calls * self.mix["batch"]
        steps = calls * self.mix["batch"] * self.mix["steps"]
        return {"trace_steps_per_s": steps / elapsed}

    def least_time_s(self, peaks):
        ops, nbytes = traces_call_work(
            batch=self.mix["batch"], steps=self.mix["steps"],
            neurons=self.plain.num_neurons, rules=self.plain.num_rules,
            synapses=self.plain.num_synapses)
        t, bound = least_time(ops, nbytes, peaks)
        entrykit.log(f"roofline per call: ops={ops} bytes={nbytes} "
                     f"least_s={t!r} bound={bound}")
        return t * self.calls

    def release(self):
        import jax
        self.kept = [(s, jax.device_get(k)) for s, k in self.kept]
        del self.comp

    def check(self) -> entrykit.Checks:
        seeds = np.concatenate([s for s, _ in self.kept])
        got = [np.concatenate([np.asarray(k[f]) for _, k in self.kept])
               for f in range(4)]
        ref = Reference(self.plain).traces(
            seeds, self.mix["steps"], self.mix["max_branches"])
        wrong = np.zeros(len(seeds), bool)
        for g, r in zip(got, ref):
            wrong |= np.any(g.reshape(len(seeds), -1)
                            != r.reshape(len(seeds), -1), axis=1)
        entrykit.log(f"check: {len(seeds)} traces of {self.calls} calls "
                     f"compared")
        return {"traces_wrong": (int(wrong.sum()), 0)}
