"""Closed loop of ``run_traces`` calls in which every trace starts from its
own initial configuration, drawn from the seed (one stream per call) by
the inputs plugin the mix names (``inputs``: a file of
``bench/generators/`` with ``draw_inputs``, ``output_neurons`` and
``sorted_outputs``, as ``sort.py`` has them).

The check replays 8 rows of each of up to ``check_calls`` calls, kept as
the calls come (reservoir), with the plain reference from those rows'
initial configurations (``traces_wrong``), and compares every trace's
final outputs with its inputs sorted (``outputs_unsorted``; the outputs
are sliced on the device before the readback).  A call that raises
counts its traces as failed, and their outputs as unsorted.
"""

from __future__ import annotations

import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import entrykit, spec
from bench.reference_lean import Reference
from bench.roofline import least_time, traces_call_work


class Entry(entrykit.Entry):

    def setup(self):
        mix = self.mix
        self.inputs = spec.plugin(self.traffic.root, "generators",
                                  mix["inputs"])
        self.outputs = self.inputs.output_neurons(self.plain)
        self.be, self.plan, self.comp = entrykit.plan_and_compile(
            self.system, (mix["batch"], mix["max_branches"]))
        with TraceAnnotation("bench.setup.warmup"):
            # every program the window runs: the scan (one start for every
            # trace runs the program of (B, m) starts), the outputs' slice
            # and the gather of the checked rows
            out = self._call(-1, self.rows(-1)[0])
            jax.block_until_ready([self._outputs(out),
                                   self._picked(out, self.traffic
                                                .check_rows(-1))])
        self.kept, self.finals, self.errors = {}, [], []

    def _outputs(self, out):
        """The final configuration's output neurons, sliced on the device."""
        return out.configs[:, -1, self.outputs]

    @staticmethod
    def _picked(out, pick):
        """Rows ``pick`` of every array of ``out``, on the device."""
        idx = jnp.asarray(pick, jnp.int32)
        return tuple(jnp.take(a, idx, axis=0) for a in out)

    def rows(self, call: int) -> np.ndarray:
        """The initial configurations of call ``call`` (-1: the warm-up)."""
        return self.inputs.draw_inputs(
            self.plain, self.traffic.rng(7, call + 1), self.mix["batch"],
            self.mix["input_max"])

    def _call(self, i: int, init):
        from repro.core import engine
        mix = self.mix
        with TraceAnnotation("bench.call", call=i):
            return engine.run_traces(
                self.comp, steps=mix["steps"],
                seeds=self.traffic.trace_seeds(i), policy=mix["policy"],
                max_branches=mix["max_branches"], backend=self.be,
                plan=self.plan, init=init)

    def window(self, seconds: float) -> dict:
        B = self.mix["batch"]
        t0 = time.perf_counter()
        calls = 0
        while True:
            rows = self.rows(calls)
            try:
                out = self._call(calls, rows)
            except Exception:               # noqa: BLE001 (counted, logged)
                self.failed += B
                self.errors.append(traceback.format_exc())
                self.finals.append((rows, None))
            else:
                self.finals.append((rows, self._outputs(out)))
                slot = self.traffic.reservoir_slot(calls,
                                                   self.mix["check_calls"])
                if slot is not None:
                    pick = self.traffic.check_rows(calls)
                    self.kept[slot] = (
                        self.traffic.trace_seeds(calls)[pick], rows[pick],
                        self._picked(out, pick))
            calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready([f for _, f in self.finals if f is not None])
        elapsed = time.perf_counter() - t0
        if self.errors:
            entrykit.log(f"{len(self.errors)} calls failed, the first:\n"
                         f"{self.errors[0]}")
        self.calls = calls
        self.attempted = calls * B
        steps = (calls * B - self.failed) * self.mix["steps"]
        return {"trace_steps_per_s": steps / elapsed}

    def least_time_s(self, peaks):
        B = self.mix["batch"]
        ops, nbytes = traces_call_work(
            batch=B, steps=self.mix["steps"],
            neurons=self.plain.num_neurons, rules=self.plain.num_rules,
            synapses=self.plain.num_synapses)
        nbytes += B * self.plain.num_neurons * 4     # the per-trace starts
        t, bound = least_time(ops, nbytes, peaks)
        entrykit.log(f"roofline per call: ops={ops} bytes={nbytes} "
                     f"least_s={t!r} bound={bound}")
        return t * (self.calls - len(self.errors))

    def release(self):
        self.finals = [(r, None if f is None else np.asarray(f))
                       for r, f in self.finals]
        self.kept = {s: (seeds, rows, jax.device_get(k))
                     for s, (seeds, rows, k) in self.kept.items()}
        del self.comp

    def check(self) -> entrykit.Checks:
        unsorted = 0
        for rows, final in self.finals:
            if final is None:
                unsorted += len(rows)
                continue
            want = self.inputs.sorted_outputs(self.plain, rows)
            unsorted += int(np.any(final != want, axis=1).sum())
        kept = [self.kept[s] for s in sorted(self.kept)]
        wrong = 0
        if kept:
            seeds = np.concatenate([k[0] for k in kept])
            rows = np.concatenate([k[1] for k in kept])
            got = [np.concatenate([np.asarray(k[2][f]) for k in kept])
                   for f in range(4)]
            ref = Reference(self.plain).traces(
                seeds, self.mix["steps"], self.mix["max_branches"],
                init=rows)
            bad = np.zeros(len(seeds), bool)
            for g, r in zip(got, ref):
                bad |= np.any(g.reshape(len(seeds), -1)
                              != r.reshape(len(seeds), -1), axis=1)
            wrong = int(bad.sum())
        entrykit.log(f"check: {sum(len(k[0]) for k in kept)} traces of "
                     f"{len(kept)} calls replayed, the outputs of "
                     f"{self.attempted} traces of {self.calls} calls "
                     f"compared with their inputs sorted")
        return {"traces_wrong": (wrong, 0),
                "outputs_unsorted": (unsorted, 0)}
