"""Closed loop of neuron-sharded ``explore_distributed`` calls over the
cell's chips, each from its own initial configuration, as ``explore``
draws them (``bench/entries/explore.py``, whose window and check this
entry keeps).

The plan is ``neuron_axis(shards, encoding="ell")``: the per-shard
encodings are ELL only (``compile_sharded`` refuses the hybrid encoding
the planner picks for one chip), and the partition is contiguous, whose
branch numbering keeps neuron 0 the most significant digit, so the
archive keeps the reference's discovery order row by row.  The backend
is the planner's pick for the sharded shape.  The mix's ``frontier_cap``
and ``visited_cap`` are global: the frontier of the sharded scheme is
global, its visited capacity per shard, so each call gets
``visited_cap // shards``."""

from __future__ import annotations

from pathlib import Path

from jax.profiler import TraceAnnotation

from bench import entrykit
from bench.spec import plugin

Explore = plugin(Path(__file__).resolve().parents[2], "entries",
                 "explore").Entry


class Entry(Explore):

    def setup(self):
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from repro.core.backend import lower_with_backend, resolve_entry_info
        from repro.core.plan import SystemPlan, compile_sharded
        from repro.sharding import neuron_axis
        mix = self.mix
        shards = mix["shards"]
        devices = jax.devices()
        if len(devices) < shards:
            raise RuntimeError(f"{shards} shards need {shards} devices; "
                               f"JAX found {len(devices)}")
        self.mesh = Mesh(np.array(devices[:shards]), ("x",))
        with TraceAnnotation("bench.setup.plan"):
            self.be, planned, _ = resolve_entry_info(
                self.system, None, SystemPlan(num_shards=shards),
                workload=(mix["frontier_cap"], mix["max_branches"]))
            self.plan = neuron_axis(shards, encoding="ell")
        with TraceAnnotation("bench.setup.compile"):
            self.comp = lower_with_backend(
                self.be, compile_sharded(self.system, self.plan), self.plan)
        entrykit.log(
            f"planner backend={self.be.name} planned_partition="
            f"{planned.partition} run plan: encoding={self.plan.encoding} "
            f"partition={self.plan.partition} shards={shards} "
            f"shard_size={self.comp.shard_size} "
            f"halo_width={self.comp.halo_width} "
            f"visited_cap_per_shard={mix['visited_cap'] // shards}")
        # one wave compiles or loads every program a call runs: the wave
        # bound is an argument of the loop, not a shape
        with TraceAnnotation("bench.setup.warmup"):
            self._call(-1, max_steps=1)
        self.results, self.kept = [], {}   # kept: slot -> (call, archive)

    def _call(self, i: int, max_steps: int = None):
        from repro.core import distributed
        mix = self.mix
        with TraceAnnotation("bench.call", call=i):
            return distributed.explore_distributed(
                self.comp, mesh=self.mesh, init=self.init(i),
                backend=self.be, plan=self.plan,
                max_steps=max_steps or mix["max_steps"],
                frontier_cap=mix["frontier_cap"],
                visited_cap=mix["visited_cap"] // mix["shards"],
                max_branches=mix["max_branches"])

    def release(self):
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.mesh.devices.flat]
        entrykit.log(f"peak_bytes_per_device={peaks}")
        del self.comp
