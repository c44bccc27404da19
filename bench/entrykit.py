"""What every entry point of ``bench/entries/`` shares: the interface the
harness drives, and the planner's pick resolved as the program resolves it.

An entry file ``bench/entries/<entry>.py`` defines ``Entry``, built as
``Entry(plain, system, mix, traffic)`` from the plain system the
reference reads, the same system as the program's ``SNPSystem``, the mix
file's parameters and the run's :class:`~bench.traffic.Traffic`.  The
harness calls, in order:

* ``setup()``: plan, compile and warm up the cell's own shapes;
* ``window(seconds) -> {metric: value}``: the measured window;
* ``release()``: keep what the check needs on the host, free the rest;
* ``check() -> {name: (value, limit)}``: the comparison with the plain
  reference, after the window;

and reads ``attempted``, ``failed``, ``least_time_s(peaks)``, ``waves()``
and ``counters()`` for the result and the per-layer metrics.

Every call into the program goes through the module it lives in
(``engine.run_traces``, ``engine.explore``, ``snp_service``), so a test
or the control can put something else in the timed path's place.
"""

from __future__ import annotations

import sys
from typing import Dict, Tuple

from jax.profiler import TraceAnnotation

__all__ = ["Checks", "Entry", "log", "plan_and_compile"]

Checks = Dict[str, Tuple[float, float]]   # name -> (value, limit)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def plan_and_compile(system, workload):
    """The planner's pick for ``workload`` and the system lowered by it,
    resolved exactly as the entry points resolve ``backend=None``."""
    from repro.core.backend import compile_with_plan, resolve_entry_info
    with TraceAnnotation("bench.setup.plan"):
        be, plan, _ = resolve_entry_info(system, None, None,
                                         workload=workload)
    with TraceAnnotation("bench.setup.compile"):
        comp = compile_with_plan(be, system, plan)
    if hasattr(comp, "in_idx"):
        encoding = "hybrid" if comp.is_hybrid else "ell"
        shape = (f"K_in={comp.max_in_degree} "
                 f"coo_synapses={int(comp.coo_src.shape[0])}")
    else:
        encoding, shape = "dense", ""
    log(f"planner backend={be.name} encoding={encoding} "
        f"hub_threshold={plan.hub_threshold} workload={workload} {shape}")
    return be, plan, comp


class Entry:
    """Defaults of the interface; an entry overrides what it has."""

    def __init__(self, plain, system, mix: dict, traffic):
        self.plain, self.system = plain, system
        self.mix, self.traffic = mix, traffic
        self.attempted = 0
        self.failed = 0

    def least_time_s(self, peaks) -> float | None:
        return None

    def waves(self) -> int | None:
        return None

    def counters(self) -> dict:
        return {}

    def release(self) -> None:
        pass
