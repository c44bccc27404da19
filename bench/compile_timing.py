"""Seconds spent compiling, from JAX's own monitoring events.

JAX reports the duration of each trace to a jaxpr, each lowering to MLIR
and each backend compile.  Traces nest (an inner ``jit`` is traced inside
its caller's trace), so a plain sum counts them twice: the time is the
union of the events' intervals.  A program loaded from the persistent
compilation cache reports no backend compile.
"""

from __future__ import annotations

import time

__all__ = ["CompileClock", "covered"]

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")
BACKEND = EVENTS[2]


def covered(spans) -> float:
    """Seconds covered by the union of ``(start, end)`` intervals."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(spans):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


class CompileClock:
    """Records ``(event, start, end)`` on the ``perf_counter`` clock while
    it is entered."""

    def __init__(self):
        self.events: list[tuple[str, float, float]] = []

    def _on(self, event: str, duration: float, **_) -> None:
        if event in EVENTS:
            end = time.perf_counter()
            self.events.append((event, end - duration, end))

    def __enter__(self) -> "CompileClock":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def seconds(self, t0: float = float("-inf"),
                t1: float = float("inf")) -> float:
        """Compile seconds of the events that ended in ``[t0, t1]``."""
        return covered((s, e) for _, s, e in self.events if t0 <= e <= t1)

    def count(self, t0: float, t1: float, event: str = None) -> int:
        """Events (of kind ``event``, or any) that ended in ``[t0, t1]``."""
        return sum(1 for name, _, e in self.events
                   if t0 <= e <= t1 and (event is None or name == event))
