"""The one traffic generator: it reads a mix file ``bench/traffic/<mix>.json``
and draws everything a run sends from ``--seed``.

A mix names the entry point it drives (``entry``, a file of
``bench/entries/``), whether the loop is closed (the next call starts
when the last returns) or open (requests arrive on a schedule), the call
or request shapes, and how many answers the check samples.

* Closed loop: call ``i`` of a run gets the trace seeds
  ``base + i·batch + [0, batch)`` (mod 2^32), ``base`` drawn from the
  seed, so no two calls of a run share a trace.
* Open loop: the mix's ``arrival`` names a file of ``bench/arrivals/``
  whose ``offsets(mix, seconds, rng)`` gives the window's arrival times;
  request ``j`` gets the trace seed ``base + j`` (mod 2^32).
* Entries draw anything else from :meth:`Traffic.rng`, one stream each.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

__all__ = ["Traffic", "load_mix"]

_U32 = 1 << 32
ROOT = Path(__file__).resolve().parents[1]


def load_mix(root: Path, name: str) -> dict:
    path = Path(root) / "bench" / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


class Traffic:
    """What one run of a mix sends, drawn from ``seed``."""

    def __init__(self, mix: dict, seed: int, root: Path = ROOT):
        self.mix, self.root = mix, Path(root)
        self.seed = int(seed) % (1 << 64)
        self._base = int(np.random.default_rng([self.seed, 1]).integers(_U32))

    def rng(self, *stream: int) -> np.random.Generator:
        """The seed's generator for one stream of whole numbers >= 0."""
        return np.random.default_rng([self.seed, *(s + 1 for s in stream)])

    # -- closed loop -------------------------------------------------------

    def trace_seeds(self, call: int) -> np.ndarray:
        """Per-trace seeds of closed-loop call ``call`` (-1: the warm-up)."""
        B = self.mix["batch"]
        start = self._base + (call + 1) * B
        return ((start + np.arange(B, dtype=np.int64)) % _U32).astype(
            np.uint32)

    def check_rows(self, call: int) -> np.ndarray:
        """Rows of call ``call`` whose traces the check compares."""
        B, k = self.mix["batch"], self.mix["check_rows_per_call"]
        return np.sort(self.rng(2, call).choice(B, size=k, replace=False))

    def reservoir_slot(self, call: int, k: int):
        """Slot in ``[0, k)`` in which the check keeps call ``call``'s
        whole answer, or ``None``: a uniform sample of ``k`` of the calls
        a window makes, however many that is, drawn as they come."""
        if call < k:
            return call
        j = int(self.rng(3, call).integers(call + 1))
        return j if j < k else None

    # -- open loop -----------------------------------------------------------

    def arrivals(self, seconds: float):
        """``(offsets_s, seeds)`` of the open-loop requests of a window."""
        from bench.spec import plugin
        process = plugin(self.root, "arrivals", self.mix["arrival"])
        offsets = np.asarray(process.offsets(self.mix, seconds, self.rng(4)),
                             np.float64)
        n = len(offsets)
        seeds = ((self._base + np.arange(n, dtype=np.int64)) % _U32).astype(
            np.uint32)
        return offsets, seeds

    def check_requests(self, n: int) -> np.ndarray:
        """Indices of the open-loop requests whose answers the check
        compares."""
        k = min(n, self.mix["check_requests"])
        return np.sort(self.rng(5).choice(n, size=k, replace=False))
