"""Operations and bytes the SNP work requires, and the least time a chip
could take for them.

The counts are of the work the inputs and outputs require, whatever
implements it, so a faster implementation can approach 100% but never
pass it:

* bytes: the ``(B, steps, m)`` int32 configurations written once, the
  per-step emissions (int32), alive and overflow flags (one byte each),
  the initial state and per-trace keys read once, and the system read
  once (rules as five int32 fields and a flag, synapses as two int32s);
* operations: per trace and step, one applicability test per rule and
  one add per real synapse (no ELL padding, no branch candidates that
  are not taken).

Peaks come from ``bench/peaks.json`` by device kind.  Integer adds run
on the vector units, far below the int8 matrix peak, so bounding them by
that peak only lowers the least time: the share stays a true lower bound
on what the chip could reach.
"""

from __future__ import annotations

import json
from pathlib import Path

__all__ = ["peaks_for", "traces_call_work", "least_time"]


def peaks_for(kind: str, root: Path) -> dict:
    """The peaks of ``kind``; an unknown device is an error."""
    table = json.loads((Path(root) / "bench" / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def traces_call_work(*, batch: int, steps: int, neurons: int, rules: int,
                     synapses: int) -> tuple[int, int]:
    """``(ops, bytes)`` that one ``run_traces`` call requires."""
    out = batch * steps * (neurons * 4 + 4 + 1 + 1)
    state_in = neurons * 4 + batch * 8
    system = rules * (5 * 4 + 1) + synapses * 2 * 4
    ops = batch * steps * (rules + synapses)
    return ops, out + state_in + system


def least_time(ops: int, nbytes: int, peaks: dict) -> tuple[float, str]:
    """Least seconds for ``ops`` and ``nbytes`` on a chip with ``peaks``,
    and which bound sets it (``"memory"`` or ``"compute"``)."""
    t_ops = ops / peaks["int8_ops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
