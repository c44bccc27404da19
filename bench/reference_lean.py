"""The plain reference with ``M_Π`` held as its nonzero entries only.

:class:`bench.reference.Reference` stores a row entry for every rule on
every out-neighbour of its neuron, forgetting rules (``produce`` 0)
included.  That is harmless where a neuron has few rules, but the sorting
system of Ionescu and Sburlan (``bench/generators/sort.py``) puts 1024
rules, 1023 of them forgetting, on middle neurons with up to 1024
out-neighbours: 538 million stored zeros at ``n`` = 1024, tens of GB, and
every step's ``M_Π`` product pays for them.  Here a rule's row holds its
consume entry and, only if it produces, its ``produce`` entries.  The
per-neuron counts of applicable rules come from one prefix sum over the
neuron-sorted rules instead of a sparse product, and the progression test
runs only on the rules that have a period; every result is the parent
class's, bit for bit.
"""

from __future__ import annotations

from typing import Optional

import numpy as np
import scipy.sparse as sp

from bench.reference import Reference as _Reference

__all__ = ["Reference"]


class Reference(_Reference):
    """:class:`bench.reference.Reference` without stored zeros."""

    def __init__(self, plain, state: Optional[str] = None):
        if state not in (None, "bfloat16"):
            raise ValueError(f"unknown state precision {state!r}")
        self.sys = plain
        self.state = state
        m, n = plain.num_neurons, plain.num_rules
        owner = plain.rule_neuron
        order = np.argsort(plain.src, kind="stable")
        src, dst = plain.src[order], plain.dst[order]
        out_deg = np.bincount(src, minlength=m)
        out_start = np.concatenate([[0], np.cumsum(out_deg)])
        # M_Π (n, m): -consume at the owner, +produce on each out-neighbour
        # of the owner of a rule that produces
        fires = np.flatnonzero(plain.produce > 0)
        deg = out_deg[owner[fires]]
        within = np.arange(int(deg.sum())) - np.repeat(
            np.cumsum(deg) - deg, deg)
        rows = np.concatenate([np.arange(n), np.repeat(fires, deg)])
        cols = np.concatenate(
            [owner, dst[np.repeat(out_start[owner[fires]], deg) + within]])
        vals = np.concatenate([-plain.consume,
                               np.repeat(plain.produce[fires], deg)])
        self.M = sp.csr_matrix((vals, (rows, cols)), shape=(n, m),
                               dtype=np.int64)
        self.env = np.where(owner == plain.out_neuron, plain.produce, 0)
        self.own = sp.csr_matrix(
            (np.ones(n, np.int64), (np.arange(n), owner)), shape=(n, m))
        self.seg_start = np.searchsorted(owner, np.arange(m + 1))

    def applicable(self, C: np.ndarray) -> np.ndarray:
        s = self.sys
        sp_ = np.take(C.astype(np.int32), s.rule_neuron, axis=1)
        on = sp_ == s.base
        per = np.flatnonzero(s.period > 0)
        if per.size:
            on[:, per] = (sp_[:, per] - s.base[per]) % s.period[per] == 0
        return (sp_ >= np.maximum(s.base, s.consume)) & (s.covering | on)

    def _info(self, C: np.ndarray):
        app = self.applicable(C)
        cum0 = np.zeros((len(C), app.shape[1] + 1), np.int32)
        np.cumsum(app, axis=1, dtype=np.int32, out=cum0[:, 1:])
        start = cum0[:, self.seg_start[:-1]]              # (rows, m)
        k = cum0[:, self.seg_start[1:]] - start
        rank = cum0[:, 1:] - np.take(start, self.sys.rule_neuron, axis=1) - 1
        return app, rank, np.maximum(k, 1), app.any(axis=1)
