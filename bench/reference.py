"""Plain reference semantics of an SNP system without delays.

A straightforward numpy implementation of the paper's transition
``C' = C + S · M_Π`` (eq. 2) over a :class:`~bench.systems.PlainSystem`,
written from the definitions and independent of the program:

* a rule ``{base + t·period} / a^consume -> a^produce`` applies at ``s``
  spikes iff ``s >= consume`` and ``s >= base`` and, unless it is a
  covering rule, ``s`` lies on the progression (``period == 0``: ``s ==
  base``);
* neuron ``i`` has ``k_i`` applicable rules and ``max(1, k_i)`` choices;
  the valid spiking vectors are numbered ``t = 0 .. Ψ-1``,
  ``Ψ = Π max(1, k_i)``, by mixed radix with neuron 0 the most
  significant digit, and digit ``d`` fires the ``d``-th applicable rule of
  its neuron in rule order; a run keeps the first ``max_branches``;
* a random trace draws its branch from a per-trace JAX key: each step
  splits the key and draws ``randint(sub, 0, max(n_valid, 1))``; a trace
  with no applicable rule stays where it is;
* exploration is breadth first: candidates in (frontier row, branch)
  order, a configuration is new if it is neither archived nor earlier in
  the same wave, and only the first ``frontier_cap`` new ones are archived
  and expanded next.

Counts are int64.  ``state="bfloat16"`` rounds every state to bfloat16
after each step: the lower-precision control, which must fail the
comparison.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np
import scipy.sparse as sp

__all__ = ["Reference", "Traces", "Archive"]


class Traces(NamedTuple):
    configs: np.ndarray    # (k, steps, m) int64
    emissions: np.ndarray  # (k, steps) int64
    alive: np.ndarray      # (k, steps) bool
    overflow: np.ndarray   # (k, steps) bool


class Archive(NamedTuple):
    configs: np.ndarray    # (n, m) int64, discovery order
    steps: int
    branch_overflow: bool
    frontier_overflow: bool
    visited_overflow: bool


_PICKS = {}


def _pick_fn():
    """``(keys, n) -> (keys, idx)``: one split and one uniform branch
    draw per trace, jitted once per process."""
    if "pick" not in _PICKS:
        import jax
        import jax.numpy as jnp

        def pick(keys, n):
            pair = jax.vmap(jax.random.split)(keys)
            keys, subs = pair[:, 0], pair[:, 1]
            idx = jax.vmap(lambda k, c: jax.random.randint(
                k, (), 0, jnp.maximum(c, 1)))(subs, n)
            return keys, idx

        _PICKS["pick"] = jax.jit(pick)
    return _PICKS["pick"]


class Reference:
    """The plain semantics of one system; ``state`` is ``None`` (exact
    int64) or ``"bfloat16"`` (the control)."""

    def __init__(self, plain, state: Optional[str] = None):
        if state not in (None, "bfloat16"):
            raise ValueError(f"unknown state precision {state!r}")
        self.sys = plain
        self.state = state
        m, n = plain.num_neurons, plain.num_rules
        owner = plain.rule_neuron
        # M_Π (n, m): -consume at the owner, +produce on each out-neighbour
        order = np.argsort(plain.src, kind="stable")
        src, dst = plain.src[order], plain.dst[order]
        out_deg = np.bincount(src, minlength=m)
        out_start = np.concatenate([[0], np.cumsum(out_deg)])
        rows, cols, vals = [np.arange(n)], [owner], [-plain.consume]
        deg = out_deg[owner]
        rows.append(np.repeat(np.arange(n), deg))
        starts = np.repeat(out_start[owner], deg)
        within = np.arange(int(deg.sum())) - np.repeat(
            np.cumsum(deg) - deg, deg)
        cols.append(dst[starts + within])
        vals.append(np.repeat(plain.produce, deg))
        self.M = sp.csr_matrix(
            (np.concatenate(vals), (np.concatenate(rows),
                                    np.concatenate(cols))),
            shape=(n, m), dtype=np.int64)
        self.env = np.where(owner == plain.out_neuron, plain.produce, 0)
        # rule -> neuron incidence, to count applicable rules per neuron
        self.own = sp.csr_matrix(
            (np.ones(n, np.int64), (np.arange(n), owner)), shape=(n, m))
        # rules of neuron i are seg_start[i] .. seg_start[i + 1] - 1
        self.seg_start = np.searchsorted(owner, np.arange(m + 1))

    # -- one step ----------------------------------------------------------

    def _round(self, C: np.ndarray) -> np.ndarray:
        if self.state is None:
            return C
        import ml_dtypes
        return C.astype(ml_dtypes.bfloat16).astype(np.int64)

    def applicable(self, C: np.ndarray) -> np.ndarray:
        s = self.sys
        sp_ = C[:, s.rule_neuron]
        ge = (sp_ >= s.base) & (sp_ >= s.consume)
        on = np.where(s.period > 0,
                      (sp_ - s.base) % np.maximum(s.period, 1) == 0,
                      sp_ == s.base)
        return ge & (s.covering | on)

    def _info(self, C: np.ndarray):
        """Applicable rules, their rank inside their neuron, and the
        per-neuron choice counts."""
        app = self.applicable(C)
        k = np.asarray((sp.csr_matrix(app.astype(np.int64)) @ self.own)
                       .todense())
        incl = np.cumsum(app, axis=1)
        excl_neuron = np.cumsum(k, axis=1) - k          # (rows, m)
        rank = incl - excl_neuron[:, self.sys.rule_neuron] - 1
        return app, rank, np.maximum(k, 1), app.any(axis=1)

    @staticmethod
    def _n_valid(choices: np.ndarray, alive: np.ndarray, T: int):
        with np.errstate(over="ignore"):   # Ψ saturates to inf
            psi = np.prod(choices.astype(np.float64), axis=1)
        return np.where(alive, np.minimum(psi, T), 0).astype(np.int64), psi

    @staticmethod
    def _digits(multi: np.ndarray, choices_row: np.ndarray, t: int) -> dict:
        """``{neuron: digit}`` of branch ``t``, non-zero digits only;
        ``multi`` lists the row's neurons with more than one choice, last
        neuron (the least significant digit) first."""
        out = {}
        for i in multi:
            if t == 0:
                break
            k = int(choices_row[i])
            if t % k:
                out[int(i)] = t % k
            t //= k
        return out

    def _fire(self, C, app, rank, choices, rows, branches):
        """Successors of ``C[rows[j]]`` along branch ``branches[j]``.

        Branch 0 fires every neuron's first applicable rule:
        ``C + S_0 · M_Π``.  Branch ``t`` differs from it only at the
        neurons whose digit ``d`` is not 0, where the ``d``-th applicable
        rule fires in place of the first: its row of ``M_Π`` is added and
        the first rule's row taken away."""
        S0 = (app & (rank == 0)).astype(np.int64)
        base = C + np.asarray((self.M.T @ S0.T).T)
        out = base[rows]
        emis = (S0 @ self.env)[rows]
        start, multi, fired = self.seg_start, {}, {}
        J, NEW, OLD = [], [], []
        for j, (r, t) in enumerate(zip(rows, branches)):
            if t == 0:
                continue
            if r not in multi:
                multi[r] = np.flatnonzero(choices[r] > 1)[::-1]
            for i, d in self._digits(multi[r], choices[r], int(t)).items():
                if (r, i) not in fired:
                    fired[r, i] = start[i] + np.flatnonzero(
                        app[r, start[i]:start[i + 1]])
                J.append(j)
                NEW.append(fired[r, i][d])
                OLD.append(fired[r, i][0])
        if J:
            J, NEW, OLD = (np.asarray(a) for a in (J, NEW, OLD))
            ptr, counts = self.M.indptr, np.diff(self.M.indptr)
            for q, sign in ((NEW, 1), (OLD, -1)):
                n = counts[q]
                flat = np.repeat(ptr[q] - (np.cumsum(n) - n), n) + \
                    np.arange(int(n.sum()))
                np.add.at(out, (np.repeat(J, n), self.M.indices[flat]),
                          sign * self.M.data[flat])
            np.add.at(emis, J, self.env[NEW] - self.env[OLD])
        return self._round(out), emis

    # -- traces --------------------------------------------------------------

    def traces(self, seeds, steps: int, max_branches: int,
               init: Optional[np.ndarray] = None) -> Traces:
        """Random-policy trajectories of the given per-trace seeds, from
        ``init``: one ``(m,)`` configuration for every trace or one row
        per trace ``(k, m)`` (default: the system's initial
        configuration)."""
        import jax
        import jax.numpy as jnp
        pick = _pick_fn()
        k = len(seeds)
        keys = jax.vmap(jax.random.PRNGKey)(
            jnp.asarray(np.asarray(seeds, np.uint32)))
        C = np.broadcast_to(self.sys.init if init is None else
                            np.asarray(init, np.int64),
                            (k, self.sys.num_neurons)).copy()
        cfgs, emis, alive, ovf = [], [], [], []
        for _ in range(steps):
            app, rank, choices, live = self._info(C)
            n_valid, psi = self._n_valid(choices, live, max_branches)
            keys, idx = pick(keys, jnp.asarray(n_valid, jnp.int32))
            idx = np.asarray(idx, np.int64)
            has = n_valid > 0
            nxt, e = self._fire(C, app, rank, choices, np.arange(k), idx)
            C = np.where(has[:, None], nxt, C)
            cfgs.append(C)
            emis.append(np.where(has, e, 0))
            alive.append(has)
            ovf.append(has & (psi > max_branches))
        return Traces(np.stack(cfgs, 1), np.stack(emis, 1),
                      np.stack(alive, 1), np.stack(ovf, 1))

    # -- exploration -----------------------------------------------------------

    def explore(self, *, max_steps: int, frontier_cap: int,
                visited_cap: int, max_branches: int,
                init: Optional[np.ndarray] = None) -> Archive:
        """Breadth-first reachable set from ``init`` (default: the
        system's initial configuration)."""
        C0 = np.asarray(self.sys.init if init is None else init,
                        np.int64)[None, :].copy()
        archive = [C0[0]]
        visited = {C0[0].astype(np.int32).tobytes()}
        frontier = C0
        b_ovf = f_ovf = v_ovf = False
        step = 0
        while step < max_steps and len(frontier):
            app, rank, choices, live = self._info(frontier)
            n_valid, psi = self._n_valid(choices, live, max_branches)
            b_ovf |= bool(np.any(live & (psi > max_branches)))
            rows = np.repeat(np.arange(len(frontier)), n_valid)
            branches = np.concatenate(
                [np.arange(c) for c in n_valid]) if rows.size else rows
            cand, _ = self._fire(frontier, app, rank, choices, rows, branches)
            new, seen = [], set()
            keys = cand.astype(np.int32)
            for row, key in zip(cand, keys):
                key = key.tobytes()
                if key in visited or key in seen:
                    continue
                seen.add(key)
                new.append((row, key))
            f_ovf |= len(new) > frontier_cap
            take = new[:frontier_cap]
            if len(archive) + len(take) > visited_cap:
                v_ovf = True
                take = take[:visited_cap - len(archive)]
            visited.update(key for _, key in take)
            archive.extend(row for row, _ in take)
            frontier = np.asarray([row for row, _ in take]).reshape(
                len(take), -1)
            step += 1
        return Archive(np.asarray(archive), step, b_ovf, f_ovf, v_ovf)
