"""The sorting SN P system of Ionescu and Sburlan (2008) on the trace path.

Each trace starts from its own input vector (``run_traces(init=...)``),
ends with its outputs holding the inputs in descending order, and equals
the pure-Python oracle step for step.  The chosen step finds the fired
rules in rule space, so neither its program nor its memory grows with the
rules a neuron holds (1024 on one neuron at n = 1024).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import oracle
from repro.core import run_trace, run_traces
from repro.core.engine import _traces_scan
from repro.core.generators import sorting
from repro.core.matrix import compile_system_sparse
from repro.core.semantics import (TABLE_MAX_RULE_SLOTS, fired_lookup,
                                  sparse_chosen_config)

# n: (traces, largest input); the oracle scans every rule per neuron
CASES = {8: (4, 30), 16: (3, 12), 64: (2, 5)}


def _inputs(n, batch, top, seed):
    values = np.random.default_rng(seed).integers(0, top + 1, (batch, n))
    return values, np.concatenate(
        [values, np.zeros((batch, 2 * n), values.dtype)], axis=1)


@pytest.mark.parametrize("n", sorted(CASES))
def test_traces_sort_their_inputs_and_match_oracle(n):
    batch, top = CASES[n]
    values, init = _inputs(n, batch, top, seed=n)
    steps = top + 2
    out = run_traces(sorting(n), steps=steps, seeds=np.arange(batch),
                     policy="random", max_branches=4, backend="sparse",
                     init=init)
    configs = np.asarray(out.configs)
    for b in range(batch):
        states, emissions = oracle.run_deterministic(
            sorting(n, values[b]), steps)
        np.testing.assert_array_equal(configs[b],
                                      np.asarray(states)[:, :3 * n])
        assert not any(emissions)
        last = values[b].max() + 1
        # the last output spike lands at step max + 1, and not before
        np.testing.assert_array_equal(configs[b, last - 1, 2 * n:],
                                      np.sort(values[b])[::-1])
        if values[b].max() > 0:
            assert (configs[b, last - 2, 2 * n:]
                    != np.sort(values[b])[::-1]).any()
    assert not np.asarray(out.emissions).any()
    assert not np.asarray(out.branch_overflow).any()


def test_init_shapes():
    """``(m,)`` starts every trace there, ``(B, m)`` one start per trace,
    and ``run_trace`` is row b of the batch; any other shape raises."""
    n, batch = 6, 3
    system = sorting(n)
    _, rows = _inputs(n, batch, 9, seed=1)
    seeds = np.arange(batch) + 11
    kw = dict(steps=11, policy="random", max_branches=4, backend="sparse")
    got = run_traces(system, seeds=seeds, init=rows, **kw)
    for b in range(batch):
        alone = run_traces(sorting(n, rows[b, :n]), seeds=seeds[b:b + 1],
                           **kw)
        one = run_trace(system, seed=int(seeds[b]), init=rows[b], **kw)
        for g, a, o in zip(got, alone, one):
            np.testing.assert_array_equal(np.asarray(g)[b], np.asarray(a)[0])
            np.testing.assert_array_equal(np.asarray(g)[b], np.asarray(o))
    shared = run_traces(system, seeds=seeds, init=rows[1], **kw)
    tiled = run_traces(system, seeds=seeds, init=np.tile(rows[1], (3, 1)),
                       **kw)
    for s, t in zip(shared, tiled):
        np.testing.assert_array_equal(np.asarray(s), np.asarray(t))
    m = 3 * n
    for bad in (np.zeros((batch + 1, m)), np.zeros((m + 1,)),
                np.zeros((batch, m, 1)), np.zeros(())):
        with pytest.raises(ValueError, match="init"):
            run_traces(system, seeds=seeds, init=bad, **kw)
    with pytest.raises(ValueError, match="init"):
        run_trace(system, seed=0, init=rows, **kw)


def test_every_init_form_runs_one_program():
    """``None``, ``(m,)`` and ``(B, m)`` starts run one compiled scan, so
    a warm-up with any of them leaves nothing to compile."""
    system = sorting(7)
    _, rows = _inputs(7, 4, 5, seed=2)
    kw = dict(steps=3, seeds=np.arange(4), policy="random", max_branches=4,
              backend="sparse")
    run_traces(system, **kw)
    before = _traces_scan._cache_size()
    for init in (rows[0], rows, None):
        run_traces(system, init=init, **kw)
    assert _traces_scan._cache_size() == before


def _eqn_count(jaxpr):
    """Equations of ``jaxpr`` and of every jaxpr nested in it."""
    count = 0
    for eqn in jaxpr.eqns:
        count += 1
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    count += _eqn_count(inner)
    return count


def _chosen_step(n, batch=4):
    comp = compile_system_sparse(sorting(n))
    cfgs = jnp.zeros((batch, 3 * n), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda c, x: sparse_chosen_config(
            x, c, 8, lambda nv: jnp.zeros_like(nv)))(comp, cfgs).jaxpr
    return comp, jaxpr


def test_chosen_step_does_not_grow_with_rule_slots():
    """One chosen step of ``sorting(64)`` and of ``sorting(512)`` (64 and
    512 rules on a middle neuron) has the same equations, and none of its
    values is ``(B, m, R)``."""
    counts = []
    for n in (64, 512):
        comp, jaxpr = _chosen_step(n)
        R = comp.max_rules_per_neuron
        assert R == n > TABLE_MAX_RULE_SLOTS
        assert fired_lookup(comp, 1) == "rules"
        assert fired_lookup(comp, 8) == "table"
        counts.append(_eqn_count(jaxpr))
        shapes = {tuple(v.aval.shape) for e in jaxpr.eqns for v in e.outvars}
        assert not any(len(s) >= 2 and s[-1] == R for s in shapes), shapes
    assert counts[0] == counts[1]


def test_compiled_sorting_system_is_small():
    """``sorting(512)``: 262,656 rules and 393,472 synapses, held in
    O(n + m·K_in), not in n × the largest fan-out."""
    comp = compile_system_sparse(sorting(512))
    assert comp.num_rules == 512 * 513
    held = sum(a.nbytes for a in comp if hasattr(a, "nbytes"))
    assert held < 50e6, held
