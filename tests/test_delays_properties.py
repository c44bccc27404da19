"""Property tests for the delayed-semantics tier.

Three families, all on hypothesis-generated small systems:

* **zero-delay collapse** — an all-zero-delay system stepped under
  ``semantics="delays"`` matches the delay-free path configuration-for-
  configuration (spikes slice identical, countdown/pending identically 0);
* **backend × encoding agreement** — every lowering of the delayed step
  (ref dense / sparse ELL / sparse hybrid / dense Pallas / sparse Pallas /
  hybrid Pallas) produces the same successor set bit-for-bit, from
  arbitrary (also unreachable) delayed states;
* **closed-neuron invariant** — a neuron whose countdown stays nonzero
  after the step (no reopen) keeps its spike count: it cannot fire,
  cannot receive, and its countdown/pending evolve deterministically.

Plus a hypothesis differential against the pure-Python oracle
(:mod:`tests.oracle`) from random delayed states — not just the initial
configuration the BFS differential in ``test_delays_oracle.py`` starts at.
"""

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

import jax.numpy as jnp  # noqa: E402

import oracle  # noqa: E402
from repro.core import (SNPSystem, Rule, compile_system,  # noqa: E402
                        compile_system_sparse, delayed_next_configs,
                        sparse_delayed_next_configs, with_delays)
from repro.kernels.snp_step.ops import snp_step  # noqa: E402
from repro.kernels.snp_step.sparse_ops import snp_step_sparse  # noqa: E402

T = 128  # max_branches everywhere here


# ---------------------------------------------------------------------------
# Strategies
# ---------------------------------------------------------------------------

@st.composite
def delayed_systems(draw):
    m = draw(st.integers(1, 4))
    n_rules = draw(st.integers(1, 6))
    rules = []
    for _ in range(n_rules):
        neuron = draw(st.integers(0, m - 1))
        consume = draw(st.integers(1, 3))
        base = draw(st.integers(consume, consume + 2))
        period = draw(st.sampled_from([0, 0, 1, 2]))
        produce = draw(st.integers(0, 2))
        covering = draw(st.booleans())
        delay = draw(st.sampled_from([0, 0, 1, 2, 3]))
        rules.append(Rule(neuron=neuron, consume=consume, produce=produce,
                          regex_base=base, regex_period=period,
                          covering=covering, delay=delay))
    pairs = [(i, j) for i in range(m) for j in range(m) if i != j]
    syn = tuple(p for p in pairs if draw(st.booleans()))
    init = tuple(draw(st.integers(0, 3)) for _ in range(m))
    return SNPSystem(num_neurons=m, initial_spikes=init, rules=tuple(rules),
                     synapses=syn, output_neuron=m - 1, name="hyp-delays")


@st.composite
def delayed_states(draw, m):
    """An arbitrary 3m state row — including states a run could never
    reach (pending without countdown): the lowerings must agree on the
    full state space, not just the reachable slice."""
    spikes = tuple(draw(st.integers(0, 3)) for _ in range(m))
    cd = tuple(draw(st.integers(0, 3)) for _ in range(m))
    pd = tuple(draw(st.integers(0, 2)) for _ in range(m))
    return spikes + cd + pd


@st.composite
def systems_and_states(draw):
    system = draw(delayed_systems())
    state = draw(delayed_states(system.num_neurons))
    return system, state


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------

def _rows(configs, valid, emissions):
    """(successor row, emission) pairs of the valid branches — batched or
    not, any state width."""
    configs = np.asarray(configs).reshape(-1, configs.shape[-1])
    valid = np.asarray(valid).reshape(-1)
    emissions = np.asarray(emissions).reshape(-1)
    return {(tuple(int(v) for v in configs[t]), int(emissions[t]))
            for t in np.nonzero(valid)[0]}


def all_lowerings(system, state):
    """Successor sets of one delayed step through every lowering."""
    cfg = jnp.asarray(state, jnp.int32)
    batch = cfg[None, :]
    comp_d = compile_system(system, semantics="delays")
    comp_e = compile_system_sparse(system, semantics="delays")
    comp_h = compile_system_sparse(system, hub_threshold=1,
                                   semantics="delays")
    out = {}
    o = delayed_next_configs(cfg, comp_d, T)
    out["ref"] = _rows(o.configs, o.valid, o.emissions)
    for name, comp in (("sparse/ell", comp_e), ("sparse/hybrid", comp_h)):
        o = sparse_delayed_next_configs(cfg, comp, T)
        out[name] = _rows(o.configs, o.valid, o.emissions)
    c, v, e, _ = snp_step(batch, comp_d, max_branches=T)
    out["pallas"] = _rows(c, v, e)
    for name, comp in (("sparse_pallas/ell", comp_e),
                       ("sparse_pallas/hybrid", comp_h)):
        c, v, e, _ = snp_step_sparse(batch, comp, max_branches=T)
        out[name] = _rows(c, v, e)
    return out


# ---------------------------------------------------------------------------
# Properties
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(systems_and_states())
def test_backend_encoding_matrix_agreement(sys_state):
    system, state = sys_state
    outs = all_lowerings(system, state)
    ref = outs.pop("ref")
    for name, got in outs.items():
        assert got == ref, name


@settings(max_examples=40, deadline=None)
@given(systems_and_states())
def test_successors_match_oracle_from_arbitrary_states(sys_state):
    system, state = sys_state
    m = system.num_neurons
    tri = (state[:m], state[m:2 * m], state[2 * m:])
    want = {(oracle.flatten(s), e) for s, e in oracle.successors(tri, system)}
    o = delayed_next_configs(jnp.asarray(state, jnp.int32),
                             compile_system(system, semantics="delays"), T)
    assert _rows(o.configs, o.valid, o.emissions) == want


@settings(max_examples=30, deadline=None)
@given(delayed_systems())
def test_zero_delay_is_bit_identical_to_no_delays(system):
    sys0 = with_delays(system, 0)
    cfg = jnp.asarray(system.initial_spikes, jnp.int32)
    m = system.num_neurons
    from repro.core.semantics import next_configs
    base = next_configs(cfg, compile_system(sys0), T)
    want = _rows(base.configs, base.valid, base.emissions)
    state = jnp.concatenate([cfg, jnp.zeros(2 * m, jnp.int32)])
    o = delayed_next_configs(state,
                             compile_system(sys0, semantics="delays"), T)
    got = _rows(o.configs, o.valid, o.emissions)
    # spikes slice identical, countdown/pending identically zero
    assert {(r[:m], e) for r, e in got} == want
    assert all(not any(r[m:]) for r, _ in got)


@settings(max_examples=40, deadline=None)
@given(systems_and_states())
def test_closed_neuron_invariant(sys_state):
    """While a neuron's countdown stays nonzero it neither fires nor
    receives: spikes unchanged, countdown decremented (or freshly set),
    pending untouched — on *every* successor branch."""
    system, state = sys_state
    m = system.num_neurons
    spikes, cd = state[:m], state[m:2 * m]
    o = delayed_next_configs(jnp.asarray(state, jnp.int32),
                             compile_system(system, semantics="delays"), T)
    rows = _rows(o.configs, o.valid, o.emissions)
    for row, _ in rows:
        sp2, cd2, pd2 = row[:m], row[m:2 * m], row[2 * m:]
        for j in range(m):
            if cd[j] > 1:  # closed before, still closed after (no reopen)
                assert sp2[j] == spikes[j]
                assert cd2[j] == cd[j] - 1
                assert pd2[j] == state[2 * m + j]
