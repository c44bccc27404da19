"""Sparse encoding + sparse step tests.

Covers the compile layer (vectorized dense M vs. brute force, the sparse
encoding rebuilding M, compile-time regression), the sparse step semantics
(bit-identity with the dense oracle, including the edge cases a sparse
path can get wrong: rules with zero synapses out, neurons with no rules,
Ψ-overflow parity, n-d batches), and the fused sparse Pallas kernel's
block sweep."""

import time

import numpy as np
import pytest

import jax.numpy as jnp

from conftest import assert_same_step as _assert_same_step
from repro.core import (compile_system, compile_system_sparse, explore,
                        get_backend, paper_pi, successor_set)
from repro.core.generators import (counter, nd_chain, power_law,
                                   random_system, ring, ring_lattice, sorting,
                                   torus)
from repro.core.semantics import next_configs, sparse_next_configs
from repro.core.system import Rule, SNPSystem
from repro.kernels.snp_step import snp_step_sparse

SYSTEMS = {
    "paper-pi": (paper_pi(True), 16),
    "paper-pi-exact": (paper_pi(False), 16),
    "ring-9": (ring(9), 8),
    "counter-4": (counter(4), 8),
    "nd-chain-6": (nd_chain(6), 64),
    "random-17": (random_system(17, 3, 0.3, seed=3), 32),
    "ring-lattice-12": (ring_lattice(12, 3, seed=1), 16),
    "torus-4x5": (torus(4, 5, seed=2), 16),
    "power-law-20": (power_law(20, 3, seed=3), 16),
}


def _brute_force_M(system):
    """The seed's original O(n·m) synapse-set scan, kept as the oracle for
    the vectorized adjacency construction."""
    n, m = system.num_rules, system.num_neurons
    order = sorted(range(n), key=lambda i: system.rules[i].neuron)
    rules = [system.rules[i] for i in order]
    syn = set(system.synapses)
    M = np.zeros((n, m), dtype=np.int32)
    for i, r in enumerate(rules):
        M[i, r.neuron] = -r.consume
        if r.produce > 0:
            for j in range(m):
                if (r.neuron, j) in syn:
                    M[i, j] = r.produce
    return M, tuple(order)


def _rebuild_M(sp):
    """``M_Π`` from the sparse encoding alone: ``-consume`` at each rule's
    owner, and the ``produce`` of every rule of neuron ``i`` at each
    neuron that has ``i`` among its in-neighbours (the ELL rows of
    ``in_idx``, then the COO tail), through the rule segments."""
    n, m = sp.num_rules, sp.num_neurons
    ss, sc = np.asarray(sp.seg_start), np.asarray(sp.seg_count)
    produce = np.asarray(sp.produce)
    M = np.zeros((n, m), np.int32)
    M[np.arange(n), np.asarray(sp.rule_neuron)] = -np.asarray(sp.consume)
    ii = np.asarray(sp.in_idx)
    src = np.concatenate([ii.ravel(), np.asarray(sp.coo_src)])
    dst = np.concatenate([np.repeat(np.arange(m), ii.shape[1]),
                          np.asarray(sp.coo_dst)])
    for i, j in zip(src[src < m], dst[src < m]):
        rules = np.arange(ss[i], ss[i] + sc[i])
        M[rules, j] += produce[rules]
    return M


# _assert_same_step lives in conftest.py (shared by the equivalence
# suites); imported above under its historical local name.


# ---------------------------------------------------------------------------
# compile layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(SYSTEMS))
def test_vectorized_dense_compile_matches_brute_force(name):
    system, _ = SYSTEMS[name]
    comp = compile_system(system)
    M, order = _brute_force_M(system)
    assert tuple(np.asarray(comp.rule_order)) == order
    np.testing.assert_array_equal(np.asarray(comp.M), M)


@pytest.mark.parametrize("name", sorted(SYSTEMS))
def test_sparse_encoding_round_trips(name):
    system, _ = SYSTEMS[name]
    sp = compile_system_sparse(system)
    M, order = _brute_force_M(system)
    n, m = system.num_rules, system.num_neurons
    assert tuple(np.asarray(sp.rule_order)) == order

    # the rule vectors, segments and in-adjacency rebuild exactly the
    # dense M, in both in-adjacency layouts
    np.testing.assert_array_equal(_rebuild_M(sp), M)
    np.testing.assert_array_equal(
        _rebuild_M(compile_system_sparse(system, hub_threshold=1)), M)

    # per-neuron segments partition the neuron-sorted rule axis
    ss, sc = np.asarray(sp.seg_start), np.asarray(sp.seg_count)
    rn = np.asarray(sp.rule_neuron)
    assert sc.sum() == n
    for mu in range(m):
        assert (rn[ss[mu]:ss[mu] + sc[mu]] == mu).all()

    # ELL in-adjacency == transposed synapse graph
    ii = np.asarray(sp.in_idx)
    for j in range(m):
        got = sorted(int(x) for x in ii[j] if x < m)
        assert got == sorted(i for (i, jj) in system.synapses if jj == j)


@pytest.mark.parametrize("system,fan_out", [
    (ring_lattice(512, 4, seed=0), 4), (sorting(64), 64)],
    ids=["ring-lattice-512", "sort-64"])
def test_sparse_compile_never_builds_dense_arrays(system, fan_out):
    """No field is O(n·m), nor n × the largest fan-out of a rule (the
    sorting system's input rules feed all 64 middle neurons)."""
    sp = compile_system_sparse(system)
    n, m = sp.num_rules, sp.num_neurons
    for arr in sp[:-1]:
        if hasattr(arr, "size"):
            assert arr.size < n * m / 4, "O(n·m)-sized field in sparse comp"
            assert arr.size < n * fan_out, "n × fan-out field in sparse comp"


def test_compile_time_regression_vectorized_adjacency():
    """The seed's per-rule × per-neuron Python loop took O(n·m) set lookups
    (~tens of seconds here); the vectorized adjacency indexing must stay
    orders of magnitude below that.  Generous bound for slow CI workers."""
    system = ring_lattice(4096, 8, seed=0)
    t0 = time.perf_counter()
    compile_system(system)
    dense_t = time.perf_counter() - t0
    t0 = time.perf_counter()
    sp = compile_system_sparse(system)
    sparse_t = time.perf_counter() - t0
    assert dense_t < 8.0, f"dense compile too slow: {dense_t:.1f}s"
    assert sparse_t < 8.0, f"sparse compile too slow: {sparse_t:.1f}s"
    assert sp.max_in_degree == 8
    # every rule writes its owner and 8 out-neighbours: 9 entries of M_Π
    ii = np.asarray(sp.in_idx)
    assert (np.bincount(ii[ii < sp.num_neurons]) == 8).all()


def test_sparse_compile_rejects_unpackable_rules():
    big = SNPSystem(
        2, (1, 0),
        (Rule(neuron=0, consume=40000, produce=1, regex_base=40000,
              covering=True),),
        ((0, 1),), output_neuron=1)
    with pytest.raises(ValueError, match="2\\^15"):
        compile_system_sparse(big)


# ---------------------------------------------------------------------------
# sparse step semantics: bit-identity with the dense oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(SYSTEMS))
def test_sparse_step_matches_dense_oracle(name):
    system, T = SYSTEMS[name]
    dn, sp = compile_system(system), compile_system_sparse(system)
    rng = np.random.default_rng(abs(hash(name)) % 2**31)
    cfgs = jnp.asarray(rng.integers(0, 5, size=(6, dn.num_neurons)),
                       jnp.int32)
    _assert_same_step(next_configs(cfgs, dn, T),
                      sparse_next_configs(cfgs, sp, T))


def test_rule_heavy_neurons_use_gather_fallback():
    """R > 8 rules per neuron flips _fired_packed to the take_along_axis
    fallback; it must stay bit-identical too."""
    system = random_system(6, 9, 0.4, max_spikes=5, seed=8)
    dn, sp = compile_system(system), compile_system_sparse(system)
    assert sp.max_rules_per_neuron > 8
    rng = np.random.default_rng(8)
    cfgs = jnp.asarray(rng.integers(0, 6, size=(5, 6)), jnp.int32)
    _assert_same_step(next_configs(cfgs, dn, 32),
                      sparse_next_configs(cfgs, sp, 32))


def test_rule_with_zero_synapses_out():
    """A produce rule whose neuron has no outgoing synapses: its M row is
    only the consume entry (spikes go nowhere, not even the environment
    unless it's the output neuron)."""
    system = SNPSystem(
        3, (2, 1, 1),
        (Rule(neuron=0, consume=1, produce=1, regex_base=1, covering=True),
         Rule(neuron=1, consume=1, produce=1, regex_base=1, covering=True),
         Rule(neuron=2, consume=1, produce=2, regex_base=1, covering=True)),
        ((0, 1),),                      # neurons 1 and 2 have no out-synapses
        output_neuron=2)
    dn, sp = compile_system(system), compile_system_sparse(system)
    cfgs = jnp.asarray([[2, 1, 1], [0, 3, 2], [1, 0, 0]], jnp.int32)
    _assert_same_step(next_configs(cfgs, dn, 8),
                      sparse_next_configs(cfgs, sp, 8))
    # and the emission still happens: neuron 2's rule feeds the environment
    out = sparse_next_configs(jnp.asarray([0, 0, 1], jnp.int32), sp, 4)
    assert int(np.asarray(out.emissions)[np.asarray(out.valid)][0]) == 2


def test_neuron_with_no_rules():
    system = SNPSystem(
        4, (1, 1, 0, 1),
        (Rule(neuron=0, consume=1, produce=1, regex_base=1, covering=True),
         Rule(neuron=3, consume=1, produce=1, regex_base=1, covering=True)),
        ((0, 1), (0, 2), (3, 2)),       # neurons 1, 2 own no rules
        output_neuron=3)
    dn, sp = compile_system(system), compile_system_sparse(system)
    assert int(np.asarray(sp.seg_count)[1]) == 0
    assert int(np.asarray(sp.seg_count)[2]) == 0
    cfgs = jnp.asarray([[1, 1, 0, 1], [0, 5, 5, 0], [2, 0, 0, 2]], jnp.int32)
    _assert_same_step(next_configs(cfgs, dn, 8),
                      sparse_next_configs(cfgs, sp, 8))


def test_overflow_flag_parity_with_ref():
    """Ψ = 2^8 = 256 > T = 16: both paths must flag overflow and agree on
    the first T branches (the deterministic valid subset)."""
    system = nd_chain(8)
    dn, sp = compile_system(system), compile_system_sparse(system)
    c0 = jnp.asarray([system.initial_spikes], jnp.int32)
    a = next_configs(c0, dn, 16)
    b = sparse_next_configs(c0, sp, 16)
    assert bool(np.asarray(a.overflow)[0]) and bool(np.asarray(b.overflow)[0])
    _assert_same_step(a, b)


@pytest.mark.parametrize("backend", ["sparse", "sparse_pallas"])
def test_supports_nd_batch_round_trip(backend):
    system, T = SYSTEMS["random-17"]
    be = get_backend(backend)
    assert be.supports_nd_batch
    comp = be.compile(system)
    rng = np.random.default_rng(7)
    flat = jnp.asarray(rng.integers(0, 4, size=(6, 17)), jnp.int32)
    nd = flat.reshape(2, 3, 17)
    a = be.expand(flat, comp, T)
    b = be.expand(nd, comp, T)
    assert b.configs.shape == (2, 3, T, 17)
    assert b.valid.shape == (2, 3, T)
    assert b.overflow.shape == (2, 3)
    np.testing.assert_array_equal(np.asarray(a.configs),
                                  np.asarray(b.configs).reshape(6, T, 17))
    np.testing.assert_array_equal(np.asarray(a.valid),
                                  np.asarray(b.valid).reshape(6, T))
    np.testing.assert_array_equal(np.asarray(a.overflow),
                                  np.asarray(b.overflow).reshape(6))


# ---------------------------------------------------------------------------
# fused sparse kernel: block sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block_b,block_t", [(1, 4), (2, 16), (4, 8), (8, 32)])
def test_sparse_kernel_block_sweep(block_b, block_t):
    system, T = SYSTEMS["random-17"]
    dn, sp = compile_system(system), compile_system_sparse(system)
    rng = np.random.default_rng(0)
    cfgs = jnp.asarray(rng.integers(0, 4, size=(7, 17)), jnp.int32)
    o, v, e, f = snp_step_sparse(cfgs, sp, max_branches=T,
                                 block_b=block_b, block_t=block_t)
    ref = next_configs(cfgs, dn, T)
    va = np.asarray(ref.valid)
    np.testing.assert_array_equal(va, np.asarray(v))
    np.testing.assert_array_equal(np.asarray(ref.overflow), np.asarray(f))
    np.testing.assert_array_equal(
        np.where(va[..., None], np.asarray(ref.configs), 0),
        np.where(va[..., None], np.asarray(o), 0))
    np.testing.assert_array_equal(
        np.where(va, np.asarray(ref.emissions), 0),
        np.where(va, np.asarray(e), 0))


# ---------------------------------------------------------------------------
# consumers on the sparse path
# ---------------------------------------------------------------------------

def test_successor_set_sparse_matches_ref():
    pi = paper_pi(True)
    assert successor_set(pi, (2, 1, 1), 16, "sparse") \
        == successor_set(pi, (2, 1, 1), 16, "ref")
    # pre-compiled sparse encodings pass straight through
    sp = compile_system_sparse(pi)
    assert successor_set(sp, (2, 1, 1), 16, "sparse") \
        == successor_set(pi, (2, 1, 1), 16, "ref")


def test_explore_sparse_on_seeded_random_systems():
    for seed in (0, 1):
        system = random_system(12, 2, 0.3, seed=seed)
        kw = dict(max_steps=5, frontier_cap=128, visited_cap=1024,
                  max_branches=32)
        ref = explore(system, backend="ref", **kw)
        got = explore(system, backend="sparse", **kw)
        np.testing.assert_array_equal(ref.configs, got.configs)
        assert ref.exhausted == got.exhausted
