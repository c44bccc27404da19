"""Step-backend layer tests: registry, cross-backend equivalence end-to-end
through every consumer (explore, run_trace, run_traces), batched trace
serving, and the snp_service batching front end.

Equivalence tests are **registry-driven**: they parametrize over
``available_backends()`` with ``"ref"`` as the oracle, so any newly
registered backend (sparse today, whatever comes next) is oracle-checked
through every consumer with zero test changes.  Each backend compiles its
own encoding via ``backend.compile`` — exactly the consumer code path.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import conftest
from repro.core import (available_backends, compile_system, explore,
                        get_backend, paper_pi, register_backend, run_trace,
                        run_traces)
from repro.core.backend import (PallasBackend, RefBackend, SparseBackend,
                                SparsePallasBackend)
from repro.core.generators import nd_chain
from repro.serve.snp_service import SNPTraceService, TraceRequest

# consumer-equivalence workloads: the cheap subset of the shared fixtures
SYSTEMS = {k: conftest.EQUIV_SYSTEMS[k]
           for k in ("paper-pi", "nd-chain-4", "random-16")}

NON_REF = [b for b in available_backends() if b != "ref"]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_contents_and_lookup():
    assert {"ref", "pallas", "sparse", "sparse_pallas"} \
        <= set(available_backends())
    assert get_backend("ref") == RefBackend()
    assert get_backend("pallas").name == "pallas"
    assert get_backend("sparse") == SparseBackend()
    assert get_backend("sparse_pallas").name == "sparse_pallas"
    # instances pass through unchanged
    be = PallasBackend(block_t=16)
    assert get_backend(be) is be
    with pytest.raises(ValueError, match="unknown step backend"):
        get_backend("no-such-backend")
    with pytest.raises(ValueError, match="already registered"):
        register_backend(RefBackend())


def test_backend_metadata():
    ref, pal = get_backend("ref"), get_backend("pallas")
    sp, spp = get_backend("sparse"), get_backend("sparse_pallas")
    for b in (ref, pal, sp, spp):
        assert b.supports_nd_batch
    assert ref.pad_multiple == 1 and sp.pad_multiple == 1
    assert pal.pad_multiple == pal.block_b
    assert spp.pad_multiple == spp.block_b
    assert ref.materializes_spiking
    assert not any(b.materializes_spiking for b in (pal, sp, spp))


def test_sparse_backends_reject_dense_compilation():
    comp = compile_system(paper_pi(True))
    cfgs = jnp.asarray([[2, 1, 1]], jnp.int32)
    for name in ("sparse", "sparse_pallas"):
        with pytest.raises(TypeError, match="CompiledSparseSNP"):
            get_backend(name).expand(cfgs, comp, 8)


@pytest.mark.parametrize("name", NON_REF)
def test_backends_agree_on_step_out(name):
    system = paper_pi(True)
    cfgs = jnp.asarray([[2, 1, 1], [2, 1, 2], [0, 0, 0]], jnp.int32)
    ref, be = get_backend("ref"), get_backend(name)
    a = ref.expand(cfgs, ref.compile(system), 8)
    b = be.expand(cfgs, be.compile(system), 8)
    conftest.assert_same_step(a, b)
    assert b.spiking is None  # only ref materializes S


# ---------------------------------------------------------------------------
# equivalence through the consumers (registry-driven)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", NON_REF)
@pytest.mark.parametrize("name", sorted(SYSTEMS))
def test_explore_backend_equivalence(name, backend):
    system, T = SYSTEMS[name]
    kw = dict(max_steps=6, frontier_cap=128, visited_cap=1024, max_branches=T)
    ref = explore(system, backend="ref", **kw)
    got = explore(system, backend=backend, **kw)
    # identical archives *in discovery order*, identical flags
    np.testing.assert_array_equal(ref.configs, got.configs)
    assert ref.num_discovered == got.num_discovered
    assert ref.steps == got.steps
    assert (ref.branch_overflow, ref.frontier_overflow, ref.visited_overflow) \
        == (got.branch_overflow, got.frontier_overflow, got.visited_overflow)


@pytest.mark.parametrize("backend", NON_REF)
@pytest.mark.parametrize("policy", ["first", "random"])
def test_run_trace_backend_equivalence(policy, backend):
    for name, (system, T) in sorted(SYSTEMS.items()):
        ref = run_trace(system, steps=10, policy=policy, seed=11,
                        max_branches=T, backend="ref")
        got = run_trace(system, steps=10, policy=policy, seed=11,
                        max_branches=T, backend=backend)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_explore_accepts_backend_instance():
    system = paper_pi(True)
    for be in (PallasBackend(block_b=4, block_t=8, block_n=8),
               SparsePallasBackend(block_b=4, block_t=8)):
        res = explore(system, max_steps=4, frontier_cap=32, visited_cap=256,
                      max_branches=16, backend=be)
        ref = explore(system, max_steps=4, frontier_cap=32, visited_cap=256,
                      max_branches=16)
        np.testing.assert_array_equal(res.configs, ref.configs)


def test_explore_loop_is_on_device_while_loop():
    """The BFS must be a single lax.while_loop: tracing the loop body must
    happen once, with a traced (non-concrete) frontier_n — i.e. no host
    Python loop peeking at per-step scalars."""
    from repro.core import engine

    comp = compile_system(paper_pi(True))
    state = engine._init_state(comp, 32, 256)
    traced = jax.make_jaxpr(
        lambda s: engine._explore_loop(s, comp, 8, 16, get_backend("ref"))
    )(state)
    assert "while" in str(traced)


# ---------------------------------------------------------------------------
# batched trace serving
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["first", "random"])
def test_run_traces_matches_per_seed_run_trace(policy):
    comp = compile_system(paper_pi(True))
    seeds = [0, 1, 7, 42, 1234]
    cfgs, emis, alive, *_ = run_traces(comp, steps=12, seeds=seeds, policy=policy)
    assert cfgs.shape == (len(seeds), 12, comp.num_neurons)
    for i, s in enumerate(seeds):
        c, e, a, *_ = run_trace(comp, steps=12, policy=policy, seed=s)
        np.testing.assert_array_equal(np.asarray(cfgs[i]), np.asarray(c))
        np.testing.assert_array_equal(np.asarray(emis[i]), np.asarray(e))
        np.testing.assert_array_equal(np.asarray(alive[i]), np.asarray(a))


@pytest.mark.parametrize("backend", NON_REF)
def test_run_traces_backend_equivalence(backend):
    system = nd_chain(4)
    seeds = list(range(6))
    ref = run_traces(system, steps=8, seeds=seeds, policy="random",
                     max_branches=32, backend="ref")
    got = run_traces(system, steps=8, seeds=seeds, policy="random",
                     max_branches=32, backend=backend)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_traces_rejects_bad_input():
    comp = compile_system(paper_pi(True))
    with pytest.raises(ValueError, match="policy"):
        run_traces(comp, steps=4, seeds=[0], policy="greedy")
    with pytest.raises(ValueError, match="1-D"):
        run_traces(comp, steps=4, seeds=[[0, 1]])


# ---------------------------------------------------------------------------
# snp_service
# ---------------------------------------------------------------------------

def test_service_batches_heterogeneous_requests():
    svc = SNPTraceService(batch_size=8, step_bucket=8)
    pi, chain = paper_pi(True), nd_chain(4)
    reqs = {
        "a": TraceRequest(pi, steps=5, policy="random", seed=7),
        "b": TraceRequest(pi, steps=11, policy="random", seed=9),
        "c": TraceRequest(pi, steps=6, policy="first"),
        "d": TraceRequest(chain, steps=4, policy="random", seed=1,
                          max_branches=32),
    }
    tickets = {k: svc.submit(r) for k, r in reqs.items()}
    assert svc.pending == 4
    results = svc.drain()
    assert svc.pending == 0
    # three groups: (pi, random), (pi, first), (chain, random)
    assert svc.stats()["device_calls"] == 3
    assert svc.stats()["traces_served"] == 4
    for k, r in reqs.items():
        got = results[tickets[k]]
        c, e, a, *_ = run_trace(r.system, steps=r.steps, policy=r.policy,
                            seed=r.seed, max_branches=r.max_branches)
        assert got.configs.shape == (r.steps, 4 if k == "d" else 3)
        np.testing.assert_array_equal(got.configs, np.asarray(c))
        np.testing.assert_array_equal(got.emissions, np.asarray(e))
        np.testing.assert_array_equal(got.alive, np.asarray(a))


def test_service_serves_256_trace_batch_in_one_call():
    svc = SNPTraceService(batch_size=256, step_bucket=8)
    pi = paper_pi(True)
    tickets = [svc.submit(TraceRequest(pi, steps=8, policy="random", seed=s))
               for s in range(256)]
    results = svc.drain()
    assert svc.stats()["device_calls"] == 1   # one jitted run_traces launch
    assert len(results) == 256
    # spot-check a few against solo traces
    for s in (0, 17, 255):
        c, e, _, *_ = run_trace(pi, steps=8, policy="random", seed=s)
        np.testing.assert_array_equal(results[tickets[s]].configs,
                                      np.asarray(c))
        np.testing.assert_array_equal(results[tickets[s]].emissions,
                                      np.asarray(e))


def test_service_chunks_oversized_groups_and_pads_short_ones():
    svc = SNPTraceService(batch_size=4, step_bucket=4)
    pi = paper_pi(True)
    tickets = [svc.submit(TraceRequest(pi, steps=3, seed=s, policy="random"))
               for s in range(6)]
    results = svc.drain()
    assert svc.stats()["device_calls"] == 2   # 6 requests / batch_size 4
    for s in range(6):
        c, _, _, *_ = run_trace(pi, steps=3, policy="random", seed=s)
        np.testing.assert_array_equal(results[tickets[s]].configs,
                                      np.asarray(c))


def test_service_with_sparse_backend_matches_ref_service():
    svc = SNPTraceService(batch_size=4, step_bucket=4, backend="sparse")
    pi = paper_pi(True)
    t = svc.submit(TraceRequest(pi, steps=6, policy="random", seed=3))
    got = svc.drain()[t]
    c, e, a, *_ = run_trace(pi, steps=6, policy="random", seed=3)
    np.testing.assert_array_equal(got.configs, np.asarray(c))
    np.testing.assert_array_equal(got.emissions, np.asarray(e))


def test_service_validates_requests():
    with pytest.raises(ValueError, match="policy"):
        TraceRequest(paper_pi(True), steps=4, policy="greedy")
    with pytest.raises(ValueError, match="steps"):
        TraceRequest(paper_pi(True), steps=0)
    svc = SNPTraceService(batch_size=2, max_steps=16)
    with pytest.raises(ValueError, match="max_steps"):
        svc.submit(TraceRequest(paper_pi(True), steps=64))
