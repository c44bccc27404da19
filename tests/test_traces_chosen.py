"""Choose-then-step: the trace scan builds only the successor it keeps.

``SparseBackend.step_chosen`` draws each trace's branch index from the
branch count and steps that branch alone; a backend without the method is
stepped by expanding every candidate and keeping one.  Both paths must give
bit-identical traces, in every encoding, semantics tier and policy, and the
chosen path must never build the ``(B, T, m)`` candidate block.
"""

import dataclasses
import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import SEMANTICS, delayed_variant, random_states
from repro.core import run_trace, run_traces
from repro.core.backend import SparseBackend
from repro.core.engine import _traces_scan, successors_per_step
from repro.core.generators import power_law, random_system, sorting
from repro.core.matrix import compile_system_sparse
from repro.core.semantics import (TABLE_MAX_RULE_SLOTS, fired_lookup,
                                  sparse_next_configs)
from repro.serve import SNPTraceService, TraceRequest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Small enough to branch past T=4 (overflow) and to let some random traces
# die (no applicable rule), in both semantics tiers.
SYSTEM = random_system(12, 2, 0.3, seed=3)
T = 4
STEPS = 16
SEEDS = np.arange(9) * 977 + 3
# hub_threshold=2 puts most of the in-adjacency into the COO tail
ENCODINGS = {"ell": None, "hybrid": 2}


@dataclasses.dataclass(frozen=True)
class ExpandOnly:
    """A backend with the sparse ``expand`` and no ``step_chosen``: the
    trace scan steps it by expand-and-pick."""

    name: str = "expand_only"
    supports_nd_batch: bool = True
    pad_multiple: int = 1
    materializes_spiking: bool = False

    def expand(self, configs, comp, max_branches):
        return SparseBackend().expand(configs, comp, max_branches)


def _comp(semantics, encoding):
    system = delayed_variant(SYSTEM) if semantics == "delays" else SYSTEM
    return compile_system_sparse(system, hub_threshold=ENCODINGS[encoding],
                                 semantics=semantics)


def _assert_same_traces(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("policy", ["random", "first"])
@pytest.mark.parametrize("encoding", sorted(ENCODINGS))
@pytest.mark.parametrize("semantics", SEMANTICS)
def test_chosen_traces_match_expand_and_pick(semantics, encoding, policy):
    comp = _comp(semantics, encoding)
    if encoding == "hybrid":
        assert comp.coo_src.shape[0] > 0
    got = run_traces(comp, steps=STEPS, seeds=SEEDS, policy=policy,
                     max_branches=T, backend="sparse")
    want = run_traces(comp, steps=STEPS, seeds=SEEDS, policy=policy,
                      max_branches=T, backend=ExpandOnly())
    _assert_same_traces(got, want)
    assert np.asarray(got.branch_overflow).any()
    if policy == "random":
        assert not np.asarray(got.alive).all()


@pytest.mark.parametrize("encoding", sorted(ENCODINGS))
@pytest.mark.parametrize("semantics", SEMANTICS)
def test_step_chosen_matches_expanded_row(semantics, encoding):
    """One step from arbitrary states, dead rows and rows with Ψ > T among
    them: the chosen successor is the expansion's row at that index."""
    comp = _comp(semantics, encoding)
    cfgs = random_states(SYSTEM, semantics, 32, seed=5)
    m = SYSTEM.num_neurons
    cfgs[:16, m:] = 0           # delays: every neuron open, so Ψ can pass T
    cfgs[:2] = 0                # no rule applies: dead
    cfgs = jnp.asarray(cfgs)
    be = SparseBackend()
    full = be.expand(cfgs, comp, T)
    n_full = jnp.sum(full.valid, axis=-1, dtype=jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(7), cfgs.shape[0])

    def choose(n):
        return jax.vmap(lambda k, c: jax.random.randint(
            k, (), 0, jnp.maximum(c, 1)))(keys, n)

    got = be.step_chosen(cfgs, comp, T, choose)
    idx = np.asarray(choose(n_full))
    rows = np.arange(cfgs.shape[0])
    np.testing.assert_array_equal(np.asarray(got.n_valid), np.asarray(n_full))
    np.testing.assert_array_equal(np.asarray(got.overflow),
                                  np.asarray(full.overflow))
    np.testing.assert_array_equal(np.asarray(got.configs),
                                  np.asarray(full.configs)[rows, idx])
    np.testing.assert_array_equal(np.asarray(got.emissions),
                                  np.asarray(full.emissions)[rows, idx])
    n = np.asarray(n_full)
    assert (n == 0).any() and np.asarray(full.overflow).any() \
        and ((n > 1) & (idx > 0)).any()


# R = 2, 12 and 16 rules on one neuron: either side of the rule-space
# threshold, the rule-heavy ones branching past T
RULE_SLOTS = {"power-law": power_law(40, 3, seed=2),
              "random-r12": random_system(10, 12, 0.4, max_spikes=6, seed=4),
              "sort-16": sorting(16)}


@pytest.mark.parametrize("name", sorted(RULE_SLOTS))
def test_step_chosen_matches_expanded_row_across_rule_slots(name):
    """The chosen successor, found in rule space past
    ``TABLE_MAX_RULE_SLOTS`` rules on a neuron and through the table below
    it, is the row ``sparse_next_configs`` puts at the chosen index."""
    system = RULE_SLOTS[name]
    comp = compile_system_sparse(system)
    R = comp.max_rules_per_neuron
    assert fired_lookup(comp, 1) == (
        "rules" if R > TABLE_MAX_RULE_SLOTS else "table")
    rng = np.random.default_rng(R)
    cfgs = jnp.asarray(rng.integers(0, min(R, 7) + 2,
                                    (24, system.num_neurons)), jnp.int32)
    full = sparse_next_configs(cfgs, comp, T)
    keys = jax.random.split(jax.random.PRNGKey(R), cfgs.shape[0])

    def choose(n):
        return jax.vmap(lambda k, c: jax.random.randint(
            k, (), 0, jnp.maximum(c, 1)))(keys, n)

    got = SparseBackend().step_chosen(cfgs, comp, T, choose)
    n_full = jnp.sum(full.valid, axis=-1, dtype=jnp.int32)
    idx = np.asarray(choose(n_full))
    rows = np.arange(cfgs.shape[0])
    np.testing.assert_array_equal(np.asarray(got.n_valid), np.asarray(n_full))
    np.testing.assert_array_equal(np.asarray(got.configs),
                                  np.asarray(full.configs)[rows, idx])
    np.testing.assert_array_equal(np.asarray(got.emissions),
                                  np.asarray(full.emissions)[rows, idx])
    if name != "sort-16":
        assert ((np.asarray(n_full) > 1) & (idx > 0)).any()


def _array_shapes(jaxpr):
    """Shapes of every value an equation of ``jaxpr`` (or of a jaxpr nested
    in one, such as a scan body or a ``fori_loop``) produces."""
    shapes = set()
    for eqn in jaxpr.eqns:
        shapes.update(tuple(v.aval.shape) for v in eqn.outvars
                      if hasattr(v.aval, "shape"))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    shapes |= _array_shapes(inner)
    return shapes


@pytest.mark.parametrize("backend,builds_block",
                         [(SparseBackend(), False), (ExpandOnly(), True)],
                         ids=["sparse", "expand_only"])
def test_traces_scan_builds_candidate_block_only_without_step_chosen(
        backend, builds_block):
    comp = _comp("no_delays", "hybrid")
    B, m, Tb = 5, comp.num_neurons, 16          # distinct B, T and m
    assert len({B, m, Tb}) == 3
    c0s = jnp.broadcast_to(comp.init_config, (B, m))
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(B, dtype=jnp.uint32))
    scan = functools.partial(_traces_scan, steps=3, max_branches=Tb,
                             policy="random", backend=backend)
    shapes = _array_shapes(jax.make_jaxpr(scan)(comp, keys, c0s).jaxpr)
    assert ((B, Tb, m) in shapes) == builds_block
    assert successors_per_step(backend, Tb) == (Tb if builds_block else 1)


def test_service_request_on_chosen_path_matches_run_trace():
    comp = _comp("no_delays", "hybrid")
    svc = SNPTraceService(batch_size=4, backend="sparse")
    tickets = [svc.submit(TraceRequest(comp, steps=STEPS, seed=int(s),
                                       policy="random", max_branches=T))
               for s in SEEDS[:3]]
    done = svc.drain()
    for ticket, seed in zip(tickets, SEEDS[:3]):
        want = run_trace(comp, steps=STEPS, policy="random", seed=int(seed),
                         max_branches=T, backend=ExpandOnly())
        res = done[ticket]
        np.testing.assert_array_equal(res.configs, np.asarray(want.configs))
        np.testing.assert_array_equal(res.emissions,
                                      np.asarray(want.emissions))
        np.testing.assert_array_equal(res.alive, np.asarray(want.alive))
        np.testing.assert_array_equal(res.branch_overflow,
                                      np.asarray(want.branch_overflow))


def test_distributed_traces_on_chosen_path_match_expand_and_pick():
    """``run_traces_distributed`` over four CPU devices, on the chosen path,
    against the single-device expand-and-pick scan."""
    body = textwrap.dedent("""
        import dataclasses
        import jax
        import numpy as np
        from repro.core import run_traces
        from repro.core.backend import SparseBackend
        from repro.core.distributed import run_traces_distributed
        from repro.core.generators import random_system, with_delays
        from repro.core.matrix import compile_system_sparse

        assert len(jax.devices()) == 4

        @dataclasses.dataclass(frozen=True)
        class ExpandOnly:
            name: str = "expand_only"
            supports_nd_batch: bool = True
            pad_multiple: int = 1
            materializes_spiking: bool = False

            def expand(self, configs, comp, max_branches):
                return SparseBackend().expand(configs, comp, max_branches)

        system = random_system(12, 2, 0.3, seed=3)
        seeds = np.arange(9) * 977 + 3
        for semantics, sys_ in (("no_delays", system),
                                ("delays",
                                 with_delays(system, lambda k, r: k % 3))):
            comp = compile_system_sparse(sys_, hub_threshold=2,
                                         semantics=semantics)
            got = run_traces_distributed(comp, steps=16, seeds=seeds,
                                         policy="random", max_branches=4,
                                         backend="sparse")
            want = run_traces(comp, steps=16, seeds=seeds, policy="random",
                              max_branches=4, backend=ExpandOnly())
            for x, y in zip(got, want):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        print("OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "OK" in proc.stdout
