"""Planner & autotuner tests (DESIGN.md §3 "Planner & autotuner").

Registry-driven coverage of the three tentpole pieces:

* every ``mode="auto"`` decision — whatever backend/encoding/block shape
  the planner picks — stays bit-identical to the ``"ref"`` oracle across
  the backend × encoding matrix (forced through poked cache entries);
* the autotune cache round-trips to disk keyed on the full
  ``(m, n, K_in, B, T)`` workload signature;
* a poisoned/corrupt cache file degrades to the analytic model with a
  ``UserWarning`` instead of crashing;
* ``KernelConfig`` validation: lower-time applicability errors, and the
  cache-collision audit (two block configurations resolve to *distinct*
  backend instances, so every backend-keyed executable cache — jit
  static args, ``_traces_shard_fn`` — keys on the block shape).
"""

import dataclasses
import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (KernelConfig, SystemPlan, available_backends,
                        explore, get_backend, resolve_kernel, run_traces)
from repro.core.autotune import (TunedChoice, WorkloadSignature, load_cache,
                                 lookup, model_choice, plan_for, predict_us,
                                 signature_of, store_choice)
from repro.core.generators import ring_lattice

SEEDS = [0, 1, 2]
STEPS = 6
T = 8


@pytest.fixture()
def cache_file(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    return path


def _system():
    return ring_lattice(12, 3, seed=0)


def _force_choice(system, choice):
    """Poke ``choice`` into the cache at the exact signature
    ``run_traces(seeds=SEEDS, max_branches=T)`` plans for."""
    sig = signature_of(system, workload=(len(SEEDS), T))
    store_choice(sig, choice)
    return sig


def _single_device_encodings(name):
    return [e for e in get_backend(name).supported_encodings()
            if e != "sharded"]


@pytest.mark.parametrize("name", sorted(available_backends()))
def test_auto_decisions_bit_identical_to_ref(name, cache_file):
    """Force the planner onto every (backend, encoding) cell and check
    run_traces under the default auto plan matches the ref oracle."""
    system = _system()
    ref = run_traces(system, steps=STEPS, seeds=SEEDS, max_branches=T,
                     backend="ref")
    blocks = {"block_b": 2, "block_t": 4} if \
        hasattr(get_backend(name), "block_b") else {}
    for encoding in _single_device_encodings(name):
        _force_choice(system, TunedChoice(backend=name, encoding=encoding,
                                          **blocks))
        plan = SystemPlan.for_system(system, workload=(len(SEEDS), T),
                                     mode="auto")
        assert plan.backend == name and plan.encoding == encoding
        got = run_traces(system, steps=STEPS, seeds=SEEDS, max_branches=T)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_auto_explore_matches_ref_archive(cache_file):
    """End-to-end: the default backend=None explore (planner-decided)
    discovers exactly the ref archive."""
    system = _system()
    ref = explore(system, max_steps=8, frontier_cap=32, visited_cap=256,
                  max_branches=T, backend="ref")
    auto = explore(system, max_steps=8, frontier_cap=32, visited_cap=256,
                   max_branches=T)
    assert sorted(ref.as_strings()) == sorted(auto.as_strings())


def test_cache_round_trips_on_full_signature(cache_file):
    sig = WorkloadSignature(m=7, n=13, kin=3, B=4, T=8)
    choice = TunedChoice(backend="sparse", encoding="ell", block_b=2,
                         block_t=4, us_per_step=12.5, source="measure")
    store_choice(sig, choice)

    got = lookup(sig)
    assert got is not None
    assert (got.backend, got.encoding, got.block_b, got.block_t) == \
        ("sparse", "ell", 2, 4)

    # the key carries every signature field: perturbing any one misses
    for field in ("m", "n", "kin", "B", "T"):
        other = dataclasses.replace(sig, **{field: getattr(sig, field) + 1})
        assert lookup(other) is None, field

    payload = json.loads(cache_file.read_text())
    assert "m7_n13_kin3_B4_T8" in payload["entries"]
    assert load_cache(cache_file) == payload["entries"]


def test_corrupt_cache_degrades_to_model_with_warning(cache_file):
    cache_file.write_text("{this is not json")
    with pytest.warns(UserWarning, match="autotune cache"):
        plan = SystemPlan.for_system(_system(), workload=(4, 8),
                                     mode="auto")
    # still a usable plan (model or heuristic decided), and still correct
    assert isinstance(plan, SystemPlan)
    system = _system()
    ref = run_traces(system, steps=STEPS, seeds=SEEDS, max_branches=T,
                     backend="ref")
    with pytest.warns(UserWarning, match="autotune cache"):
        got = run_traces(system, steps=STEPS, seeds=SEEDS, max_branches=T)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_poisoned_entry_is_skipped_not_fatal(cache_file):
    """Valid JSON, nonsense content: the entry is ignored, planning
    proceeds (model/heuristic), nothing raises."""
    system = _system()
    sig = signature_of(system, workload=(len(SEEDS), T))
    cache_file.write_text(json.dumps({"version": 1, "entries": {
        sig.key(): {"backend": "no-such-backend", "block_b": "huge"},
    }}))
    assert lookup(sig) is None
    plan = SystemPlan.for_system(system, workload=(len(SEEDS), T),
                                 mode="auto")
    assert isinstance(plan, SystemPlan)


def test_measure_mode_times_and_persists(cache_file):
    system = _system()
    plan = SystemPlan.for_system(system, workload=(4, T), mode="measure")
    assert plan.backend in available_backends()
    sig = signature_of(system, workload=(4, T))
    entries = load_cache(cache_file)
    assert sig.key() in entries
    assert entries[sig.key()]["source"] == "measure"
    assert entries[sig.key()]["us_per_step"] > 0
    # and the measured winner is found by a subsequent auto plan
    again = SystemPlan.for_system(system, workload=(4, T), mode="auto")
    assert again.backend == plan.backend


def test_model_predicts_and_guards_extrapolation(cache_file):
    small = WorkloadSignature(m=16, n=32, kin=3, B=8, T=8)
    assert predict_us(small, "ref") > 0
    choice = model_choice(small)
    assert choice is not None and choice.source == "model"
    # interpret-mode kernels are never picked far outside their fitted
    # support: at bench-exceeding work sizes the model must choose one of
    # the non-interpret backends, which the baseline says win there anyway
    huge = WorkloadSignature(m=10 ** 5, n=2 * 10 ** 5, kin=8,
                             B=256, T=64)
    assert model_choice(huge).backend in ("ref", "sparse")


def test_workload_hint_reaches_the_signature():
    system = _system()
    sig = signature_of(system, workload=(17, 5))
    assert (sig.B, sig.T) == (17, 5)
    assert (sig.m, sig.n) == (system.num_neurons, system.num_rules)
    in_deg_max = sig.kin
    assert in_deg_max >= 1


def test_kernel_config_validation():
    with pytest.raises(ValueError, match="block_b"):
        KernelConfig(block_b=0)
    with pytest.raises(ValueError, match="block_t"):
        KernelConfig(block_t=-4)
    cfg = KernelConfig(block_b=4).merged(block_t=8)
    assert (cfg.block_b, cfg.block_t, cfg.block_n) == (4, 8, None)
    assert hash(KernelConfig(block_b=4)) == hash(KernelConfig(block_b=4))


def test_resolve_kernel_applicability_errors():
    cfg = KernelConfig(block_b=4, block_t=8)
    for name in ("ref", "sparse"):
        with pytest.raises(ValueError, match="no kernel block"):
            resolve_kernel(get_backend(name), SystemPlan(kernel=cfg))
    with pytest.raises(ValueError, match="block_n"):
        resolve_kernel(get_backend("sparse_pallas"),
                       SystemPlan(kernel=KernelConfig(block_n=128)))
    # and the same errors surface at lower/compile time
    with pytest.raises(ValueError, match="no kernel block"):
        get_backend("ref").compile(_system(), plan=SystemPlan(kernel=cfg))


def test_resolve_kernel_reblocks_and_keys_caches():
    base = get_backend("sparse_pallas")
    be1 = resolve_kernel(base, SystemPlan(
        kernel=KernelConfig(block_b=2, block_t=4)))
    be2 = resolve_kernel(base, SystemPlan(
        kernel=KernelConfig(block_b=4, block_t=8)))
    assert (be1.block_b, be1.block_t) == (2, 4)
    assert be1 != be2 and hash(be1) != hash(be2)
    # None axes keep the backend's own defaults
    be3 = resolve_kernel(base, SystemPlan(kernel=KernelConfig(block_b=2)))
    assert (be3.block_b, be3.block_t) == (2, base.block_t)
    # the lru-cached distributed shard_map keys on the instance: distinct
    # block configs -> distinct executables, equal config -> cache hit
    from repro.core.distributed import _flat_mesh, _traces_shard_fn
    mesh, axis = _flat_mesh(None)
    f1 = _traces_shard_fn(mesh, axis, 4, 8, "first", be1)
    f2 = _traces_shard_fn(mesh, axis, 4, 8, "first", be2)
    f1b = _traces_shard_fn(mesh, axis, 4, 8, "first", resolve_kernel(
        base, SystemPlan(kernel=KernelConfig(block_b=2, block_t=4))))
    assert f1 is not f2
    assert f1 is f1b


def test_plan_kernel_runs_bit_identical_with_odd_blocks(cache_file):
    """A plan-carried kernel config with awkward block shapes exercises
    the padding path and still matches ref bit-for-bit."""
    system = _system()
    ref = run_traces(system, steps=STEPS, seeds=SEEDS, max_branches=T,
                     backend="ref")
    for name, cfg in [("pallas", KernelConfig(block_b=3, block_t=5)),
                      ("sparse_pallas", KernelConfig(block_b=3, block_t=5))]:
        got = run_traces(system, steps=STEPS, seeds=SEEDS, max_branches=T,
                         backend=name, plan=SystemPlan(kernel=cfg))
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_static_mode_keeps_the_heuristic(cache_file):
    """mode="static" never consults cache or model (a poisoned cache file
    must not even be read)."""
    cache_file.write_text("{broken")
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        plan = SystemPlan.for_system(_system())
    assert plan.backend is None and plan.encoding in ("ell", "hybrid")


def test_sharded_planning_picks_sharded_capable_backend(cache_file):
    system = _system()
    plan = plan_for(system, num_shards=2, workload=(8, T))
    if plan is not None:
        assert plan.encoding == "ell" and plan.num_shards == 2
        assert "sharded" in \
            get_backend(plan.backend).supported_encodings()


def test_planner_skips_kernels_a_chip_refuses(cache_file, monkeypatch):
    """With the kernels compiled instead of emulated (as on a TPU), the
    planner never hands out a backend whose ``compile_refusal`` names the
    shape — so a refusal never reaches the degrade chain at run time."""
    import repro.core.backend as backend_mod
    from repro.core import add_degrade_listener, paper_pi, \
        remove_degrade_listener
    monkeypatch.setattr(backend_mod, "resolve_interpret", lambda flag: False)
    system = paper_pi(covering=True)
    for workload in ((128, 16), (256, 64), (64, 32)):
        plan = SystemPlan.for_system(system, workload=workload, mode="auto")
        be = get_backend(plan.backend)
        assert getattr(be, "compile_refusal",
                       lambda *a, **k: None)(3, 5) is None, plan
    events = []
    add_degrade_listener(events.append)
    try:
        res = explore(system, max_steps=8, frontier_cap=128,
                      visited_cap=1024, max_branches=16)
    finally:
        remove_degrade_listener(events.append)
    assert not events and res.num_discovered > 1
