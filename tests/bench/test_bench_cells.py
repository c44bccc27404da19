"""Every cell of ``BENCHMARK.json`` runs end to end on the CPU at a tiny size
and comes out correct; with the timed path broken underneath, the same
run comes out not correct."""

import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchkit  # noqa: E402

from bench import spec  # noqa: E402

CELLS = [c["name"] for c in spec.load_spec(benchkit.ROOT)["workloads"]]
ENTRY_OF = {c["name"]: spec.load_mix(benchkit.ROOT, c["traffic"])["entry"]
            for c in spec.load_spec(benchkit.ROOT)["workloads"]}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchkit.tiny_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(root, workload, monkeypatch):
    res = benchkit.run_tiny(root, workload, monkeypatch)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    cells = spec.load_spec(root)
    cell = spec.find_cell(cells, workload)
    want = {m["name"] for m in spec.end_to_end_for(cells, cell)}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["checks"].values())


# -- faults of the timed path ------------------------------------------------


def _break_traces(monkeypatch, fault):
    import jax.numpy as jnp

    from repro.core import engine
    from repro.serve import snp_service
    orig = engine.run_traces

    def run(comp, *, steps, seeds, **kw):
        if fault == "half_batch":
            seeds = np.asarray(seeds)
            half = orig(comp, steps=steps, seeds=seeds[:len(seeds) // 2],
                        **kw)
            return type(half)(*(jnp.concatenate(
                [a, jnp.zeros((len(seeds) - a.shape[0],) + a.shape[1:],
                              a.dtype)]) for a in half))
        out = orig(comp, steps=steps, seeds=seeds, **kw)
        if fault == "unchanged":
            return out._replace(configs=jnp.broadcast_to(
                comp.init_config, out.configs.shape))
        return out._replace(configs=out.configs.at[:, -1, 0].add(1))

    monkeypatch.setattr(engine, "run_traces", run)
    monkeypatch.setattr(snp_service, "run_traces", run)
    if fault == "half_batch":
        # the service pads its batch: leave out half of its requests
        run_batch = snp_service.SNPTraceService._run_batch

        def half_requests(self, comp, policy, max_branches, tickets, reqs,
                          backend=None):
            out = run_batch(self, comp, policy, max_branches, tickets, reqs,
                            backend)
            for t in tickets[(len(tickets) + 1) // 2:]:
                r = out[t]
                out[t] = dataclasses.replace(
                    r, configs=np.zeros_like(r.configs))
            return out

        monkeypatch.setattr(snp_service, "run_traces", orig)
        monkeypatch.setattr(snp_service.SNPTraceService, "_run_batch",
                            half_requests)


def _break_explore(monkeypatch, fault):
    from repro.core import engine
    orig = engine.explore

    def run(comp, **kw):
        if fault == "half_batch":
            kw["frontier_cap"] //= 2
            return orig(comp, **kw)
        res = orig(comp, **kw)
        if fault == "unchanged":
            return dataclasses.replace(res, configs=res.configs[:1],
                                       num_discovered=1)
        configs = res.configs.copy()
        configs[-1, 0] += 1
        return dataclasses.replace(res, configs=configs)

    monkeypatch.setattr(engine, "explore", run)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(root, workload, fault,
                                          monkeypatch):
    if ENTRY_OF[workload] == "explore":
        _break_explore(monkeypatch, fault)
    else:
        _break_traces(monkeypatch, fault)
    res = benchkit.run_tiny(root, workload, monkeypatch)
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
