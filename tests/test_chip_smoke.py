"""``chip_smoke.py``'s phases at tiny sizes on the CPU (kernels emulated),
so the chip's smoke path stays covered here; plus the exactness system
against the pure-Python oracle and the script's refusal without a TPU."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import oracle
from repro.core import available_backends, explore

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
import chip_smoke as smoke  # noqa: E402

TINY = smoke.Sizes(
    trace_m=64, traces=8, trace_steps=4, trace_branches=8,
    kernel_m=32, kernel_traces=8,
    explore_m=64, visited_cap=2048, frontier_cap=16, explore_branches=8,
    explore_steps=3, serve_m=32, requests=8, serve_steps=16,
    dist_frontier_cap=64, dist_steps=2)


@pytest.fixture(autouse=True)
def _fresh_autotune_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))


@pytest.mark.parametrize("phase", smoke.ONE_CHIP, ids=lambda f: f.__name__)
def test_one_chip_phase_at_tiny_size(phase, capsys):
    assert phase(TINY)
    out = capsys.readouterr().out
    name = phase.__name__[len("phase_"):]
    assert f"phase={name} ok" in out or (
        name == "step" and "phase=step m=" in out and " ok " in out)


def test_paper_pi_phase_runs_every_backend_emulated():
    results = smoke.phase_paper_pi(TINY)
    assert set(results) == {"planner", *available_backends()}


def test_exact_system_leaves_bf16_range_and_matches_oracle():
    system = smoke.exact_system()
    assert max(r.produce for r in system.rules) > 256
    want, _ = oracle.explore(system, 5)
    want = {s[:system.num_neurons] for s in want}
    for name in available_backends():
        res = explore(system, backend=name, max_steps=5, frontier_cap=256,
                      visited_cap=4096, max_branches=16)
        assert not (res.branch_overflow or res.frontier_overflow
                    or res.visited_overflow), name
        assert {tuple(int(v) for v in r) for r in res.configs} == want, name
        assert res.configs.max() > 2 ** 8


def test_four_chip_phases_on_virtual_devices(tmp_path):
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(REPO)!r})
        import chip_smoke as smoke
        tiny = smoke.Sizes(trace_m=64, traces=8, trace_steps=4,
                           trace_branches=8, explore_m=64,
                           visited_cap=2048, explore_branches=8,
                           dist_frontier_cap=64, dist_steps=2)
        assert smoke.run(smoke.FOUR_CHIPS, tiny)
        print("FOUR_OK")
    """)
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "REPRO_AUTOTUNE_CACHE": str(tmp_path / "at.json")}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    assert "FOUR_OK" in proc.stdout
    assert "phase=explore_distributed ok" in proc.stdout
    assert "phase=traces_distributed ok" in proc.stdout


@pytest.mark.parametrize("spans, want", [
    ([], 0.0),
    ([(0.0, 2.0), (3.0, 4.0)], 3.0),
    # a kernel traced inside its caller's trace counts once
    ([(1.0, 1.5), (0.0, 2.0), (1.8, 3.0)], 3.0),
])
def test_compile_seconds_count_nested_spans_once(spans, want):
    assert smoke._covered(spans) == pytest.approx(want)


def test_timed_line_splits_compile_from_run(capsys):
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda x: jnp.cumsum(x * 3))
    smoke.run([lambda sizes: smoke.timed(
        "probe", None, lambda: fn(jnp.arange(7)).block_until_ready())], TINY)
    line = capsys.readouterr().out.splitlines()[0]
    fields = dict(f.split("=", 1) for f in line.split() if "=" in f)
    assert float(fields["compile_s"]) > 0 and float(fields["run_s"]) >= 0


def test_smoke_exits_nonzero_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300, cwd=REPO)
    assert proc.returncode != 0
    assert "phase=" not in proc.stdout and '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.mark.parametrize("env_dir", [None, "cache-from-env"])
def test_compile_cache_location(env_dir, tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO / "src")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    code = ("import jax; from repro.launch.compile_cache import "
            "enable_compile_cache; print(enable_compile_cache()); "
            "print(jax.config.jax_compilation_cache_dir)")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    returned, configured = proc.stdout.split()[-2:]
    want = str(tmp_path / env_dir) if env_dir else str(REPO / ".jax_cache")
    assert returned == configured == want
