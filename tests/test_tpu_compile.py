"""The fused kernels compiled for a described TPU v5e (no chip needed).

Each test compiles a kernel wrapper with ``interpret=False`` at a shape
``chip_smoke.py`` runs, or checks that a shape the TPU compiler would
reject is refused at lower time with a ``ValueError``.  The topology is
described inside a fixture, never at import: only one process at a time
may load the TPU library, and every test worker imports this file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import (PallasBackend, SparsePallasBackend, SystemPlan,
                        compile_system, compile_system_sparse, paper_pi)
from repro.core.generators import power_law, ring
from repro.kernels.snp_step.kernel import VMEM_LIMIT_BYTES, vmem_bytes
from repro.kernels.snp_step.ops import snp_step
from repro.kernels.snp_step.sparse_ops import snp_step_sparse


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=sharding), tree)


def _smoke_exact_system():
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    import chip_smoke
    return chip_smoke.exact_system()


# (system, B, T): paper Π and the exactness system as chip_smoke explores
# them, and the step phase's kernel-width power-law system.
DENSE_SHAPES = {
    "paper_pi": (lambda: paper_pi(covering=True), 128, 16),
    "exact": (_smoke_exact_system, 256, 16),
    "power_law_2048": (lambda: power_law(2048, 4, seed=2, max_in=64), 64, 32),
}


@pytest.mark.parametrize("name", sorted(DENSE_SHAPES))
def test_dense_kernel_compiles_for_v5e(name, one_chip):
    make, B, T = DENSE_SHAPES[name]
    system = make()
    be = PallasBackend(interpret=False)
    comp = be.compile(system)          # not refused at this width
    cfg = jax.ShapeDtypeStruct((B, system.num_neurons), jnp.int32,
                               sharding=one_chip)
    compiled = snp_step.lower(
        cfg, _abstract(comp, one_chip), max_branches=T,
        kernel=be.kernel_config, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_delayed_dense_kernel_compiles_for_v5e(one_chip):
    # m=512 ran out of VMEM at the previous default blocks (8, 128, 512)
    system = ring(512)
    comp = compile_system(system, semantics="delays")
    be = PallasBackend(interpret=False)
    be.lower(comp, SystemPlan(semantics="delays"))
    cfg = jax.ShapeDtypeStruct((16, 3 * 512), jnp.int32, sharding=one_chip)
    compiled = snp_step.lower(
        cfg, _abstract(comp, one_chip), max_branches=64,
        kernel=be.kernel_config, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dense_kernel_refuses_what_exceeds_vmem():
    system = ring(16384)
    be = PallasBackend(interpret=False)
    assert vmem_bytes(16384, 16384, block_b=be.block_b, block_t=be.block_t,
                      block_n=be.block_n) > VMEM_LIMIT_BYTES
    with pytest.raises(ValueError, match="VMEM"):
        be.compile(system)           # refused before the dense build
    # the emulated kernel has no VMEM to run out of
    assert PallasBackend(interpret=True).compile_refusal(16384, 16384) is None


def test_sparse_kernel_is_refused_and_really_does_not_lower(one_chip):
    system = paper_pi(covering=True)
    comp = compile_system_sparse(system)
    with pytest.raises(ValueError, match="gather"):
        SparsePallasBackend(interpret=False).lower(comp, SystemPlan())
    cfg = jax.ShapeDtypeStruct((128, 3), jnp.int32, sharding=one_chip)
    with pytest.raises(NotImplementedError, match="gather"):
        snp_step_sparse.lower(cfg, _abstract(comp, one_chip),
                              max_branches=16, interpret=False).compile()


def test_default_blocks_fit_the_smoke_widths():
    be = PallasBackend()
    for m in (3, 4, 2048):
        need = vmem_bytes(m, 2 * m, block_b=be.block_b, block_t=be.block_t,
                          block_n=be.block_n)
        assert need <= VMEM_LIMIT_BYTES, m
    assert np.isclose(VMEM_LIMIT_BYTES / 2**20, 96)
