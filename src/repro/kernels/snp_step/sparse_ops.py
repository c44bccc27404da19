"""Public jit'd wrappers around the fused sparse SNP transition kernel.

Mirrors :mod:`.ops` for the dense kernel: computes the cheap ``O(B·m·R)``
per-config bookkeeping with the reference sparse semantics (applicability,
ranks, radix strides, and the packed fired-rule table the kernel gathers
from), pads the batch/branch dimensions to block multiples (padding rows
decode digit 0 into all-zero tables: no valid branches, no contribution),
and unpads/masks the results.

Two entry points over the one encoding-parameterized kernel body
(DESIGN.md §3 "Kernel lowering"):

* :func:`snp_step_sparse` — single-device step on a
  :class:`~repro.core.matrix.CompiledSparseSNP`; pure-ELL **and** hybrid
  ELL+COO encodings (the COO segment-sum stage runs in-kernel from the
  compiler's ``coo_bounds``/``hub_slot`` metadata).
* :func:`snp_step_sparse_shard` — one neuron shard of a
  :class:`~repro.core.plan.ShardedCompiled`: the caller
  (``explore_distributed``'s sharded step) passes the already-combined
  cross-shard strides/Ψ and the received halo produce; ``in_idx`` indexes
  the extended ``[local | halo | zero]`` space.  Traceable inside
  ``shard_map`` — the halo ``all_to_all`` stays outside the kernel.

``interpret=None`` (the default) compiles the kernels on a TPU and emulates
them anywhere else (:func:`repro.core.plan.resolve_interpret`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.matrix import CompiledSparseSNP, is_delayed
from repro.core.plan import KernelConfig
from repro.core.semantics import (delayed_packed_actions, packed_rule_table,
                                  sparse_branch_info,
                                  sparse_delayed_branch_info, split_state)

from .sparse_kernel import snp_step_sparse_pallas

__all__ = ["snp_step_sparse", "snp_step_sparse_shard"]


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _resolve_blocks(kernel: Optional[KernelConfig], block_b, block_t):
    """The effective sparse block shape: explicit per-axis kwarg >
    ``kernel`` config field > :meth:`KernelConfig.sparse_default`.  A
    config asking for neuron-axis tiling (``block_n``) is a clear error —
    this kernel keeps the whole neuron axis resident per block."""
    if kernel is not None and kernel.block_n is not None:
        raise ValueError(
            f"sparse kernel config sets block_n={kernel.block_n}, but the "
            "sparse lowering keeps the whole neuron axis resident per "
            "block (grid (B/bb, T/bt)); drop block_n — only the dense "
            "kernel tiles that axis")
    base = KernelConfig.sparse_default() if kernel is None else \
        KernelConfig.sparse_default().merged(
            block_b=kernel.block_b, block_t=kernel.block_t)
    cfg = base.merged(block_b=block_b, block_t=block_t)
    return cfg.block_b, cfg.block_t


def _pad_bt(x, rows, branches=None, value=0):
    """Zero/value-pad the batch axis (axis 0) and optionally the branch
    axis (axis 1) to block multiples — shared by both wrappers so padding
    semantics can't diverge.  (Distinct name and axes from the dense
    wrapper's ``ops._pad``, which pads leading/trailing axes.)"""
    pads = [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    if branches is not None:
        pads[1] = (0, branches - x.shape[1])
    return jnp.pad(x, pads, constant_values=value)


@functools.partial(
    jax.jit,
    static_argnames=("max_branches", "block_b", "block_t", "kernel",
                     "interpret"),
)
def snp_step_sparse(
    configs: jnp.ndarray,   # (B, m) int32
    comp: CompiledSparseSNP,
    *,
    max_branches: int,
    block_b: Optional[int] = None,
    block_t: Optional[int] = None,
    kernel: Optional[KernelConfig] = None,
    interpret: Optional[bool] = None,
):
    """Fused sparse successor expansion: returns (successors (B,T,m) int32,
    valid (B,T) bool, emissions (B,T) int32, overflow (B,) bool).

    The block shape comes from ``kernel`` (a hashable
    :class:`~repro.core.plan.KernelConfig`, usually carried by a
    ``SystemPlan``), overridable per axis with the explicit kwargs;
    unset axes fall back to :meth:`KernelConfig.sparse_default`.

    Bit-identical to :func:`repro.core.semantics.sparse_next_configs` (and
    hence to the dense oracle on valid entries for spike counts < 2^24),
    for pure-ELL and hybrid ELL+COO encodings alike.  A delayed ``comp``
    (``semantics="delays"``; 3m-wide state rows) routes through the
    kernel's delay stage and returns ``(B, T, 3m)`` successors,
    bit-identical to
    :func:`repro.core.semantics.sparse_delayed_next_configs`.
    """
    B = configs.shape[0]
    m = comp.num_neurons
    T = max_branches
    delayed = is_delayed(comp)
    block_b, block_t = _resolve_blocks(kernel, block_b, block_t)

    if comp.coo_src.shape[0] and (comp.coo_bounds is None
                                  or comp.hub_slot is None):
        # Static-shape check, so this raises at trace time with a real
        # message instead of a shape crash deep in the kernel.  Only
        # hand-built encodings can get here: compile_system_sparse always
        # emits the segment metadata the in-kernel COO stage consumes.
        raise ValueError(
            "snp_step_sparse: hybrid ELL+COO encoding without COO lowering "
            "metadata (coo_bounds/hub_slot); lower the system through "
            "compile_system_sparse / backend.compile instead of building "
            "the CompiledSparseSNP by hand")

    block_b = min(block_b, max(B, 1))
    block_t = min(block_t, T)

    if delayed:
        spikes, cd, pd = split_state(configs)
        info = sparse_delayed_branch_info(configs, comp)
        packed_e, packed_d = delayed_packed_actions(comp)
        tab = packed_rule_table(info, comp, packed_e)         # (B, m, R)
        dtab = packed_rule_table(info, comp, packed_d)
    else:
        spikes, cd, pd, dtab = configs, None, None, None
        info = sparse_branch_info(configs, comp)
        tab = packed_rule_table(info, comp)                   # (B, m, R)

    Bp, Tp = _round_up(B, block_b), _round_up(T, block_t)

    out, valid, emis = snp_step_sparse_pallas(
        _pad_bt(spikes, Bp),
        # padded configs: stride 1 / choices 1 / psi 0 -> no valid branches
        _pad_bt(info.stride, Bp, value=1),
        _pad_bt(info.choices.astype(jnp.int32), Bp, value=1),
        _pad_bt(info.psi, Bp),
        _pad_bt(tab, Bp),
        comp.in_idx,
        comp.out_neuron,
        coo_src=comp.coo_src if comp.coo_src.shape[0] else None,
        coo_bounds=comp.coo_bounds if comp.coo_src.shape[0] else None,
        hub_slot=comp.hub_slot if comp.coo_src.shape[0] else None,
        dtab=_pad_bt(dtab, Bp) if delayed else None,
        cd=_pad_bt(cd, Bp) if delayed else None,
        pd=_pad_bt(pd, Bp) if delayed else None,
        max_branches=Tp,
        block_b=block_b, block_t=block_t,
        interpret=interpret,
    )
    out = out[:B, :T]
    valid = valid[:B, :T] & info.alive[:, None]
    emis = emis[:B, :T]
    overflow = info.psi > float(T)
    return out, valid, emis, overflow


def snp_step_sparse_shard(
    configs: jnp.ndarray,   # (B, mloc) int32 — local frontier slices
    stride: jnp.ndarray,    # (B, mloc) f32 — cross-shard-combined strides
    choices: jnp.ndarray,   # (B, mloc) int32 — local choice counts
    psi: jnp.ndarray,       # (B,) f32 — replicated global Ψ
    tab: jnp.ndarray,       # (B, mloc, R) int32 — local packed rule table
    in_idx: jnp.ndarray,    # (mloc, Kin) int32 — extended-space indices
    halo: jnp.ndarray,      # (B, T, H) int32 — received remote produce
    *,
    max_branches: int,
    block_b: Optional[int] = None,
    block_t: Optional[int] = None,
    kernel: Optional[KernelConfig] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """One shard's candidate slices ``(B, T, mloc)`` through the fused
    kernel.  Bookkeeping (branch info, radix combine, the halo exchange)
    belongs to the caller — this wrapper only pads to block multiples and
    routes the extended encoding into the kernel body.  Traceable (called
    inside ``explore_distributed``'s ``shard_map``)."""
    B, mloc = configs.shape
    T = max_branches
    H = halo.shape[-1]
    block_b, block_t = _resolve_blocks(kernel, block_b, block_t)
    block_b = min(block_b, max(B, 1))
    block_t = min(block_t, T)
    Bp, Tp = _round_up(B, block_b), _round_up(T, block_t)

    # The emission gather index is the extended zero slot: shard emissions
    # are judged by the driver, not here.
    out, _, _ = snp_step_sparse_pallas(
        _pad_bt(configs, Bp),
        _pad_bt(stride, Bp, value=1),
        _pad_bt(choices, Bp, value=1),
        _pad_bt(psi, Bp),
        _pad_bt(tab, Bp),
        in_idx,
        jnp.asarray(mloc + H, jnp.int32),
        halo=_pad_bt(halo, Bp, branches=Tp),
        max_branches=Tp,
        block_b=block_b, block_t=block_t,
        interpret=interpret,
    )
    return out[:B, :T]
