"""Public jit'd wrappers around the fused dense SNP transition kernel.

Handles everything the raw kernel assumes away: the cheap O(B·n) branch
bookkeeping (applicability, ranks, radix strides — computed with the
reference semantics), padding every dimension to block multiples (padding
rules never fire: app=0, M rows=0), and unpadding/masking the results.

:func:`snp_step` is the single-device step on a
:class:`~repro.core.matrix.CompiledSNP`; :func:`snp_step_dense_shard`
steps one neuron shard of a :class:`~repro.core.plan.ShardedCompiled`
through the same kernel body's halo form (``C' = C + halo·H_adj +
S·M_local`` — DESIGN.md §3 "Kernel lowering"), with the bookkeeping and
the halo exchange owned by ``explore_distributed``'s sharded step.

``interpret=None`` (the default) compiles the kernel on a TPU and emulates
it anywhere else (:func:`repro.core.plan.resolve_interpret`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.matrix import CompiledSNP, is_delayed
from repro.core.plan import KernelConfig
from repro.core.semantics import (branch_info, delayed_branch_info,
                                  delayed_weight_matrix, split_state)

from .kernel import snp_step_pallas

__all__ = ["snp_step", "snp_step_dense_shard"]


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _resolve_blocks(kernel: Optional[KernelConfig], block_b, block_t,
                    block_n):
    """The effective dense block shape: explicit per-axis kwarg >
    ``kernel`` config field > :meth:`KernelConfig.dense_default`.  Both
    wrappers resolve through here so precedence can't diverge."""
    base = KernelConfig.dense_default() if kernel is None else \
        KernelConfig.dense_default().merged(
            block_b=kernel.block_b, block_t=kernel.block_t,
            block_n=kernel.block_n)
    cfg = base.merged(block_b=block_b, block_t=block_t, block_n=block_n)
    return cfg.block_b, cfg.block_t, cfg.block_n


def _pad(x, rows=None, cols=None, value=0):
    """Pad the leading (batch/rule) and/or trailing axis to a block
    multiple — shared by both wrappers so padding semantics can't
    diverge."""
    pads = [(0, 0)] * x.ndim
    if rows is not None:
        pads[0] = (0, rows - x.shape[0])
    if cols is not None:
        pads[-1] = (0, cols - x.shape[-1])
    return jnp.pad(x, pads, constant_values=value)


@functools.partial(
    jax.jit,
    static_argnames=("max_branches", "block_b", "block_t", "block_n",
                     "kernel", "interpret"),
)
def snp_step(
    configs: jnp.ndarray,   # (B, m) int32
    comp: CompiledSNP,
    *,
    max_branches: int,
    block_b: Optional[int] = None,
    block_t: Optional[int] = None,
    block_n: Optional[int] = None,
    kernel: Optional[KernelConfig] = None,
    interpret: Optional[bool] = None,
):
    """Fused successor expansion: returns (successors (B,T,m) int32,
    valid (B,T) bool, emissions (B,T) int32, overflow (B,) bool).

    The block shape comes from ``kernel`` (a hashable
    :class:`~repro.core.plan.KernelConfig`, usually carried by a
    ``SystemPlan``), overridable per axis with the explicit kwargs;
    unset axes fall back to :meth:`KernelConfig.dense_default`.

    Bit-identical to :func:`repro.kernels.snp_step.ref.snp_step_ref` for all
    spike counts < 2^24 (f32-exact integer range).  A delayed ``comp``
    (``semantics="delays"``; 3m-wide state rows) routes through the
    kernel's delay stage and returns ``(B, T, 3m)`` successors,
    bit-identical to :func:`repro.core.semantics.delayed_next_configs`.
    """
    B = configs.shape[0]
    n = comp.num_rules
    m = comp.num_neurons
    T = max_branches
    delayed = is_delayed(comp)

    block_b, block_t, block_n = _resolve_blocks(
        kernel, block_b, block_t, block_n)
    block_b = min(block_b, max(B, 1))
    block_t = min(block_t, T)
    block_n = min(block_n, _round_up(n, 128))

    if delayed:
        info = delayed_branch_info(configs, comp)
        spikes, cd, pd = split_state(configs)
    else:
        info = branch_info(configs, comp)
        spikes, cd, pd = configs, None, None
    stride = jnp.minimum(info.stride, 2.0 ** 30).astype(jnp.int32)
    # clamp choices>=1 so the kernel's % never sees 0 (already >=1 by defn)

    Bp, Tp, Np = (_round_up(B, block_b), _round_up(T, block_t),
                  _round_up(n, block_n))

    if delayed:
        weights = _pad(delayed_weight_matrix(comp), rows=Np)   # (Np, 4m)
        extra = dict(
            cd=_pad(cd, rows=Bp),
            pd=_pad(pd, rows=Bp),
            adj=comp.adjacency,
            # all-zero one-hot when the system has no output neuron
            # (out_neuron == m) — emissions then stay 0, matching the
            # reference's zero-padded gather.
            outoh=(jnp.arange(m) == comp.out_neuron).astype(jnp.int32),
        )
    else:
        weights = _pad(comp.M, rows=Np)
        extra = {}

    out, valid, emis = snp_step_pallas(
        _pad(spikes, rows=Bp),
        _pad(_pad(info.rank, cols=Np, value=-1), rows=Bp),
        _pad(_pad(info.app, cols=Np), rows=Bp),
        # padded configs: stride 1 / choices 1 / psi 0 -> no valid branches
        _pad(stride, rows=Bp, value=1),
        _pad(info.choices, rows=Bp, value=1),
        _pad(info.psi, rows=Bp),
        _pad(comp.neuron_onehot, rows=Np),          # (n, m) pad rules
        weights,
        _pad(comp.env_produce, rows=Np),
        max_branches=Tp,
        block_b=block_b, block_t=block_t, block_n=block_n,
        interpret=interpret,
        **extra,
    )
    out = out[:B, :T]
    valid = valid[:B, :T] & info.alive[:, None]
    emis = emis[:B, :T]
    overflow = info.psi > float(T)
    return out, valid, emis, overflow


def snp_step_dense_shard(
    configs: jnp.ndarray,   # (B, mloc) int32 — local frontier slices
    rank: jnp.ndarray,      # (B, nloc) int32 — local-rule ranks
    app: jnp.ndarray,       # (B, nloc) bool — local-rule applicability
    stride: jnp.ndarray,    # (B, mloc) f32 — cross-shard-combined strides
    choices: jnp.ndarray,   # (B, mloc) int32
    psi: jnp.ndarray,       # (B,) f32 — replicated global Ψ
    onehot: jnp.ndarray,    # (nloc, mloc) int8 — rule→local-neuron map
    M_local: jnp.ndarray,   # (nloc, mloc) int32 — local columns of M_Π
    hadj: jnp.ndarray,      # (H, mloc) int8 — halo 0/1 in-adjacency
    halo: jnp.ndarray,      # (B, T, H) int32 — received remote produce
    *,
    max_branches: int,
    block_b: Optional[int] = None,
    block_t: Optional[int] = None,
    block_n: Optional[int] = None,
    kernel: Optional[KernelConfig] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """One shard's candidate slices ``(B, T, mloc)`` through the fused
    dense kernel (``C' = C + halo·H_adj + S·M_local`` — kernel.py module
    docstring).  Bookkeeping and the halo exchange belong to the caller
    (``explore_distributed``'s sharded step); this wrapper pads to block
    multiples and clamps the saturating f32 strides into the kernel's
    int32 decode.  Traceable inside ``shard_map``."""
    B, m = configs.shape
    n = rank.shape[1]
    T = max_branches
    block_b, block_t, block_n = _resolve_blocks(
        kernel, block_b, block_t, block_n)
    block_b = min(block_b, max(B, 1))
    block_t = min(block_t, T)
    block_n = min(block_n, _round_up(n, 128))
    Bp, Tp, Np = (_round_up(B, block_b), _round_up(T, block_t),
                  _round_up(n, block_n))

    halo_p = jnp.pad(halo, [(0, Bp - B), (0, Tp - T), (0, 0)])
    out, _, _ = snp_step_pallas(
        _pad(configs, rows=Bp),
        _pad(_pad(rank, cols=Np, value=-1), rows=Bp),
        _pad(_pad(app, cols=Np), rows=Bp),
        _pad(jnp.minimum(stride, 2.0 ** 30).astype(jnp.int32),
             rows=Bp, value=1),
        _pad(choices, rows=Bp, value=1),
        _pad(psi, rows=Bp),
        _pad(onehot, rows=Np),
        _pad(M_local, rows=Np),
        jnp.zeros((Np,), jnp.int32),    # shard emissions: driver's job
        halo=halo_p,
        hadj=hadj,
        max_branches=Tp,
        block_b=block_b, block_t=block_t, block_n=block_n,
        interpret=interpret,
    )
    return out[:B, :T]
