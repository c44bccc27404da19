"""Fused *sparse* SNP transition kernel (Pallas, TPU).

The dense kernel (:mod:`.kernel`) streams the ``(n, m)`` matrix through the
MXU; this kernel never sees an ``O(n·m)`` operand.  For a tile of
configurations and branch indices it computes, entirely in VMEM,

    digits[b, t, μ]  = (t // stride[b, μ]) % choices[b, μ]       (VPU, f32 —
                       exact for T < 2^23, see semantics._decode_digits)
    packed[b, t, μ]  = tab[b, μ, digits[b, t, μ]]                (unrolled
                       select over the R rule slots — no dynamic gather)
    ΔC[b, t, j]      = Σ_{k < K_in} produce[b, t, in_idx[j, k]]
                       - consume[b, t, j]                        (gather/sum)
    C'[b, t, :]      = C[b, :] + ΔC[b, t, :]

where ``tab`` is the per-config packed rule table (``produce | consume <<
16`` of the d-th applicable rule per neuron, 0 where none — built by the
ops wrapper via :func:`repro.core.semantics.packed_rule_table`,
``O(B·m·R)``) and ``in_idx`` is the ELL-packed synapse in-adjacency
(DESIGN.md §3).  The environment emission is the fired produce at the
output neuron.  Work per (b, t) is ``O(m·(1 + K_in))`` — proportional to
``nnz(M_Π)``, not ``n·m``.

**One body, every ``SystemPlan`` encoding** (DESIGN.md §3 "Kernel
lowering").  The ELL body above is parameterized by two pieces of encoding
metadata, both optional and both scatter-free:

* **COO segment-sum stage** (hybrid ELL+COO plans): the compiler sorts the
  tail by ``(dst, src)`` and records per-hub run offsets
  (``coo_bounds``) plus a neuron→hub map (``hub_slot``), so the tail
  contribution is a gather + inclusive ``cumsum`` + two static-shape
  gathers of run endpoints — never a scatter:

      contrib = produce_fired[coo_src]                 (gather, (bb,bt,Ec))
      cum0    = [0, cumsum(contrib)]                   (VPU)
      tail[h] = cum0[bounds[h+1]] - cum0[bounds[h]]    (gather, (bb,bt,Hn))
      ΔC[j]  += tail_pad[hub_slot[j]]                  (gather, (bb,bt,m))

* **halo extension** (neuron-axis-sharded plans): ``in_idx`` indexes the
  *extended* produce space ``[local (m) | halo (H) | zero]``; the halo
  produce values arrive as an extra kernel input (exchanged by
  ``explore_distributed``'s ``all_to_all`` *outside* the kernel — Pallas
  bodies hold no collectives), and the output-neuron index must already
  point at the extended zero slot.

Grid: ``(B/bb, T/bt)`` with the whole neuron axis resident per block; the
VMEM working set is ``O(bb·bt·(m + H + Ec))``, so the ops wrapper shrinks
``bb`` for very wide systems.  All arithmetic is int32 (exact).
Correctness is validated in interpret mode against
:func:`repro.core.semantics.sparse_next_configs`.  The kernel does not
compile for a TPU yet: Mosaic lowers only same-shape 2-D gathers, and the
body gathers over the ``(bb, bt, m)`` produce rows and the ``(bb, m, R)``
rule table.  ``SparsePallasBackend`` therefore refuses every compiled
(non-interpret) lowering at lower time, and the planner never picks it on
a chip.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.plan import resolve_interpret

__all__ = ["snp_step_sparse_pallas"]


def _make_kernel(has_coo: bool, has_halo: bool, has_delay: bool = False):
    """ELL body specialized to the encoding metadata actually present
    (specialization keeps the ref list static for ``pallas_call``).
    ``has_delay`` selects the delayed-semantics tier; it is mutually
    exclusive with ``has_halo`` (plan.py refuses sharded delays)."""
    assert not (has_halo and has_delay)

    def kernel(*refs):
        it = iter(refs)
        c_ref = next(it)        # (bb, m)     i32 — configurations (spikes)
        stride_ref = next(it)   # (bb, m)     f32 — radix strides (may +inf)
        choices_ref = next(it)  # (bb, m)     i32 — per-neuron choices (>=1)
        psi_ref = next(it)      # (bb, 1)     f32 — number of valid branches
        tab_ref = next(it)      # (bb, m, R)  i32 — produce | consume << 16
        #                         (emit-now payload packed_e under delays)
        inidx_ref = next(it)    # (m, Kin)    i32 — extended-space indices
        outn_ref = next(it)     # (1,)        i32 — emission gather index
        if has_coo:
            coosrc_ref = next(it)   # (Ec,)    i32 — tail sources
            coob_ref = next(it)     # (Hn+1,)  i32 — per-hub run offsets
            hub_ref = next(it)      # (m,)     i32 — neuron -> hub slot
        if has_halo:
            halo_ref = next(it)     # (bb, bt, H) i32 — remote produce
        if has_delay:
            dtab_ref = next(it)     # (bb, m, R) i32 — produce | d << 16
            cd_ref = next(it)       # (bb, m)    i32 — countdowns
            pd_ref = next(it)       # (bb, m)    i32 — pending spikes
        out_ref = next(it)      # (bb, bt, m|3m) i32 — successor configs
        valid_ref = next(it)    # (bb, bt, 1) i32
        emis_ref = next(it)     # (bb, bt, 1) i32

        j = pl.program_id(1)   # branch-tile index
        bb, bt, _ = out_ref.shape
        m = c_ref.shape[-1]
        R = tab_ref.shape[2]
        Kin = inidx_ref.shape[1]

        # Branch ids for this tile; decode one mixed-radix digit per neuron
        # (f32 division, exact for T < 2^23 — semantics._decode_digits).
        t = j * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt, 1), 1)
        tf = t.astype(jnp.float32)
        stride = stride_ref[...].reshape(bb, 1, m)
        choices = choices_ref[...].reshape(bb, 1, m).astype(jnp.float32)
        q = jnp.floor(tf / stride)
        digits = (q - choices * jnp.floor(q / choices)).astype(jnp.int32)

        # Fired-rule actions: unrolled select over the R rule slots.
        tab = tab_ref[...]
        packed_f = jnp.zeros((bb, bt, m), jnp.int32)
        for d in range(R):  # static R, unrolled
            packed_f = jnp.where(
                digits == d, tab[:, :, d].reshape(bb, 1, m), packed_f)
        prod_f = packed_f & 0xFFFF   # emit-now produce under delays
        cons_f = packed_f >> 16

        if has_delay:
            # Second rank table: the fired *delayed* action (nonzero iff
            # the fired rule has d > 0, since d >= 1 sets bit 16+).
            dtab = dtab_ref[...]
            packed_d = jnp.zeros((bb, bt, m), jnp.int32)
            for d in range(R):  # static R, unrolled
                packed_d = jnp.where(
                    digits == d, dtab[:, :, d].reshape(bb, 1, m), packed_d)
            cd = cd_ref[...].reshape(bb, 1, m)
            pd = pd_ref[...].reshape(bb, 1, m)
            reopen = cd == 1
            # The vector riding the in-adjacency is the emit-now vector:
            # fired d=0 produce plus reopening neurons' pending spikes.
            prod_f = prod_f + jnp.where(reopen, pd, 0)

        # Extended produce space the in-adjacency indexes into: pure ELL is
        # [local | zero]; a shard adds the received halo produce between
        # them ([local | halo | zero]).  Padding entries always hit the
        # trailing zero, contributing nothing.
        parts = [prod_f]
        if has_halo:
            parts.append(halo_ref[...])
        parts.append(jnp.zeros((bb, bt, 1), jnp.int32))
        prod_ext = jnp.concatenate(parts, axis=-1)
        in_idx = inidx_ref[...]
        incoming = jnp.zeros((bb, bt, m), jnp.int32)
        for k in range(Kin):  # static K_in, unrolled
            incoming = incoming + jnp.take(prod_ext, in_idx[:, k], axis=-1)

        if has_coo:
            # COO segment-sum stage (module docstring): tail sources are
            # always local neurons, so gather from prod_ext's local prefix.
            contrib = jnp.take(prod_ext, coosrc_ref[...], axis=-1)
            cum0 = jnp.concatenate(
                [jnp.zeros((bb, bt, 1), jnp.int32),
                 jnp.cumsum(contrib, axis=-1)], axis=-1)
            bounds = coob_ref[...]
            tail = (jnp.take(cum0, bounds[1:], axis=-1)
                    - jnp.take(cum0, bounds[:-1], axis=-1))
            tail_pad = jnp.concatenate(
                [tail, jnp.zeros((bb, bt, 1), jnp.int32)], axis=-1)
            incoming = incoming + jnp.take(tail_pad, hub_ref[...], axis=-1)

        if not has_delay:
            out_ref[...] = c_ref[...].reshape(bb, 1, m) - cons_f + incoming
        else:
            # Closed-neuron algebra (core.semantics.sparse_delayed_
            # next_configs, bit-for-bit): reception gated on the post-
            # update countdown, pending landing consumed on reopen.
            fired_del = packed_d != 0
            prod_pend = packed_d & 0xFFFF
            d_f = packed_d >> 16
            cd_next = jnp.where(fired_del, d_f, jnp.maximum(cd - 1, 0))
            gate = cd_next == 0
            spikes = c_ref[...].reshape(bb, 1, m) - cons_f \
                + jnp.where(gate, incoming, 0)
            pd_next = jnp.where(fired_del, prod_pend,
                                jnp.where(reopen, 0, pd))
            out_ref[...] = jnp.concatenate(
                [spikes, cd_next, pd_next], axis=-1)
        valid_ref[...] = (tf < psi_ref[...].reshape(bb, 1, 1)
                          ).astype(jnp.int32)
        emis_ref[...] = jnp.take(prod_ext, outn_ref[0], axis=-1
                                 ).reshape(bb, bt, 1)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("max_branches", "block_b", "block_t", "interpret"),
)
def snp_step_sparse_pallas(
    configs: jnp.ndarray,    # (B, m) int32, B % block_b == 0
    stride: jnp.ndarray,     # (B, m) float32 (saturating, may be +inf)
    choices: jnp.ndarray,    # (B, m) int32
    psi: jnp.ndarray,        # (B,) float32
    tab: jnp.ndarray,        # (B, m, R) int32 packed rule table
    in_idx: jnp.ndarray,     # (m, Kin) int32 — extended-space indices
    out_neuron: jnp.ndarray,  # () int32 — emission index (zero slot if none)
    coo_src: jnp.ndarray = None,     # (Ec,) int32 — hybrid tail sources
    coo_bounds: jnp.ndarray = None,  # (Hn+1,) int32 — per-hub run offsets
    hub_slot: jnp.ndarray = None,    # (m,) int32 — neuron -> hub slot
    halo: jnp.ndarray = None,        # (B, T, H) int32 — sharded halo produce
    dtab: jnp.ndarray = None,        # (B, m, R) int32 — delayed-action table
    cd: jnp.ndarray = None,          # (B, m) int32 — countdowns (delays)
    pd: jnp.ndarray = None,          # (B, m) int32 — pending spikes
    *,
    max_branches: int,
    block_b: int,
    block_t: int,
    interpret: Optional[bool] = None,
):
    """Raw tiled kernel call.  Use :mod:`..sparse_ops` for the padded
    public API — the block shape is *required* here: the grid/tile choice
    belongs to the caller (ultimately a
    :class:`~repro.core.plan.KernelConfig` on the plan, DESIGN.md §3
    "Planner & autotuner"), not the kernel.  ``coo_*``/``hub_slot``
    select the COO segment-sum stage (hybrid plans), ``halo`` the
    extended-index shard stage — both default to the pure-ELL body.
    ``dtab``/``cd``/``pd`` select the delayed-semantics body (``tab``
    then carries the emit-now payload ``packed_e``) and the output widens
    to ``(B, T, 3m)`` state rows.  ``valid``/``emissions`` leave the
    kernel as ``(B, T, 1)`` blocks (the dense kernel's layout), so
    ``block_t`` need only be a multiple of 8."""
    interpret = resolve_interpret(interpret)
    B, m = configs.shape
    R = tab.shape[2]
    Kin = in_idx.shape[1]
    T = max_branches
    assert B % block_b == 0 and T % block_t == 0, (
        "sparse_ops.py must pad shapes to block multiples"
    )
    has_coo = coo_src is not None and coo_src.shape[0] > 0
    has_halo = halo is not None
    has_delay = dtab is not None
    assert not (has_halo and has_delay), \
        "sharded delayed lowering is unsupported (plan.py refuses it)"
    out_m = 3 * m if has_delay else m
    grid = (B // block_b, T // block_t)

    in_specs = [
        pl.BlockSpec((block_b, m), lambda i, j: (i, 0)),
        pl.BlockSpec((block_b, m), lambda i, j: (i, 0)),
        pl.BlockSpec((block_b, m), lambda i, j: (i, 0)),
        pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((block_b, m, R), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((m, Kin), lambda i, j: (0, 0)),
        pl.BlockSpec((1,), lambda i, j: (0,)),
    ]
    operands = [
        configs.astype(jnp.int32),
        stride.astype(jnp.float32),
        choices.astype(jnp.int32),
        psi.reshape(B, 1).astype(jnp.float32),
        tab.astype(jnp.int32),
        in_idx.astype(jnp.int32),
        out_neuron.reshape(1).astype(jnp.int32),
    ]
    if has_coo:
        Ec, Hn = coo_src.shape[0], coo_bounds.shape[0] - 1
        in_specs += [
            pl.BlockSpec((Ec,), lambda i, j: (0,)),
            pl.BlockSpec((Hn + 1,), lambda i, j: (0,)),
            pl.BlockSpec((m,), lambda i, j: (0,)),
        ]
        operands += [coo_src.astype(jnp.int32),
                     coo_bounds.astype(jnp.int32),
                     hub_slot.astype(jnp.int32)]
    if has_halo:
        H = halo.shape[-1]
        in_specs.append(
            pl.BlockSpec((block_b, block_t, H), lambda i, j: (i, j, 0)))
        operands.append(halo.astype(jnp.int32))
    if has_delay:
        in_specs += [
            pl.BlockSpec((block_b, m, R), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((block_b, m), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, m), lambda i, j: (i, 0)),
        ]
        operands += [dtab.astype(jnp.int32), cd.astype(jnp.int32),
                     pd.astype(jnp.int32)]

    out, valid, emis = pl.pallas_call(
        _make_kernel(has_coo, has_halo, has_delay),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_b, block_t, out_m),
                         lambda i, j: (i, j, 0)),
            pl.BlockSpec((block_b, block_t, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((block_b, block_t, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, out_m), jnp.int32),
            jax.ShapeDtypeStruct((B, T, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, T, 1), jnp.int32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(*operands)
    return out, valid[..., 0].astype(bool), emis[..., 0]
