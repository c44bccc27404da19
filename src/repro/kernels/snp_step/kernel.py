"""Fused SNP transition kernel (Pallas, TPU).

One kernel computes, for a frontier tile of configurations and a tile of
branch indices, the successor configurations

    C'[b, t, :] = C[b, :] + S[b, t, :] · M        (paper eq. 2)

where the spiking vector ``S[b, t]`` is *decoded on the fly* from the branch
index ``t`` (mixed-radix rank decode, DESIGN.md §2) — ``S`` never
materializes in HBM.  The decode itself is phrased as an MXU matmul:

    digits[b, t, μ]   = (t // stride[b, μ]) % choices[b, μ]      (VPU, int)
    digits_r[b, t, i] = digits · onehotᵀ   (neuron-of-rule gather == matmul)
    S[b, t, i]        = app[b, i] ⊙ (digits_r[b, t, i] == rank[b, i])
    C'                = C + S · M                                (MXU)

Grid: ``(B/bb, T/bt, n/bn)`` with the rule dimension innermost and
accumulated into the revisited output block, so systems whose ``M`` exceeds
VMEM still stream through.  Block defaults keep the working set
(digit scratch + onehot/M tiles + S tile) within ~8 MB of VMEM and all
matmul dims at multiples of the 128-lane MXU.

**Shard consumption** (DESIGN.md §3 "Kernel lowering"): the same body also
serves one neuron shard of a :class:`~repro.core.plan.ShardedCompiled`.
The shard's dense lowering (``PallasBackend.lower``) restricts each local
rule's row to local columns (``M_local``), and the produce of *remote*
in-neighbors arrives as a halo input (exchanged outside the kernel) that
is folded in as one extra MXU matmul against the static 0/1 halo
in-adjacency: ``C' = C + halo·H_adj + S·M_local``.  Dummy padding rules
are never applicable (``app = 0``), so their rows contribute nothing.

**Delayed semantics** (DESIGN.md "Delayed semantics"): the same grid also
runs the ``semantics="delays"`` tier.  ``M`` is swapped for the stacked
``(n, 4m)`` weight matrix ``W`` so the accumulated contraction ``S·W``
yields each fired rule's ``[consume | produce·(d=0) | delay |
produce·(d>0)]`` into a VMEM accumulator; after the last rule tile one
combine stage applies the closed-neuron algebra (reopen-pending fanout
over the 0/1 adjacency, reception gate, countdown/pending update) and
writes ``(bb, bt, 3m)`` state rows ``[spikes | countdown | pending]``.

**Exactness on a TPU.** Every contraction runs at
``precision=HIGHEST``: the default TPU precision rounds f32 operands to
bf16, which is exact only for integers below 2^8, while ``M_Π`` entries
reach 2^16.  With full-f32 passes the kernel is bit-identical to the
integer reference for spike counts below 2^24.

**VMEM.** The neuron axis is never tiled: the digit scratch and the output
block are ``(bb, bt, m)``, the incidence and ``M`` blocks ``(m, bn)``.
:func:`vmem_bytes` estimates one grid step's working set, and the backend
refuses at lower time a shape whose estimate exceeds
:data:`VMEM_LIMIT_BYTES` (``PallasBackend.compile_refusal``) instead of
letting the TPU compiler fail at run time.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.plan import resolve_interpret

__all__ = ["snp_step_pallas", "vmem_bytes", "VMEM_LIMIT_BYTES"]

# Scoped VMEM the kernel asks the TPU compiler for: most of a v5e core's
# 128 MiB, leaving headroom for the compiler's own spills.
VMEM_LIMIT_BYTES = 96 * 2**20

_HIGHEST = jax.lax.Precision.HIGHEST


def vmem_bytes(m: int, n: int, *, block_b: int, block_t: int, block_n: int,
               halo: int = 0, delayed: bool = False) -> int:
    """Estimated VMEM of one grid step at this block shape, in bytes.

    Counts every block twice (the pipeline double-buffers inputs and
    outputs), the scratch once, and the live ``(bb, bt, ·)`` temporaries
    of the body; sub-128 lane widths are padded to 128 as the TPU lays
    them out.  ``n`` only clamps the rule tile, as the ops wrapper does.
    The estimate is deliberately high: at the default blocks a described
    v5e compiles m = 11264 (estimate 102 MiB) and runs out of VMEM at
    m = 32768 with 273 MiB used (estimate 296 MiB)."""
    lane = lambda x: -(-x // 128) * 128  # noqa: E731
    bb, bt = block_b, block_t
    bn = min(block_n, lane(n))
    w = 4 * m if delayed else m              # width of M (W under delays)
    out_w = 3 * m if delayed else m
    blocks = (3 * bb * lane(m) + 2 * bb * bn + bb * 128   # c/stride/choices,
              + m * bn + bn * lane(w)                     # rank/app, psi,
              + bb * bt * lane(out_w) + 2 * bb * bt * 128)  # onehot, M, outs
    if not delayed:
        blocks += bn * 128                                 # env
    if halo:
        blocks += bb * bt * lane(halo) + halo * lane(m)
    if delayed:
        blocks += 2 * bb * lane(m) + m * lane(m) + m * 128  # cd, pd, adj, outoh
    scratch = bb * bt * lane(m) + (bb * bt * lane(w) if delayed else 0)
    temps = 3 * bb * bt * lane(w) + 2 * bb * bt * bn
    if delayed:
        temps += 8 * bb * bt * lane(m)       # the combine stage's rows
    # 1/8 margin over the count: the compiler's own spills and padding
    return 4 * (2 * blocks + scratch + temps) * 9 // 8


def _make_kernel(has_halo: bool, has_delay: bool = False):
    """Body specialized to whether a shard halo input is present and
    whether the step runs the delayed-semantics tier (keeps the ref list
    static for ``pallas_call``).  The two are mutually exclusive: no
    backend shards ``semantics="delays"`` (plan.py refuses)."""
    assert not (has_halo and has_delay)

    def kernel(*refs):
        it = iter(refs)
        c_ref = next(it)        # (bb, m)  f32 — configurations (spikes)
        rank_ref = next(it)     # (bb, bn) f32 — rank among applicable
        app_ref = next(it)      # (bb, bn) f32 — applicability mask
        stride_ref = next(it)   # (bb, m)  i32 — radix strides (clamped)
        choices_ref = next(it)  # (bb, m)  i32 — per-neuron choice counts
        psi_ref = next(it)      # (bb, 1)  f32 — number of valid branches
        onehot_ref = next(it)   # (m, bn)  f32 — neuron→rule incidence
        mat_ref = next(it)      # (bn, m)  f32 — M_Π block; (bn, 4m) W
        #                         block under delays (delayed_weight_matrix)
        if not has_delay:
            env_ref = next(it)  # (bn, 1)  f32 — emission weights
        if has_halo:
            halo_ref = next(it)  # (bb, bt, H) f32 — remote fired produce
            hadj_ref = next(it)  # (H, m)      f32 — halo 0/1 in-adjacency
        if has_delay:
            cd_ref = next(it)    # (bb, m) f32 — countdowns
            pd_ref = next(it)    # (bb, m) f32 — pending spikes
            adj_ref = next(it)   # (m, m)  f32 — 0/1 synapse adjacency
            outoh_ref = next(it)  # (m, 1) f32 — output-neuron one-hot
        out_ref = next(it)      # (bb, bt, m|3m) f32 — accumulated over k
        valid_ref = next(it)    # (bb, bt, 1) i32
        emis_ref = next(it)     # (bb, bt, 1) f32 (accumulated over k)
        digit_ref = next(it)    # (bb, bt, m) f32 scratch, persists across k
        if has_delay:
            acc_ref = next(it)  # (bb, bt, 4m) f32 scratch — S·W accumulator

        j = pl.program_id(1)   # branch-tile index
        k = pl.program_id(2)   # rule-tile index (innermost, accumulated)
        bb, bt, _ = out_ref.shape
        m = c_ref.shape[-1]

        @pl.when(k == 0)
        def _init():
            # Branch ids for this tile.
            t = (j * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt, 1), 1))
            stride = stride_ref[...].reshape(bb, 1, m)
            choices = choices_ref[...].reshape(bb, 1, m)
            digits = (t // stride) % choices                 # (bb, bt, m) i32
            digit_ref[...] = digits.astype(jnp.float32)
            if has_delay:
                acc_ref[...] = jnp.zeros((bb, bt, 4 * m), jnp.float32)
            else:
                # Output starts at C (broadcast over branches) plus, for a
                # shard, the halo contribution; S·M accumulates in over k.
                base = jnp.broadcast_to(
                    c_ref[...].reshape(bb, 1, m), (bb, bt, m))
                if has_halo:
                    base = base + jax.lax.dot_general(
                        halo_ref[...], hadj_ref[...],
                        (((2,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=_HIGHEST,
                    )
                out_ref[...] = base
            emis_ref[...] = jnp.zeros((bb, bt, 1), jnp.float32)
            valid_ref[...] = (t.astype(jnp.float32)
                              < psi_ref[...].reshape(bb, 1, 1)
                              ).astype(jnp.int32)

        digits = digit_ref[...]                               # (bb, bt, m)
        # "gather digit of each rule's neuron" as an MXU matmul with the
        # 0/1 incidence: digits_r[b,t,i] = Σ_μ digits[b,t,μ]·onehot[μ,i].
        digits_r = jax.lax.dot_general(
            digits, onehot_ref[...],
            (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_HIGHEST,
        )                                                     # (bb, bt, bn)
        s = app_ref[...].reshape(bb, 1, -1) * (
            digits_r == rank_ref[...].reshape(bb, 1, -1)
        ).astype(jnp.float32)                                 # (bb, bt, bn)
        if not has_delay:
            out_ref[...] += jax.lax.dot_general(
                s, mat_ref[...],
                (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_HIGHEST,
            )
            emis_ref[...] += jax.lax.dot_general(
                s, env_ref[...],
                (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_HIGHEST,
            )
            return

        # Delayed tier: accumulate the stacked contraction S·W — per
        # (branch, neuron) the fired rule's [consume | produce·(d=0) | d |
        # produce·(d>0)] — then combine once after the last rule tile
        # (matches core.semantics.delayed_next_configs bit-for-bit).
        acc_ref[...] += jax.lax.dot_general(
            s, mat_ref[...],
            (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_HIGHEST,
        )

        @pl.when(k == pl.num_programs(2) - 1)
        def _combine():
            acc = acc_ref[...]
            cons_f = acc[..., :m]
            emit_fired = acc[..., m:2 * m]
            d_f = acc[..., 2 * m:3 * m]
            prod_pend = acc[..., 3 * m:]
            cd = cd_ref[...].reshape(bb, 1, m)
            pd = pd_ref[...].reshape(bb, 1, m)

            reopen = cd == 1.0
            emit = emit_fired + jnp.where(reopen, pd, 0.0)
            incoming = jax.lax.dot_general(
                emit, adj_ref[...],
                (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_HIGHEST,
            )
            fired_del = d_f > 0.0
            cd_next = jnp.where(fired_del, d_f, jnp.maximum(cd - 1.0, 0.0))
            gate = cd_next == 0.0
            spikes = c_ref[...].reshape(bb, 1, m) - cons_f \
                + jnp.where(gate, incoming, 0.0)
            pd_next = jnp.where(fired_del, prod_pend,
                                jnp.where(reopen, 0.0, pd))
            out_ref[...] = jnp.concatenate(
                [spikes, cd_next, pd_next], axis=-1)
            emis_ref[...] = jax.lax.dot_general(
                emit, outoh_ref[...],
                (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_HIGHEST,
            )

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("max_branches", "block_b", "block_t", "block_n",
                     "interpret"),
)
def snp_step_pallas(
    configs: jnp.ndarray,    # (B, m) int32, B % block_b == 0
    rank: jnp.ndarray,       # (B, n) int32
    app: jnp.ndarray,        # (B, n) bool
    stride: jnp.ndarray,     # (B, m) int32 (pre-clamped < 2^30)
    choices: jnp.ndarray,    # (B, m) int32
    psi: jnp.ndarray,        # (B,) float32
    onehot: jnp.ndarray,     # (n, m) int8 — rule→neuron incidence
    M: jnp.ndarray,          # (n, m) int32
    env: jnp.ndarray,        # (n,) int32 — ignored under delays
    halo: jnp.ndarray = None,   # (B, T, H) int32 — shard halo produce
    hadj: jnp.ndarray = None,   # (H, m) int8 — halo 0/1 in-adjacency
    cd: jnp.ndarray = None,     # (B, m) int32 — countdowns (delays tier)
    pd: jnp.ndarray = None,     # (B, m) int32 — pending spikes
    adj: jnp.ndarray = None,    # (m, m) int32 — 0/1 synapse adjacency
    outoh: jnp.ndarray = None,  # (m,) int32 — output-neuron one-hot
    *,
    max_branches: int,
    block_b: int,
    block_t: int,
    block_n: int,
    interpret: Optional[bool] = None,
):
    """Raw tiled kernel call.  Use :mod:`..ops` for the padded public API
    — the block shape is *required* here: the grid/tile choice belongs to
    the caller (ultimately a :class:`~repro.core.plan.KernelConfig` on
    the plan, DESIGN.md §3 "Planner & autotuner"), not the kernel.
    ``halo``/``hadj`` select the shard body (module docstring);
    ``cd``/``pd``/``adj``/``outoh`` select the delayed-semantics body,
    with ``M`` carrying the stacked (n, 4m) weight matrix
    (:func:`repro.core.semantics.delayed_weight_matrix`) and the output
    widening to ``(B, T, 3m)`` state rows.  ``interpret=None`` emulates
    the kernel unless JAX's default backend is a TPU
    (:func:`repro.core.plan.resolve_interpret`).

    ``valid``/``emissions`` leave the kernel as ``(B, T, 1)`` blocks, so
    a ``block_t`` below ``T`` only has to be a multiple of 8, not of the
    128-lane width a trailing ``(bb, bt)`` block would need."""
    interpret = resolve_interpret(interpret)
    B, m = configs.shape
    n = rank.shape[1]
    T = max_branches
    assert B % block_b == 0 and T % block_t == 0 and n % block_n == 0, (
        "ops.py must pad shapes to block multiples"
    )
    has_halo = halo is not None
    has_delay = cd is not None
    assert not (has_halo and has_delay), \
        "sharded delayed lowering is unsupported (plan.py refuses it)"
    out_m = 3 * m if has_delay else m
    grid = (B // block_b, T // block_t, n // block_n)

    in_specs = [
        pl.BlockSpec((block_b, m), lambda i, j, k: (i, 0)),
        pl.BlockSpec((block_b, block_n), lambda i, j, k: (i, k)),
        pl.BlockSpec((block_b, block_n), lambda i, j, k: (i, k)),
        pl.BlockSpec((block_b, m), lambda i, j, k: (i, 0)),
        pl.BlockSpec((block_b, m), lambda i, j, k: (i, 0)),
        pl.BlockSpec((block_b, 1), lambda i, j, k: (i, 0)),
        pl.BlockSpec((m, block_n), lambda i, j, k: (0, k)),
        pl.BlockSpec((block_n, M.shape[-1]), lambda i, j, k: (k, 0)),
    ]
    operands = [
        configs.astype(jnp.float32),
        rank.astype(jnp.float32),
        app.astype(jnp.float32),
        stride.astype(jnp.int32),
        choices.astype(jnp.int32),
        psi.reshape(B, 1).astype(jnp.float32),
        onehot.T.astype(jnp.float32),   # (m, n)
        M.astype(jnp.float32),          # (n, m); (n, 4m) W under delays
    ]
    if not has_delay:
        in_specs += [pl.BlockSpec((block_n, 1), lambda i, j, k: (k, 0))]
        operands += [env.reshape(n, 1).astype(jnp.float32)]
    if has_halo:
        H = halo.shape[-1]
        in_specs += [
            pl.BlockSpec((block_b, block_t, H), lambda i, j, k: (i, j, 0)),
            pl.BlockSpec((H, m), lambda i, j, k: (0, 0)),
        ]
        operands += [halo.astype(jnp.float32), hadj.astype(jnp.float32)]
    if has_delay:
        in_specs += [
            pl.BlockSpec((block_b, m), lambda i, j, k: (i, 0)),
            pl.BlockSpec((block_b, m), lambda i, j, k: (i, 0)),
            pl.BlockSpec((m, m), lambda i, j, k: (0, 0)),
            pl.BlockSpec((m, 1), lambda i, j, k: (0, 0)),
        ]
        operands += [
            cd.astype(jnp.float32),
            pd.astype(jnp.float32),
            adj.astype(jnp.float32),
            outoh.reshape(m, 1).astype(jnp.float32),
        ]

    scratch_shapes = [pltpu.VMEM((block_b, block_t, m), jnp.float32)]
    if has_delay:
        scratch_shapes += [pltpu.VMEM((block_b, block_t, 4 * m),
                                      jnp.float32)]

    out, valid, emis = pl.pallas_call(
        _make_kernel(has_halo, has_delay),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_b, block_t, out_m),
                         lambda i, j, k: (i, j, 0)),
            pl.BlockSpec((block_b, block_t, 1), lambda i, j, k: (i, j, 0)),
            pl.BlockSpec((block_b, block_t, 1), lambda i, j, k: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, out_m), jnp.float32),
            jax.ShapeDtypeStruct((B, T, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, T, 1), jnp.float32),
        ],
        scratch_shapes=scratch_shapes,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(*operands)
    return (out.astype(jnp.int32), valid[..., 0].astype(bool),
            emis[..., 0].astype(jnp.int32))
