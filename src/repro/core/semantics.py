"""Pure-jnp reference semantics for batched SNP simulation.

This is the mathematical core of the paper, vectorized over a *frontier*
of ``B`` configurations at once:

* applicability mask over rules            (paper Alg. 2, step II-1)
* mixed-radix rank-decode of every valid
  spiking vector — replaces the paper's
  host-side string enumeration             (paper Alg. 2, steps II-2/II-3)
* the affine transition ``C' = C + S·M``   (paper eq. 2)

Everything here is shape-static and jit/vmap/shard_map friendly.  The fused
Pallas TPU kernel (``repro.kernels.snp_step``) implements the same math with
explicit VMEM tiling; this module doubles as its oracle (``ref.py``).
The sparse twins (:func:`sparse_branch_info`, :func:`sparse_next_configs`)
run the same math on the ELL/segment encoding
(:class:`~repro.core.matrix.CompiledSparseSNP`) in ``O(B·T·nnz)`` with
bit-identical valid entries — see DESIGN.md §3.  The chosen-successor
twins (:func:`sparse_chosen_config`, :func:`sparse_delayed_chosen_config`)
count the branches first, let the caller pick one per row, and build only
that successor: the trace scan keeps one of the ``T`` anyway.  On systems
with more than :data:`TABLE_MAX_RULE_SLOTS` rules on a neuron they find
each neuron's fired rule in rule space, so neither program nor memory
grows with the rules a neuron holds.

Enumeration order.  Neuron 0 is the most-significant mixed-radix digit:
branch index ``t ∈ [0, Ψ)`` decodes to ``digit_i = (t // stride_i) % k_i``
with ``stride_i = Π_{j>i} k_j``, where ``k_i = max(1, #applicable rules in
neuron i)``.  Within a neuron, digit ``d`` selects the ``d``-th applicable
rule in the total order.  This enumerates exactly the Ψ valid spiking
vectors of Alg. 2 — by construction, no generate-and-filter.

Overflow discipline.  Ψ can be astronomically large; all radix products are
computed in float32, which saturates monotonically (exact for products below
2^24, +inf beyond) — see DESIGN.md §2.  Whenever ``Ψ > max_branches`` the
config is flagged in ``branch_overflow`` and only the first ``max_branches``
branches (a valid, deterministic subset) are produced.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .matrix import CompiledAny, CompiledSNP, CompiledSparseSNP

__all__ = [
    "applicability",
    "branch_info",
    "sparse_branch_info",
    "packed_rule_table",
    "spiking_vectors",
    "next_configs",
    "sparse_next_configs",
    "sparse_chosen_config",
    "in_adjacency_sum",
    "TABLE_MAX_RULE_SLOTS",
    "fired_lookup",
    "StepOut",
    "ChosenOut",
    "split_state",
    "delayed_branch_info",
    "sparse_delayed_branch_info",
    "delayed_weight_matrix",
    "delayed_packed_actions",
    "delayed_next_configs",
    "sparse_delayed_next_configs",
    "sparse_delayed_chosen_config",
]

# Every f32 contraction on the SNP path runs at full f32 precision, so the
# dense steps stay bit-identical to the integer sparse path on a TPU.
_HIGHEST = jax.lax.Precision.HIGHEST


def applicability(config: jnp.ndarray, comp: CompiledAny) -> jnp.ndarray:
    """Boolean mask (..., n): which rules may fire at ``config`` (..., m).

    A rule with regex ``{b + t·p}`` is applicable at ``s`` spikes iff

    * exact mode:    ``s >= b`` and (``p == 0`` ? ``s == b``
                     : ``(s - b) % p == 0``)
    * covering mode: ``s >= b``  (the paper's (b-3) ``>=`` threshold;
                     with ``p > 0`` membership is against ``{b+t·p}``'s
                     downward closure, i.e. still just ``s >= b``)

    and always ``s >= consume``.
    """
    s = jnp.take(config, comp.rule_neuron, axis=-1)  # (..., n) spikes at owner
    ge_base = s >= comp.regex_base
    diff = s - comp.regex_base
    on_progression = jnp.where(
        comp.regex_period > 0,
        (diff % jnp.maximum(comp.regex_period, 1)) == 0,
        s == comp.regex_base,
    )
    member = jnp.where(comp.covering, ge_base, ge_base & on_progression)
    return member & (s >= comp.consume)


class BranchInfo(NamedTuple):
    app: jnp.ndarray        # (..., n) bool
    rank: jnp.ndarray       # (..., n) int32 — index among applicable in neuron
    choices: jnp.ndarray    # (..., m) int32 — max(1, #applicable)
    stride: jnp.ndarray     # (..., m) float32 — Π_{j>i} choices_j (exact < 2^24)
    psi: jnp.ndarray        # (...,)  float32 — Ψ (saturating)
    alive: jnp.ndarray      # (...,)  bool — any rule applicable at all


def branch_info(config: jnp.ndarray, comp: CompiledSNP) -> BranchInfo:
    return _branch_info_from_app(applicability(config, comp), comp)


def _branch_info_from_app(app: jnp.ndarray, comp: CompiledSNP) -> BranchInfo:
    app_i = app.astype(jnp.int32)
    onehot = comp.neuron_onehot.astype(jnp.int32)  # (n, m)

    # #applicable per neuron, and per-rule rank among the applicable rules of
    # its own neuron.  Rules are neuron-sorted, so an inclusive cumsum minus
    # the neuron's exclusive prefix gives the within-neuron rank.
    k = app_i @ onehot                       # (..., m)
    incl = jnp.cumsum(app_i, axis=-1)        # (..., n)
    # exclusive prefix at each rule's neuron start: total applicable in all
    # earlier neurons = sum over neurons j < neuron(i) of k_j.
    k_prefix = jnp.cumsum(k, axis=-1) - k    # (..., m) exclusive over neurons
    start = jnp.take_along_axis(
        k_prefix,
        jnp.broadcast_to(comp.rule_neuron, app.shape).astype(jnp.int32),
        axis=-1,
    )
    rank = incl - start - 1                  # valid where app

    choices = jnp.maximum(k, 1)
    cf = choices.astype(jnp.float32)
    # stride_i = Π_{j > i} choices_j ; suffix products via reversed cumprod.
    suffix = jnp.cumprod(cf[..., ::-1], axis=-1)[..., ::-1]  # Π_{j >= i}
    psi = suffix[..., 0]
    stride = jnp.concatenate(
        [suffix[..., 1:], jnp.ones_like(cf[..., :1])], axis=-1
    )
    alive = jnp.any(app, axis=-1)
    return BranchInfo(app=app, rank=rank, choices=choices, stride=stride,
                      psi=psi, alive=alive)


def spiking_vectors(
    config: jnp.ndarray, comp: CompiledSNP, max_branches: int
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """All valid spiking vectors at ``config``.

    Returns ``(S, valid, overflow)`` with ``S``: (..., T, n) int32 in
    **neuron-sorted rule order** (use ``comp.rule_order`` to map back to the
    paper's total order), ``valid``: (..., T) bool, ``overflow``: (...,) bool.
    Dead configs (no applicable rule) produce no valid branches.
    """
    return _decode_spiking(branch_info(config, comp), comp, max_branches)


def _decode_spiking(
    info: BranchInfo, comp: CompiledSNP, max_branches: int
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    T = max_branches
    t = jnp.arange(T, dtype=jnp.int32)

    # Mixed-radix decode directly in *rule space*: gather each rule's
    # neuron-stride/choice first ((..., n) tensors), then decode per branch.
    # This skips the (..., T, m) digit tensor and the (..., T, n) gather —
    # ~25% less HBM traffic on wide systems (EXPERIMENTS.md §Perf cell C).
    # Strides are exact in float32 whenever Ψ <= T (see module docstring);
    # clamp before casting so saturated strides stay valid int32 (yielding
    # digit 0: a legal choice).
    stride_i = jnp.minimum(info.stride, 2.0 ** 30).astype(jnp.int32)
    rule_idx = comp.rule_neuron.astype(jnp.int32)
    stride_r = jnp.take(stride_i, rule_idx, axis=-1)      # (..., n)
    choices_r = jnp.take(info.choices, rule_idx, axis=-1)  # (..., n)
    digits_r = (
        t[:, None] // stride_r[..., None, :]
    ) % choices_r[..., None, :]                            # (..., T, n)
    S = (
        info.app[..., None, :]
        & (digits_r == info.rank[..., None, :])
    ).astype(jnp.int32)

    valid = (t.astype(jnp.float32) < info.psi[..., None]) & info.alive[..., None]
    overflow = info.psi > float(T)
    return S, valid, overflow


class StepOut(NamedTuple):
    configs: jnp.ndarray    # (..., T, m) int32 — successor configurations
    valid: jnp.ndarray      # (..., T) bool
    emissions: jnp.ndarray  # (..., T) int32 — spikes sent to the environment
    overflow: jnp.ndarray   # (...,) bool — Ψ exceeded max_branches
    spiking: jnp.ndarray    # (..., T, n) int32 — the spiking vectors used


def next_configs(
    config: jnp.ndarray, comp: CompiledSNP, max_branches: int
) -> StepOut:
    """One synchronous SNP step: every successor of every config.

    ``C' = C + S · M_Π`` (paper eq. 2), batched over leading dims and over
    all ``T = max_branches`` candidate branches.
    """
    S, valid, overflow = spiking_vectors(config, comp, max_branches)
    # An f32 matmul at HIGHEST precision is exact for |values| < 2^24 and
    # maps onto the MXU; the TPU's default precision would round M_Π's
    # entries (up to 2^16) to bf16.  Spike counts beyond 2^24 are out of
    # scope (they would overflow int32 fast).
    delta = jnp.einsum(
        "...tn,nm->...tm", S.astype(jnp.float32), comp.M.astype(jnp.float32),
        precision=_HIGHEST).astype(jnp.int32)
    out = config[..., None, :] + delta
    emissions = jnp.einsum(
        "...tn,n->...t", S.astype(jnp.float32),
        comp.env_produce.astype(jnp.float32), precision=_HIGHEST,
    ).astype(jnp.int32)
    return StepOut(configs=out, valid=valid, emissions=emissions,
                   overflow=overflow, spiking=S)


# ---------------------------------------------------------------------------
# Sparse path: the same math on the ELL/segment encoding, O(B·T·m·degree)
# instead of O(B·T·n·m) — see DESIGN.md §3.
# ---------------------------------------------------------------------------


def sparse_branch_info(config: jnp.ndarray,
                       comp: CompiledSparseSNP) -> BranchInfo:
    """:func:`branch_info` on the sparse encoding — bit-identical outputs.

    Per-neuron applicable counts come from a prefix-sum difference over the
    neuron-sorted rule axis (a segment sum over ``seg_start``/``seg_count``)
    instead of the dense ``app @ neuron_onehot`` matmul; ranks reuse the
    same inclusive-cumsum trick.  The float32 stride/Ψ products are the
    *same operations in the same order* as the dense path, so overflow
    saturation matches exactly (DESIGN.md §2).
    """
    return _sparse_info_from_app(applicability(config, comp), comp)


def _sparse_info_from_app(app: jnp.ndarray,
                          comp: CompiledSparseSNP) -> BranchInfo:
    app_i = app.astype(jnp.int32)
    incl = jnp.cumsum(app_i, axis=-1)                        # (..., n)
    cum0 = jnp.concatenate(
        [jnp.zeros_like(incl[..., :1]), incl], axis=-1)      # (..., n+1)
    start = jnp.take(cum0, comp.seg_start, axis=-1)          # (..., m)
    k = jnp.take(cum0, comp.seg_start + comp.seg_count, axis=-1) - start
    rank = incl - jnp.take(start, comp.rule_neuron, axis=-1) - 1

    choices = jnp.maximum(k, 1)
    cf = choices.astype(jnp.float32)
    suffix = jnp.cumprod(cf[..., ::-1], axis=-1)[..., ::-1]
    psi = suffix[..., 0]
    stride = jnp.concatenate(
        [suffix[..., 1:], jnp.ones_like(cf[..., :1])], axis=-1)
    alive = jnp.any(app, axis=-1)
    return BranchInfo(app=app, rank=rank, choices=choices, stride=stride,
                      psi=psi, alive=alive)


def packed_rule_table(info: BranchInfo, comp: CompiledSparseSNP,
                      packed: jnp.ndarray = None) -> jnp.ndarray:
    """``tab`` (..., m, R) int32: ``produce | consume << 16`` of the d-th
    applicable rule of neuron μ at slot ``[..., μ, d]``, 0 where there is
    none.  ``O(B·m·R²)`` per *config* (not per branch), built scatter-free:
    static-index gathers pull each segment's ≤ R rules side by side, a tiny
    cumsum ranks the applicable ones, and an unrolled R² select places each
    at its rank slot (XLA scatters cost ~50x a gathered element on CPU; R
    is small by construction).  The packing (bounds checked by
    ``compile_system_sparse``) makes the hot per-branch fired-rule lookup a
    single gather instead of one per attribute.

    ``packed`` overrides the per-rule (n,) int32 payload (the delayed tier
    routes its own action packings through the same rank machinery)."""
    n = comp.num_rules
    m = comp.num_neurons
    R = comp.rule_slots.shape[0]
    batch = info.app.shape[:-1]
    app = info.app.reshape(-1, n)
    B = app.shape[0]
    slots = comp.rule_slots                                  # (R,) arange
    seg_idx = jnp.minimum(
        comp.seg_start[:, None] + slots[None, :], n - 1)     # (m, R)
    in_seg = slots[None, :] < comp.seg_count[:, None]        # (m, R)
    if packed is None:
        packed = comp.produce | (comp.consume << 16)         # (n,)
    packed_s = jnp.where(in_seg, jnp.take(packed, seg_idx, axis=0), 0)
    app_s = jnp.take(
        app, seg_idx.reshape(-1), axis=-1).reshape(B, m, R) & in_seg
    # rank of slot j within its segment = #applicable among slots <= j, - 1
    dd = jnp.cumsum(app_s.astype(jnp.int32), axis=-1) - 1    # (B, m, R)
    cols = [
        jnp.where(app_s & (dd == d), packed_s[None], 0).sum(axis=-1)
        for d in range(R)
    ]
    return jnp.stack(cols, axis=-1).reshape(*batch, m, R)


# Most rules on one neuron (``R``) for which a one-successor step looks
# the fired rules up in the (B, m, R) table; above it the step selects
# them in rule space (:func:`_fired_by_rules`).  Both are exact; the table
# costs O(B·m·R²) and an R-fold unrolled program, the rule-space select
# one more rule-axis prefix sum.  DESIGN.md §3 has the cells either side.
TABLE_MAX_RULE_SLOTS = 8


def fired_lookup(comp: CompiledAny, branches: int) -> str:
    """How a step that builds ``branches`` successors per row finds each
    neuron's fired rule: ``"rules"`` (rule space: the dense one-hot
    spiking vectors, or :func:`_fired_by_rules`) or ``"table"``
    (:func:`packed_rule_table`)."""
    if not isinstance(comp, CompiledSparseSNP):
        return "rules"
    R = comp.rule_slots.shape[0]
    return "rules" if branches == 1 and R > TABLE_MAX_RULE_SLOTS \
        else "table"


def _fired_by_rules(info: BranchInfo, comp: CompiledSparseSNP,
                    digits: jnp.ndarray, packed: jnp.ndarray
                    ) -> jnp.ndarray:
    """The per-rule (n,) int32 ``packed`` payload of the rule each neuron
    fires at ``digits`` (B, T', m), as (B, T', m), found in rule space.

    Rule ``r`` fires iff it is applicable and its rank is its neuron's
    digit; a neuron fires at most one rule, so its payload is the sum over
    its segment of the neuron-sorted rules: a difference of one inclusive
    prefix sum at the segment ends.  The int32 sums may wrap, but each
    difference is one payload below 2^31, so it is exact.  ``O(B·T'·n)``,
    the order of :func:`applicability`, whatever ``R`` is."""
    digit_r = jnp.take(digits, comp.rule_neuron, axis=-1)    # (B, T', n)
    fired = info.app[:, None, :] & (info.rank[:, None, :] == digit_r)
    cum = jnp.cumsum(jnp.where(fired, packed, 0), axis=-1)
    cum0 = jnp.concatenate(
        [jnp.zeros_like(cum[..., :1]), cum], axis=-1)        # (B, T', n+1)
    return (jnp.take(cum0, comp.seg_start + comp.seg_count, axis=-1)
            - jnp.take(cum0, comp.seg_start, axis=-1))


def _fired_payload(info: BranchInfo, comp: CompiledSparseSNP,
                   digits: jnp.ndarray, packed: jnp.ndarray = None
                   ) -> jnp.ndarray:
    """(B, T', m) payload of each neuron's fired rule at ``digits``: in
    rule space where :func:`fired_lookup` says so, else through the
    packed rule table."""
    if packed is None:
        packed = comp.produce | (comp.consume << 16)         # (n,)
    if fired_lookup(comp, digits.shape[-2]) == "rules":
        return _fired_by_rules(info, comp, digits, packed)
    return _fired_packed(digits, packed_rule_table(info, comp, packed))


def _decode_digits(t: jnp.ndarray, info: BranchInfo) -> jnp.ndarray:
    """Mixed-radix digit per (branch, neuron): ``(t // stride) % choices``
    as (B, T', m) int32, computed in float32.  ``t`` is ``(T',)`` branch
    indices shared by every row of the (B, ...) ``info``, or ``(B, T')``
    one set per row.

    Integer division does not vectorize on CPU (and costs ~20x a float op);
    f32 division is *exact* here: with ``j = floor(t/stride)``, a wrong
    floor needs the true quotient within ulp(j)/2 ≤ 2^-23·j of an integer
    from below, but it sits at least ``1/stride ≥ j/T`` away — impossible
    for ``T < 2^23``.  Saturated (+inf) strides quotient to 0, matching the
    dense path's clamped-int division.  Same argument for the modulus.
    """
    tf = t.astype(jnp.float32)[..., None]
    s = info.stride[..., None, :]
    c = info.choices.astype(jnp.float32)[..., None, :]
    q = jnp.floor(tf / s)
    return (q - c * jnp.floor(q / c)).astype(jnp.int32)


def _fired_packed(digits: jnp.ndarray, tab: jnp.ndarray) -> jnp.ndarray:
    """Fired-rule lookup ``tab[..., μ, digits[..., t, μ]]`` as (..., T, m).

    ``R`` is small by construction, so an unrolled select beats a dynamic
    per-element gather (~8x on CPU); the gather fallback covers rule-heavy
    systems.  Digits are always < choices ≤ R, and slot 0 of an empty
    neuron is 0 (no rule fires).
    """
    R = tab.shape[-1]
    if R <= 8:
        packed_f = jnp.zeros(digits.shape, jnp.int32)
        for d in range(R):
            packed_f = jnp.where(
                digits == d, tab[..., None, :, d], packed_f)
        return packed_f
    batch = digits.shape[:-2]
    T, m = digits.shape[-2:]
    flat_b = int(np.prod(batch)) if batch else 1
    offs = (jnp.arange(m, dtype=jnp.int32) * R).reshape(1, 1, m)
    flat = (digits.reshape(flat_b, T, m) + offs).reshape(flat_b, T * m)
    out = jnp.take_along_axis(tab.reshape(flat_b, m * R), flat, axis=-1)
    return out.reshape(*batch, T, m)


def in_adjacency_sum(vals: jnp.ndarray, in_idx: jnp.ndarray,
                     coo_src: jnp.ndarray | None = None,
                     coo_dst: jnp.ndarray | None = None) -> jnp.ndarray:
    """``Σ_k vals[..., in_idx[j, k]]`` (plus the COO tail's segment-sum
    into ``coo_dst``) as ``(..., m)``: the ELL in-adjacency contraction.
    ``vals`` ``(..., W)`` already ends in the zero slot that padding
    entries point at.

    The sum runs as a ``fori_loop`` over ``K_in`` of row gathers on the
    transposed ``(W, rows)`` layout, so one ``(m, rows)`` accumulator is
    live whatever ``K_in`` is and the program does not grow with it.
    Unrolled gathers along the lane axis let XLA keep every one of the
    ``K_in`` results live: 65 GiB for a v5e's 16 at m=32768, B=256, T=32.
    int32 throughout, so the sum is exact in any order."""
    lead, W = vals.shape[:-1], vals.shape[-1]
    m = in_idx.shape[0]
    rows = vals.reshape(-1, W).T                              # (W, R)
    cols = in_idx.T                                           # (K_in, m)
    acc = jax.lax.fori_loop(
        0, cols.shape[0],
        lambda k, a: a + jnp.take(rows, cols[k], axis=0),
        jnp.zeros((m, rows.shape[1]), vals.dtype))
    if coo_src is not None and coo_src.shape[0]:
        # Tail synapses of hub neurons (in-degree past the plan's hub
        # threshold, DESIGN.md §3): gather the value at each tail source,
        # segment-sum into the target neurons.
        acc = acc + jax.ops.segment_sum(
            jnp.take(rows, coo_src, axis=0), coo_dst, num_segments=m)
    return acc.T.reshape(*lead, m)


class ChosenOut(NamedTuple):
    """One chosen successor per row (:func:`sparse_chosen_config`)."""

    configs: jnp.ndarray    # (B, w) int32 — the successor at the chosen branch
    emissions: jnp.ndarray  # (B,) int32
    n_valid: jnp.ndarray    # (B,) int32 — number of valid branches (<= T)
    overflow: jnp.ndarray   # (B,) bool — Ψ exceeded max_branches


def _branch_valid(info: BranchInfo, T: int) -> jnp.ndarray:
    """(B, T) bool: branch ``t`` exists (``t < Ψ`` in float32, so a
    saturated Ψ keeps every branch) at a live config."""
    t = jnp.arange(T, dtype=jnp.int32)
    return (t[None, :].astype(jnp.float32) < info.psi[:, None]) \
        & info.alive[:, None]


def _sparse_successors(cfg: jnp.ndarray, info: BranchInfo,
                       comp: CompiledSparseSNP, t: jnp.ndarray
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Successors of the (B, m) rows ``cfg`` at branch indices ``t`` —
    ``(T',)`` shared or ``(B, T')`` per row — as ``(configs (B, T', m),
    emissions (B, T'))``.  Steps 1–4 of :func:`sparse_next_configs`."""
    digits = _decode_digits(t, info)                         # (B, T', m)
    packed_f = _fired_payload(info, comp, digits)            # (B, T', m)
    prod_f = packed_f & 0xFFFF
    cons_f = packed_f >> 16

    prod_pad = jnp.concatenate(
        [prod_f, jnp.zeros(prod_f.shape[:-1] + (1,), jnp.int32)],
        axis=-1)                                             # (B, T', m+1)
    delta = in_adjacency_sum(prod_pad, comp.in_idx, comp.coo_src,
                             comp.coo_dst) - cons_f
    return (cfg[:, None, :] + delta,
            jnp.take(prod_pad, comp.out_neuron, axis=-1))


def _expand(config: jnp.ndarray, comp: CompiledSparseSNP, T: int,
            info_fn, successors) -> StepOut:
    """All ``T`` candidate successors of every row (the explore path)."""
    w = config.shape[-1]
    batch = config.shape[:-1]
    cfg = config.reshape(-1, w)
    info = info_fn(cfg, comp)
    out, emissions = successors(cfg, info, comp,
                                jnp.arange(T, dtype=jnp.int32))
    return StepOut(
        configs=out.reshape(*batch, T, w),
        valid=_branch_valid(info, T).reshape(*batch, T),
        emissions=emissions.reshape(*batch, T),
        overflow=(info.psi > float(T)).reshape(batch),
        spiking=None,
    )


def _choose_then_step(config: jnp.ndarray, comp: CompiledSparseSNP, T: int,
                      choose, info_fn, successors) -> ChosenOut:
    """Count each row's valid branches, let ``choose`` pick one index per
    row, and build only that successor (the trace path)."""
    info = info_fn(config, comp)
    n_valid = jnp.sum(_branch_valid(info, T), axis=-1, dtype=jnp.int32)
    idx = choose(n_valid)                                    # (B,)
    out, emissions = successors(config, info, comp, idx[:, None])
    return ChosenOut(configs=out[:, 0], emissions=emissions[:, 0],
                     n_valid=n_valid, overflow=info.psi > float(T))


def sparse_next_configs(
    config: jnp.ndarray, comp: CompiledSparseSNP, max_branches: int
) -> StepOut:
    """One synchronous SNP step on the sparse encoding.

    Produces identical *valid* entries to :func:`next_configs` without ever
    materializing the ``(..., T, n)`` one-hot spiking tensor or any
    ``O(n·m)`` matrix:

    1. decode the mixed-radix digit per (branch, neuron)     — (..., T, m);
    2. one gather into the packed per-config rule table      -> the fired
       rule's (produce, consume) per neuron;
    3. contract over the ELL in-adjacency: a fired rule's row of ``M_Π`` is
       ``-consume`` at its owner plus ``produce`` on the owner's
       out-neighbors, so ``ΔC[j] = Σ_{i ∈ in(j)} produce_fired[i] -
       consume_fired[j]`` — a ``K_in``-wide gather/segment-sum
       (:func:`in_adjacency_sum`);
    4. the environment emission is the fired produce at the output neuron.

    All arithmetic is int32 (exact); agreement with the dense f32 matmul
    holds for spike counts < 2^24 (DESIGN.md §2).
    """
    return _expand(config, comp, max_branches, sparse_branch_info,
                   _sparse_successors)


def sparse_chosen_config(config: jnp.ndarray, comp: CompiledSparseSNP,
                         max_branches: int, choose) -> ChosenOut:
    """One step of each (B, m) row down a single branch: the successor
    :func:`sparse_next_configs` would put at ``choose(n_valid)``, built
    alone.

    ``n_valid`` (B,) int32 is ``sum(valid)`` of the full expansion, and
    ``choose`` maps it to one branch index per row (traceable, < T).  The
    decode, fired-rule lookup and in-adjacency contraction then run on
    ``B`` rows instead of ``B·T``, with the same int32 arithmetic, so the
    result is bit-identical to picking that row of the expansion.  Past
    :data:`TABLE_MAX_RULE_SLOTS` rules on a neuron the fired rules are
    selected in rule space (:func:`fired_lookup`)."""
    return _choose_then_step(config, comp, max_branches, choose,
                             sparse_branch_info, _sparse_successors)


# ---------------------------------------------------------------------------
# Delayed semantics (SystemPlan(semantics="delays"), DESIGN.md §2 "Delayed
# semantics"): rules carry a firing delay d (arXiv 1212.2529 / 2211.15156).
# A configuration row widens to 3m — [spikes | countdown | pending]:
#
#   countdown[j] > 0  — neuron j is *closed*: its rules are inapplicable
#                       and incoming spikes are lost;
#   countdown[j] == 1 — j reopens THIS transition: pending[j] (the produce
#                       of the delayed rule it fired d steps ago) lands on
#                       its out-neighbors (and the environment, if j is the
#                       output neuron) at the end of the step;
#   firing a rule with d > 0 consumes immediately, sets countdown := d and
#   pending := produce; firing with d == 0 emits immediately (classic).
#
# Reception gate: neuron j receives incoming spikes iff its *post-update*
# countdown is 0 — equivalently iff it neither stays closed (cd > 1) nor
# just fired a delayed rule.  All-zero delays collapse every branch of this
# transition onto the paper's ``C' = C + S·M`` exactly.
# ---------------------------------------------------------------------------


def split_state(config: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray,
                                              jnp.ndarray]:
    """Split a delayed-state row (..., 3m) into (spikes, countdown,
    pending), each (..., m)."""
    m = config.shape[-1] // 3
    return config[..., :m], config[..., m:2 * m], config[..., 2 * m:]


def _delayed_alive(info: BranchInfo, cd: jnp.ndarray) -> BranchInfo:
    """Closed neurons keep the system live: a config with open countdowns
    must still take its (deterministic, Ψ=1) decrement step even when no
    rule is applicable, or pending spikes would never land."""
    return info._replace(alive=info.alive | jnp.any(cd > 0, axis=-1))


def delayed_branch_info(config: jnp.ndarray, comp: CompiledSNP) -> BranchInfo:
    """:func:`branch_info` under the delayed semantics: applicability is
    additionally masked by the owning neuron being open, and liveness
    extends to configs with running countdowns."""
    spikes, cd, _ = split_state(config)
    open_at_owner = jnp.take(cd, comp.rule_neuron, axis=-1) == 0
    app = applicability(spikes, comp) & open_at_owner
    return _delayed_alive(_branch_info_from_app(app, comp), cd)


def sparse_delayed_branch_info(config: jnp.ndarray,
                               comp: CompiledSparseSNP) -> BranchInfo:
    """:func:`sparse_branch_info` under the delayed semantics."""
    spikes, cd, _ = split_state(config)
    open_at_owner = jnp.take(cd, comp.rule_neuron, axis=-1) == 0
    app = applicability(spikes, comp) & open_at_owner
    return _delayed_alive(_sparse_info_from_app(app, comp), cd)


def delayed_weight_matrix(comp: CompiledSNP) -> jnp.ndarray:
    """Stacked per-rule weight matrix ``W`` (n, 4m) for the dense delayed
    step: one ``S·W`` contraction yields, per (branch, neuron), the fired
    rule's ``[consume | produce·(d=0) | d | produce·(d>0)]`` — replacing
    ``S·M`` so the dense Pallas kernel's delay stage stays a single
    accumulated matmul (kernels/snp_step/kernel.py)."""
    oh = comp.neuron_onehot.astype(jnp.float32)              # (n, m)
    d = comp.delay.astype(jnp.float32)[:, None]
    p = comp.produce.astype(jnp.float32)[:, None]
    c = comp.consume.astype(jnp.float32)[:, None]
    nodelay = (comp.delay == 0).astype(jnp.float32)[:, None]
    return jnp.concatenate(
        [oh * c, oh * (p * nodelay), oh * d, oh * (p * (1.0 - nodelay))],
        axis=-1)


def delayed_packed_actions(comp: CompiledSparseSNP
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-rule int32 payloads for the sparse delayed step's two rank
    tables (:func:`packed_rule_table`):

    * ``packed_e`` = ``produce·(d=0) | consume << 16`` — the *emit-now*
      table the core gather/segment-sum contraction consumes (a delayed
      rule's produce is withheld from the wire);
    * ``packed_d`` = ``produce | d << 16`` where ``d > 0``, else 0 — the
      delayed-action table (nonzero iff the fired rule has a delay, since
      ``d >= 1`` sets bit 16+); bounds guaranteed by ``Rule`` validation
      (``produce < 2^16`` checked at compile, ``d < 2^15``).
    """
    nodelay = comp.delay == 0
    packed_e = jnp.where(nodelay, comp.produce, 0) | (comp.consume << 16)
    packed_d = jnp.where(nodelay, 0, comp.produce | (comp.delay << 16))
    return packed_e, packed_d


def delayed_next_configs(
    config: jnp.ndarray, comp: CompiledSNP, max_branches: int
) -> StepOut:
    """One synchronous *delayed* SNP step, dense encoding: every successor
    (..., T, 3m) of every state row (..., 3m).

    The fired-rule attributes come from one stacked f32 contraction
    ``S·W`` (:func:`delayed_weight_matrix`, exact below 2^24 at HIGHEST
    precision); the
    reopen-pending fanout and the reception-gated incoming ride the 0/1
    synapse ``comp.adjacency``, which ``M``'s per-rule rows cannot carry.
    """
    spikes, cd, pd = split_state(config)
    m = spikes.shape[-1]
    info = delayed_branch_info(config, comp)
    S, valid, overflow = _decode_spiking(info, comp, max_branches)

    acc = jnp.einsum("...tn,nk->...tk", S.astype(jnp.float32),
                     delayed_weight_matrix(comp),
                     precision=_HIGHEST).astype(jnp.int32)
    cons_f = acc[..., :m]
    emit_fired = acc[..., m:2 * m]
    d_f = acc[..., 2 * m:3 * m]
    prod_pend = acc[..., 3 * m:]

    reopen = (cd == 1)[..., None, :]                    # (..., 1, m)
    emit = emit_fired + jnp.where(reopen, pd[..., None, :], 0)
    incoming = jnp.einsum(
        "...ti,ij->...tj", emit.astype(jnp.float32),
        comp.adjacency.astype(jnp.float32),
        precision=_HIGHEST).astype(jnp.int32)

    fired_del = d_f > 0
    cd_next = jnp.where(fired_del, d_f,
                        jnp.maximum(cd - 1, 0)[..., None, :])
    gate = cd_next == 0
    spikes_next = spikes[..., None, :] - cons_f \
        + jnp.where(gate, incoming, 0)
    pd_next = jnp.where(fired_del, prod_pend,
                        jnp.where(reopen, 0, pd[..., None, :]))

    emit_pad = jnp.concatenate(
        [emit, jnp.zeros(emit.shape[:-1] + (1,), jnp.int32)], axis=-1)
    emissions = jnp.take(emit_pad, comp.out_neuron, axis=-1)
    out = jnp.concatenate([spikes_next, cd_next, pd_next], axis=-1)
    return StepOut(configs=out, valid=valid, emissions=emissions,
                   overflow=overflow, spiking=S)


def _sparse_delayed_successors(cfg: jnp.ndarray, info: BranchInfo,
                               comp: CompiledSparseSNP, t: jnp.ndarray
                               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`_sparse_successors` under the delayed semantics: the (B, 3m)
    state rows at branch indices ``t``, as ``(configs (B, T', 3m),
    emissions (B, T'))``."""
    spikes, cd, pd = split_state(cfg)
    packed_e, packed_d = delayed_packed_actions(comp)
    digits = _decode_digits(t, info)                         # (B, T', m)
    pe = _fired_payload(info, comp, digits, packed_e)
    prod_now = pe & 0xFFFF
    cons_f = pe >> 16
    pdl = _fired_payload(info, comp, digits, packed_d)
    fired_del = pdl != 0
    prod_pend = pdl & 0xFFFF
    d_f = pdl >> 16

    reopen = (cd == 1)[:, None, :]
    emit = prod_now + jnp.where(reopen, pd[:, None, :], 0)   # (B, T', m)
    emit_pad = jnp.concatenate(
        [emit, jnp.zeros(emit.shape[:-1] + (1,), jnp.int32)], axis=-1)
    incoming = in_adjacency_sum(emit_pad, comp.in_idx, comp.coo_src,
                                comp.coo_dst)

    cd_next = jnp.where(fired_del, d_f,
                        jnp.maximum(cd - 1, 0)[:, None, :])
    gate = cd_next == 0
    spikes_next = spikes[:, None, :] - cons_f \
        + jnp.where(gate, incoming, 0)
    pd_next = jnp.where(fired_del, prod_pend,
                        jnp.where(reopen, 0, pd[:, None, :]))

    out = jnp.concatenate([spikes_next, cd_next, pd_next], axis=-1)
    return out, jnp.take(emit_pad, comp.out_neuron, axis=-1)


def sparse_delayed_next_configs(
    config: jnp.ndarray, comp: CompiledSparseSNP, max_branches: int
) -> StepOut:
    """One synchronous *delayed* SNP step on the sparse encoding —
    bit-identical valid entries to :func:`delayed_next_configs`.

    Identical shape to :func:`sparse_next_configs` with two twists: the
    vector riding the ELL/COO in-adjacency is the *emit-now* vector
    (fired d=0 produce + reopening neurons' pending) instead of the raw
    fired produce, and a second rank table decodes the fired delayed
    action (``produce | d << 16``) to drive countdown/pending updates and
    the receiver gate.
    """
    return _expand(config, comp, max_branches, sparse_delayed_branch_info,
                   _sparse_delayed_successors)


def sparse_delayed_chosen_config(config: jnp.ndarray,
                                 comp: CompiledSparseSNP,
                                 max_branches: int, choose) -> ChosenOut:
    """:func:`sparse_chosen_config` under the delayed semantics: the
    (B, 3m) successor :func:`sparse_delayed_next_configs` would put at
    ``choose(n_valid)``, built alone."""
    return _choose_then_step(config, comp, max_branches, choose,
                             sparse_delayed_branch_info,
                             _sparse_delayed_successors)
