"""Matrix encodings of an SNP system (paper §2.2), as JAX-ready arrays.

Two lowerings of an :class:`~repro.core.system.SNPSystem`, both with rules
**sorted by owning neuron** so per-neuron segment operations are contiguous:

* :func:`compile_system` — the paper's dense spiking transition matrix
  ``M_Π`` (:class:`CompiledSNP`); ``O(n·m)`` memory, exact match for the
  paper's eq. 2 formulation.
* :func:`compile_system_sparse` — an ELL/segment encoding
  (:class:`CompiledSparseSNP`) that never materializes ``M_Π``: the
  per-rule vectors, per-neuron rule segments, and the ELL-packed
  in-adjacency of the synapse graph.  A fired rule's row of ``M_Π`` is
  ``-consume`` at its owner plus ``produce`` on the owner's out-neighbours,
  so these rebuild ``M_Π`` exactly, in ``O(n + m·K_in)`` memory where the
  dense matrix is ``O(n·m)`` — the sparse step backends (``"sparse"``,
  ``"sparse_pallas"``) run on this encoding in ``O(B·T·m·degree)`` instead
  of ``O(B·T·n·m)``.
  With ``hub_threshold=H`` (requested through a
  :class:`~repro.core.plan.SystemPlan` with ``encoding="hybrid"``) the ELL
  in-adjacency is capped at ``H`` entries per neuron and the tail synapses
  of hub neurons spill into a COO segment (``coo_src``/``coo_dst``,
  combined by segment-sum) — exact, and no padding blow-up on heavy-tailed
  graphs (power-law without ``max_in``).  Layout details in DESIGN.md §3.

Both compilers build their arrays from vectorized numpy adjacency indexing
(no per-rule × per-neuron Python loops), so systems with ``m >= 10^4``
neurons compile in well under a second.
"""

from __future__ import annotations

from typing import List, NamedTuple, Union

import jax.numpy as jnp
import numpy as np

from .system import Rule, SNPSystem

__all__ = [
    "CompiledSNP",
    "CompiledSparseSNP",
    "CompiledAny",
    "compile_system",
    "compile_system_sparse",
    "is_compiled",
    "is_delayed",
]

_SEMANTICS = ("no_delays", "delays")


def _check_semantics(system: SNPSystem, semantics: str) -> bool:
    """Validate the semantics axis at compile time; returns ``True`` for
    the delayed tier.  Compiling a system that carries nonzero delays
    under the paper's ``no_delays`` semantics raises — the delays would
    silently be ignored otherwise."""
    if semantics not in _SEMANTICS:
        raise ValueError(
            f"semantics must be one of {_SEMANTICS}, got {semantics!r}")
    if semantics == "no_delays" and system.max_delay > 0:
        raise ValueError(
            f"system {system.name!r} has rules with delay > 0; compile it "
            "under SystemPlan(semantics=\"delays\") (the paper's matrix "
            "semantics is delay-free)")
    return semantics == "delays"


class CompiledSNP(NamedTuple):
    """Dense device-array encoding of an SNP system.

    Shapes: ``m`` neurons, ``n`` rules (sorted by neuron).

    The trailing delay fields are ``None`` under the default
    ``no_delays`` semantics (the historical encoding, bit-identical to
    pre-delay builds); ``SystemPlan(semantics="delays")`` populates them
    and widens ``init_config`` to the ``3m`` state layout
    ``[spikes | countdown | pending]`` (DESIGN.md §2 "Delayed semantics").
    """

    M: jnp.ndarray              # (n, m) int32 — spiking transition matrix
    rule_neuron: jnp.ndarray    # (n,)  int32 — owning neuron of each rule
    consume: jnp.ndarray        # (n,)  int32
    produce: jnp.ndarray        # (n,)  int32
    regex_base: jnp.ndarray     # (n,)  int32
    regex_period: jnp.ndarray   # (n,)  int32 (0 => single word)
    covering: jnp.ndarray       # (n,)  bool
    neuron_onehot: jnp.ndarray  # (n, m) int8 — rule->neuron incidence
    env_produce: jnp.ndarray    # (n,)  int32 — spikes emitted to environment
    init_config: jnp.ndarray    # (m,) int32 — C_0 (3m under delays)
    rule_order: jnp.ndarray     # (n,)  int32 — original rule index per
    #                             sorted position (an array, so a jitted
    #                             call carries one leaf for it, not n)
    # -- delayed-semantics extension (None == no_delays encoding) ---------
    delay: jnp.ndarray = None       # (n,) int32 — per-rule firing delay
    adjacency: jnp.ndarray = None   # (m, m) int32 — 0/1 synapse matrix
    #   (src, dst); carries reopening neurons' pending spikes to their
    #   out-neighbors, which M's per-rule rows cannot express.
    out_neuron: jnp.ndarray = None  # () int32 — output neuron, or m if
    #   none; under delays env emission is the *emit-now* amount at this
    #   neuron (time-shifted by d), not the per-rule env_produce.

    @property
    def num_rules(self) -> int:
        return self.M.shape[0]

    @property
    def num_neurons(self) -> int:
        return self.M.shape[1]

    @property
    def state_width(self) -> int:
        """Columns of one configuration row: ``m``, or ``3m`` under the
        delayed semantics (``[spikes | countdown | pending]``)."""
        return self.init_config.shape[0]


class CompiledSparseSNP(NamedTuple):
    """ELL/segment device-array encoding of an SNP system — no ``O(n·m)``
    arrays anywhere (DESIGN.md §3).

    Shapes: ``m`` neurons, ``n`` rules (sorted by neuron), ``R`` =
    ``max_rules_per_neuron``, ``Kin`` = max synapse in-degree (>= 1).

    Padding convention: index entries beyond a row's real length point at
    the out-of-range id (neuron ``m`` / rule ``n``); every consumer gathers
    through a zero-extended table so padding contributes exactly 0.
    """

    # -- per-rule metadata (identical convention to CompiledSNP) ----------
    rule_neuron: jnp.ndarray    # (n,)  int32
    consume: jnp.ndarray        # (n,)  int32
    produce: jnp.ndarray        # (n,)  int32
    regex_base: jnp.ndarray     # (n,)  int32
    regex_period: jnp.ndarray   # (n,)  int32
    covering: jnp.ndarray       # (n,)  bool
    env_produce: jnp.ndarray    # (n,)  int32
    init_config: jnp.ndarray    # (m,)  int32
    out_neuron: jnp.ndarray     # ()    int32 — output neuron, or m if none
    rule_order: jnp.ndarray     # (n,)  int32
    # -- per-neuron rule segments (rules are neuron-sorted) ---------------
    seg_start: jnp.ndarray      # (m,) int32 — first rule index of neuron
    seg_count: jnp.ndarray      # (m,) int32 — #rules owned by neuron
    rule_slots: jnp.ndarray     # (R,) int32 == arange(R); carries R in its
    #                             shape so traced code can size tables
    # -- ELL in-adjacency of the synapse graph ----------------------------
    in_idx: jnp.ndarray         # (m, Kin) int32 — in-neighbors, pad m
    # -- COO tail of the in-adjacency (hybrid encoding; empty for pure ELL)
    coo_src: jnp.ndarray        # (Ec,) int32 — tail in-neighbor
    coo_dst: jnp.ndarray        # (Ec,) int32 — tail target neuron (sorted)
    # -- COO lowering metadata: the scatter-free segment-sum form the fused
    #    kernel consumes (DESIGN.md §3 "Kernel lowering").  ``coo_dst`` is
    #    sorted, so each hub's tail is one contiguous run: hub ``h`` owns
    #    entries ``coo_bounds[h]:coo_bounds[h+1]`` and a neuron maps to its
    #    hub via ``hub_slot`` (``Hn`` = no tail, the zero slot).  ``None``
    #    only on hand-built encodings that skipped the compiler — the
    #    kernel refuses those instead of silently downgrading.
    coo_bounds: jnp.ndarray = None   # (Hn+1,) int32 — per-hub tail offsets
    hub_slot: jnp.ndarray = None     # (m,) int32 — neuron -> hub index or Hn
    # -- delayed-semantics extension (None == no_delays encoding) ---------
    delay: jnp.ndarray = None        # (n,) int32 — per-rule firing delay
    #   The reopen-pending fanout reuses in_idx/COO (the same in-adjacency
    #   the fired produce rides), so no extra adjacency array is needed.

    @property
    def num_rules(self) -> int:
        return self.rule_neuron.shape[0]

    @property
    def num_neurons(self) -> int:
        return self.seg_start.shape[0]

    @property
    def state_width(self) -> int:
        """Columns of one configuration row: ``m``, or ``3m`` under the
        delayed semantics (``[spikes | countdown | pending]``)."""
        return self.init_config.shape[0]

    @property
    def max_rules_per_neuron(self) -> int:
        return self.rule_slots.shape[0]

    @property
    def max_in_degree(self) -> int:
        return self.in_idx.shape[1]

    @property
    def is_hybrid(self) -> bool:
        """True when the in-adjacency carries a COO tail (hybrid plan)."""
        return self.coo_src.shape[0] > 0

    @property
    def in_adjacency_slots(self) -> int:
        """Total in-adjacency storage slots (ELL padding included) — the
        quantity the hybrid split minimizes on heavy-tailed graphs."""
        return self.in_idx.size + self.coo_src.shape[0]


CompiledAny = Union[CompiledSNP, CompiledSparseSNP]


def is_compiled(obj) -> bool:
    """True for any compiled encoding (dense or sparse)."""
    return isinstance(obj, (CompiledSNP, CompiledSparseSNP))


def is_delayed(comp) -> bool:
    """True when ``comp`` was compiled under the delayed semantics tier
    (its per-rule delay vector is populated and its configuration rows
    carry the ``[spikes | countdown | pending]`` layout)."""
    return getattr(comp, "delay", None) is not None


# ---------------------------------------------------------------------------
# shared numpy lowering helpers
# ---------------------------------------------------------------------------


def _ragged_arange(counts: np.ndarray) -> np.ndarray:
    """``concatenate([arange(c) for c in counts])`` without the Python loop."""
    counts = np.asarray(counts, np.int64)
    total = int(counts.sum())
    if total == 0:
        return np.zeros((0,), np.int64)
    starts = np.cumsum(counts) - counts
    return np.arange(total) - np.repeat(starts, counts)


class _Lowered(NamedTuple):
    """Neuron-sorted rule arrays + synapse adjacency, all numpy."""

    order: np.ndarray         # (n,) i64 — original index per sorted rule
    rules: List[Rule]
    neuron: np.ndarray        # (n,) i32
    consume: np.ndarray       # (n,) i32
    produce: np.ndarray       # (n,) i32
    regex_base: np.ndarray
    regex_period: np.ndarray
    covering: np.ndarray      # (n,) bool
    env_produce: np.ndarray   # (n,) i32
    src: np.ndarray           # (E,) i32 — synapse sources, sorted by (src,dst)
    dst: np.ndarray           # (E,) i32
    out_deg: np.ndarray       # (m,) i64
    out_start: np.ndarray     # (m,) i64 — CSR row starts into src/dst


def _lower(system: SNPSystem) -> _Lowered:
    m, n = system.num_neurons, system.num_rules
    if n == 0:
        raise ValueError("system has no rules")

    # Stable sort rules by neuron, remembering the original total order so
    # spiking vectors can be reported in the paper's ordering.
    neuron0 = np.fromiter((r.neuron for r in system.rules), np.int64, n)
    order = np.argsort(neuron0, kind="stable")
    rules = [system.rules[i] for i in order]

    neuron = neuron0[order].astype(np.int32)
    consume = np.fromiter((r.consume for r in rules), np.int32, n)
    produce = np.fromiter((r.produce for r in rules), np.int32, n)
    regex_base = np.fromiter((r.regex_base for r in rules), np.int32, n)
    regex_period = np.fromiter((r.regex_period for r in rules), np.int32, n)
    covering = np.fromiter((r.covering for r in rules), bool, n)
    env_produce = np.where(neuron == system.output_neuron, produce, 0) \
        .astype(np.int32)

    syn = np.asarray(system.synapses, np.int64).reshape(-1, 2)
    o = np.lexsort((syn[:, 1], syn[:, 0]))
    src, dst = syn[o, 0], syn[o, 1]
    out_deg = np.bincount(src, minlength=m)
    out_start = np.cumsum(out_deg) - out_deg

    return _Lowered(order=order, rules=rules,
                    neuron=neuron, consume=consume, produce=produce,
                    regex_base=regex_base, regex_period=regex_period,
                    covering=covering, env_produce=env_produce,
                    src=src.astype(np.int32), dst=dst.astype(np.int32),
                    out_deg=out_deg, out_start=out_start)


def _rule_row_entries(low: _Lowered):
    """Flat (rule, column, value) triples of the produce entries of M_Π.

    Rule ``i`` (neuron-sorted) with ``produce > 0`` writes ``produce`` into
    every out-neighbor column of its neuron; the consume entry (its own
    neuron, value ``-consume``) is handled separately.
    """
    prod_rules = np.nonzero(low.produce > 0)[0]
    deg_r = low.out_deg[low.neuron[prod_rules]]
    rows = np.repeat(prod_rules, deg_r)
    pos = _ragged_arange(deg_r)
    flat = np.repeat(low.out_start[low.neuron[prod_rules]], deg_r) + pos
    cols = low.dst[flat] if rows.size else np.zeros((0,), np.int32)
    vals = np.repeat(low.produce[prod_rules], deg_r)
    return rows.astype(np.int64), cols, vals.astype(np.int32)


def _delay_vector(low: _Lowered) -> np.ndarray:
    return np.fromiter((r.delay for r in low.rules), np.int32,
                       len(low.rules))


def _widened_init(system: SNPSystem) -> np.ndarray:
    """``[spikes | countdown | pending]`` initial state: every neuron
    starts open with nothing pending."""
    m = system.num_neurons
    out = np.zeros((3 * m,), np.int32)
    out[:m] = system.initial_spikes
    return out


def compile_system(system: SNPSystem, *,
                   semantics: str = "no_delays") -> CompiledSNP:
    """Dense lowering (paper eq. 1).  Fully vectorized: the dense ``M`` is
    built by adjacency indexing, not an ``O(n·m)`` synapse-set scan.

    ``semantics="delays"`` additionally emits the per-rule delay vector,
    the 0/1 synapse adjacency (reopen-pending fanout), and the widened
    ``3m`` initial state (DESIGN.md §2 "Delayed semantics")."""
    delayed = _check_semantics(system, semantics)
    m, n = system.num_neurons, system.num_rules
    low = _lower(system)

    M = np.zeros((n, m), dtype=np.int32)
    M[np.arange(n), low.neuron] = -low.consume
    rows, cols, vals = _rule_row_entries(low)
    M[rows, cols] = vals  # no collisions: self-synapses are forbidden

    onehot = np.zeros((n, m), dtype=np.int8)
    onehot[np.arange(n), low.neuron] = 1

    extra = {}
    if delayed:
        adj = np.zeros((m, m), np.int32)
        adj[low.src, low.dst] = 1
        extra = dict(
            delay=jnp.asarray(_delay_vector(low)),
            adjacency=jnp.asarray(adj),
            out_neuron=jnp.asarray(
                system.output_neuron if system.output_neuron >= 0 else m,
                dtype=jnp.int32))
    init = _widened_init(system) if delayed \
        else np.asarray(system.initial_spikes, np.int32)

    return CompiledSNP(
        M=jnp.asarray(M),
        rule_neuron=jnp.asarray(low.neuron),
        consume=jnp.asarray(low.consume),
        produce=jnp.asarray(low.produce),
        regex_base=jnp.asarray(low.regex_base),
        regex_period=jnp.asarray(low.regex_period),
        covering=jnp.asarray(low.covering),
        neuron_onehot=jnp.asarray(onehot),
        env_produce=jnp.asarray(low.env_produce),
        init_config=jnp.asarray(init, dtype=jnp.int32),
        rule_order=jnp.asarray(low.order, jnp.int32),
        **extra,
    )


def compile_system_sparse(system: SNPSystem, *,
                          hub_threshold: int | None = None,
                          semantics: str = "no_delays"
                          ) -> CompiledSparseSNP:
    """Sparse lowering: per-rule vectors + per-neuron segments + ELL
    in-adjacency.  Never allocates anything ``O(n·m)``; memory and compile
    time are ``O(n + m·Kin)`` with the measured in-degree.

    ``hub_threshold=H`` selects the **hybrid** in-adjacency: the ELL part
    is capped at ``H`` entries per neuron and every further in-synapse of a
    hub neuron lands in the COO tail (``coo_src``/``coo_dst``, sorted by
    ``(dst, src)``), so heavy-tailed graphs stop paying ``m·Kin`` padding
    for one hub.  ``None`` (default) is the pure-ELL layout, bit-identical
    to the pre-plan encoding.  Callers normally reach this through
    ``backend.compile(system, plan=...)`` (DESIGN.md §3).

    ``semantics="delays"`` emits the per-rule delay vector and the
    widened ``3m`` initial state; the reopen-pending fanout rides the
    same ELL/COO in-adjacency as the fired produce, so the layout gains
    no new index arrays (DESIGN.md §2 "Delayed semantics")."""
    delayed = _check_semantics(system, semantics)
    m = system.num_neurons
    low = _lower(system)

    # The sparse step packs (produce, consume) of a fired rule into one
    # int32 (produce | consume << 16) so the hot per-branch lookup is a
    # single gather; bounds far beyond any simulable system (spike counts
    # must stay < 2^24 anyway, DESIGN.md §2).
    if int(low.produce.max(initial=0)) >= 1 << 16 \
            or int(low.consume.max(initial=0)) >= 1 << 15:
        raise ValueError("sparse encoding requires produce < 2^16 and "
                         "consume < 2^15 per rule")

    # -- per-neuron rule segments -----------------------------------------
    seg_count = np.bincount(low.neuron, minlength=m).astype(np.int32)
    seg_start = (np.cumsum(seg_count) - seg_count).astype(np.int32)
    R = int(max(seg_count.max(), 1))

    # -- ELL in-adjacency (transposed synapse graph) ----------------------
    # Entries sorted by (target, source); a ragged arange over the in-degree
    # histogram yields each entry's slot within its target's row.  With a
    # hub threshold, slots >= threshold spill to the COO tail (still in
    # (target, source) order, so the split is deterministic).
    in_deg = np.bincount(low.dst, minlength=m)
    kin_full = int(max(in_deg.max() if in_deg.size else 0, 1))
    if hub_threshold is not None and hub_threshold < 1:
        raise ValueError(f"hub_threshold must be >= 1, got {hub_threshold}")
    Kin = kin_full if hub_threshold is None else min(kin_full,
                                                    int(hub_threshold))
    o = np.lexsort((low.src, low.dst))
    slot = _ragged_arange(in_deg)
    ell_part = slot < Kin
    in_idx = np.full((m, Kin), m, dtype=np.int32)
    in_idx[low.dst[o][ell_part], slot[ell_part]] = low.src[o][ell_part]
    coo_src = low.src[o][~ell_part].astype(np.int32)
    coo_dst = low.dst[o][~ell_part].astype(np.int32)

    # COO segment metadata (kernel lowering, DESIGN.md §3): coo_dst is
    # (dst, src)-sorted, so each hub's tail is one contiguous run.
    hubs, hub_counts = np.unique(coo_dst, return_counts=True)
    hn = hubs.shape[0]
    coo_bounds = np.zeros((hn + 1,), np.int32)
    np.cumsum(hub_counts, out=coo_bounds[1:])
    hub_slot = np.full((m,), hn, np.int32)
    hub_slot[hubs] = np.arange(hn, dtype=np.int32)

    init = _widened_init(system) if delayed \
        else np.asarray(system.initial_spikes, np.int32)
    return CompiledSparseSNP(
        rule_neuron=jnp.asarray(low.neuron),
        consume=jnp.asarray(low.consume),
        produce=jnp.asarray(low.produce),
        regex_base=jnp.asarray(low.regex_base),
        regex_period=jnp.asarray(low.regex_period),
        covering=jnp.asarray(low.covering),
        env_produce=jnp.asarray(low.env_produce),
        init_config=jnp.asarray(init, dtype=jnp.int32),
        out_neuron=jnp.asarray(
            system.output_neuron if system.output_neuron >= 0 else m,
            dtype=jnp.int32),
        rule_order=jnp.asarray(low.order, jnp.int32),
        seg_start=jnp.asarray(seg_start),
        seg_count=jnp.asarray(seg_count),
        rule_slots=jnp.arange(R, dtype=jnp.int32),
        in_idx=jnp.asarray(in_idx),
        coo_src=jnp.asarray(coo_src),
        coo_dst=jnp.asarray(coo_dst),
        coo_bounds=jnp.asarray(coo_bounds),
        hub_slot=jnp.asarray(hub_slot),
        delay=jnp.asarray(_delay_vector(low)) if delayed else None,
    )
