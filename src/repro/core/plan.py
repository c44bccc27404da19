"""Partition/encoding planning layer: one `SystemPlan` in front of compile.

The paper's matrix representation makes the SNP transition device-friendly,
but a monolithic per-device encoding stops at ~10^4 neurons.  Everything a
compiler must decide *about storage layout* — and nothing about semantics —
lives here:

* **encoding per neuron block** — ``"dense"`` (the paper's ``M_Π``),
  ``"ell"`` (PR 2's ELL/segment layout), or ``"hybrid"``: ELL capped at a
  hub threshold with the tail synapses of heavy neurons spilled into a COO
  segment combined by segment-sum.  Hybrid is the heavy-tail answer
  (power-law graphs without ``max_in``): pure ELL pads *every* neuron's
  in-adjacency row to the top hub's in-degree, hybrid pads only to the
  threshold (DESIGN.md §3).
* **neuron-axis partition** — ``num_shards > 1`` lowers to a
  :class:`ShardedCompiled`: per-shard encodings (stacked so shard ``d``'s
  slice rides a ``shard_map`` device axis) plus the halo/exchange metadata
  saying which remote neuron segments each shard's rules read.  Consumed by
  :func:`repro.core.distributed.explore_distributed` (DESIGN.md §2).

Backends accept a plan in ``compile(system, plan=...)``
(:mod:`repro.core.backend`); the default plan (``SystemPlan()``) reproduces
each backend's historical encoding bit-for-bit, so every existing workload
is unchanged until a plan asks for more.

Decision rules (``SystemPlan.for_system``): let ``mean`` be the mean
in-degree and ``Kin`` the max.  The auto hub threshold is
``H = max(4, 4·ceil(mean))`` — wide enough that regular graphs
(ring lattice, torus, Erdős–Rényi at benchmark densities) keep a zero COO
tail, tight enough that a power-law hub spills.  Hybrid is chosen iff
``Kin > 2·H`` (the padding saved is at least half the ELL array);
otherwise plain ELL.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .system import SNPSystem

__all__ = [
    "KernelConfig",
    "SystemPlan",
    "ShardArrays",
    "DenseShardArrays",
    "ShardedCompiled",
    "auto_hub_threshold",
    "compile_sharded",
    "is_sharded",
    "lower_shard_dense",
    "partition_neurons",
    "partition_stats",
    "resolve_interpret",
]

_ENCODINGS = ("auto", "dense", "ell", "hybrid")
_MODES = ("auto", "measure", "static")
_SEMANTICS = ("no_delays", "delays")
_PARTITIONS = ("contiguous", "degree")

# Dummy padding rules (sharded lowering) use this regex base: applicability
# requires spikes == 2^24, which the engine's spike-count contract
# (DESIGN.md §2, counts < 2^24) makes unreachable.
_NEVER_BASE = 1 << 24


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Whether a fused Pallas kernel runs emulated: ``None`` (the default
    of the kernel backends and wrappers) means "unless JAX's default
    backend is a TPU", so one code path compiles the kernel on a chip and
    emulates it on the CPU.  An explicit bool pins the mode (a compile for
    a described TPU passes ``False``)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Grid/block shape for the fused Pallas lowerings, lifted out of the
    kernel wrappers so a plan can carry it (DESIGN.md §3 "Planner &
    autotuner").

    * ``block_b`` / ``block_t`` — batch / branch tile; both kernels grid
      over ``(B/bb, T/bt)``.
    * ``block_n`` — rule-axis tile of the **dense** kernel only (the
      sparse kernel keeps the whole neuron axis resident per block);
      setting it for a sparse lowering is a lower-time error.

    ``None`` fields mean "keep that axis's wrapper default".  Frozen and
    hashable, so a config rides ``jit(static_argnames=...)`` and keys the
    per-backend compile caches (two block shapes never collide into one
    cached executable)."""

    block_b: Optional[int] = None
    block_t: Optional[int] = None
    block_n: Optional[int] = None

    def __post_init__(self) -> None:
        for field in ("block_b", "block_t", "block_n"):
            v = getattr(self, field)
            if v is None:
                continue
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                raise ValueError(
                    f"KernelConfig.{field} must be a positive int or "
                    f"None, got {v!r}")

    @staticmethod
    def dense_default() -> "KernelConfig":
        """The dense wrapper defaults (``ops.snp_step``), sized so the
        kernel's VMEM working set (``kernel.vmem_bytes``) stays inside its
        limit up to a few thousand neurons."""
        return KernelConfig(block_b=8, block_t=32, block_n=128)

    @staticmethod
    def sparse_default() -> "KernelConfig":
        """The sparse wrapper defaults (``sparse_ops.snp_step_sparse``);
        no ``block_n`` — the neuron axis is never tiled."""
        return KernelConfig(block_b=8, block_t=32)

    def merged(self, *, block_b: Optional[int] = None,
               block_t: Optional[int] = None,
               block_n: Optional[int] = None) -> "KernelConfig":
        """This config with explicit per-axis overrides folded in
        (explicit kwarg > this config's field)."""
        return KernelConfig(
            block_b=self.block_b if block_b is None else block_b,
            block_t=self.block_t if block_t is None else block_t,
            block_n=self.block_n if block_n is None else block_n,
        )


@dataclasses.dataclass(frozen=True)
class SystemPlan:
    """How to lay an SNP system out on device(s).

    * ``encoding`` — ``"auto"`` (the backend's native layout: dense for
      ``ref``/``pallas``, ELL for the sparse pair), ``"dense"``, ``"ell"``,
      or ``"hybrid"`` (ELL capped at ``hub_threshold`` + COO tail).
    * ``hub_threshold`` — in-degree cap for the hybrid ELL part; ``None``
      lets :func:`auto_hub_threshold` pick from the degree histogram.
    * ``num_shards`` — neuron-axis partition count; ``> 1`` lowers through
      :func:`compile_sharded` and is only consumed by
      ``explore_distributed`` (one shard per device).
    * ``mode`` — how :func:`for_system` (and the entry points that call it
      when the caller names no backend) decide: ``"auto"`` consults the
      autotune cache then the analytic cost model, ``"measure"`` runs the
      autotuner inline, ``"static"`` keeps the degree heuristic
      (:mod:`repro.core.autotune`, DESIGN.md §3 "Planner & autotuner").
    * ``backend`` — step-backend registry name the planner picked (or the
      caller pinned); ``None`` leaves the choice to the call site.
    * ``kernel`` — optional :class:`KernelConfig` block shape for Pallas
      backends; validated at lower time (``resolve_kernel``) against the
      backend it lands on.
    * ``semantics`` — transition-semantics tier: ``"no_delays"`` (the
      paper's delay-free systems, the default, bit-identical to the
      historical behavior) or ``"delays"`` (per-rule firing delays with
      neuron open/closed state; configurations widen to ``3m`` —
      DESIGN.md "Delayed semantics").  A backend that cannot realize an
      encoding under the requested tier raises at compile time
      (``supported_encodings(semantics=...)``), never downgrades.
    * ``partition`` — how neurons map to shards when ``num_shards > 1``:
      ``"contiguous"`` (the historical ``mloc``-sized slices, bit-identical
      layout) or ``"degree"`` (hub-aware greedy bin-packing: neurons are
      placed heaviest-degree-first onto the least-loaded shard, so the
      hubs of a power-law graph spread across devices instead of piling
      onto whichever slice they fall in — :func:`partition_neurons`).
      Per-shard occupancy lands on ``ShardedCompiled.occupancy`` so the
      planner can report imbalance (:func:`partition_stats`).

    Frozen and hashable, so a plan can ride through
    ``jit(static_argnames=...)`` with the backend.
    """

    encoding: str = "auto"
    hub_threshold: Optional[int] = None
    num_shards: int = 1
    mode: str = "auto"
    backend: Optional[str] = None
    kernel: Optional[KernelConfig] = None
    semantics: str = "no_delays"
    partition: str = "contiguous"

    def __post_init__(self) -> None:
        if self.encoding not in _ENCODINGS:
            raise ValueError(
                f"unknown encoding {self.encoding!r}; one of {_ENCODINGS}")
        if self.semantics not in _SEMANTICS:
            raise ValueError(
                f"unknown semantics {self.semantics!r}; one of {_SEMANTICS}")
        if self.hub_threshold is not None and self.hub_threshold < 1:
            raise ValueError(
                f"hub_threshold must be >= 1, got {self.hub_threshold}")
        if self.num_shards < 1:
            raise ValueError(
                f"num_shards must be >= 1, got {self.num_shards}")
        if self.mode not in _MODES:
            raise ValueError(
                f"unknown mode {self.mode!r}; one of {_MODES}")
        if self.kernel is not None and not isinstance(self.kernel,
                                                      KernelConfig):
            raise ValueError(
                f"plan kernel must be a KernelConfig or None, "
                f"got {type(self.kernel).__name__}")
        if self.partition not in _PARTITIONS:
            raise ValueError(
                f"unknown partition {self.partition!r}; one of {_PARTITIONS}")

    @staticmethod
    def default() -> "SystemPlan":
        """The identity plan: every backend keeps its historical encoding."""
        return SystemPlan()

    @staticmethod
    def for_system(system: SNPSystem, *,
                   num_shards: int = 1,
                   workload: Optional[Tuple[int, int]] = None,
                   mode: str = "static",
                   semantics: str = "no_delays") -> "SystemPlan":
        """Concrete plan for ``system``.

        ``mode="static"`` (the default) keeps the degree heuristic
        (module docstring rules): hybrid iff the max in-degree is
        heavy-tailed relative to the mean, else plain ELL.  With
        ``num_shards > 1`` the encoding stays ELL regardless — the
        per-shard lowering is ELL-only (:func:`compile_sharded` refuses
        the hybrid combination).

        ``mode="auto"`` consults the autotune cache (seeded from the
        committed bench baseline) and falls back to the analytic cost
        model; ``mode="measure"`` times candidate configurations inline
        and persists the winner (:mod:`repro.core.autotune`).  Both fall
        through to the static heuristic when the planner has nothing to
        say.  ``workload=(B, T)`` is the batch/branch shape the plan will
        serve — the dense/sparse crossover depends on it, not just on the
        degree histogram."""
        if mode not in _MODES:
            raise ValueError(f"unknown mode {mode!r}; one of {_MODES}")
        if semantics not in _SEMANTICS:
            raise ValueError(
                f"unknown semantics {semantics!r}; one of {_SEMANTICS}")
        if semantics == "delays" and num_shards > 1:
            raise ValueError(
                "no backend shards semantics='delays' yet; use "
                "num_shards=1 for delayed systems")
        if mode != "static":
            from . import autotune  # lazy: autotune imports backend
            plan = autotune.plan_for(system, num_shards=num_shards,
                                     workload=workload,
                                     measure=(mode == "measure"),
                                     semantics=semantics)
            if plan is not None:
                return plan
        in_deg = _in_degrees(system)
        h = auto_hub_threshold(in_deg)
        kin = int(in_deg.max()) if in_deg.size else 0
        if num_shards == 1 and kin > 2 * h:
            return SystemPlan(encoding="hybrid", hub_threshold=h,
                              mode=mode, semantics=semantics)
        # Heavy-tailed graph over >1 shard: spread the hubs (the same
        # degree test that triggers hybrid single-device).
        part = "degree" if (num_shards > 1 and kin > 2 * h) else "contiguous"
        return SystemPlan(encoding="ell", num_shards=num_shards, mode=mode,
                          semantics=semantics, partition=part)

    def resolved_hub_threshold(self, system: SNPSystem) -> Optional[int]:
        """The hub threshold ``compile_system_sparse`` should cap ELL rows
        at: ``None`` unless this plan asks for the hybrid encoding."""
        if self.encoding != "hybrid":
            return None
        if self.hub_threshold is not None:
            return self.hub_threshold
        return auto_hub_threshold(_in_degrees(system))


def _in_degrees(system: SNPSystem) -> np.ndarray:
    syn = np.asarray(system.synapses, np.int64).reshape(-1, 2)
    return np.bincount(syn[:, 1], minlength=system.num_neurons) \
        if syn.size else np.zeros((system.num_neurons,), np.int64)


def auto_hub_threshold(in_deg: np.ndarray) -> int:
    """``max(4, 4·ceil(mean nonzero in-degree))`` — see module docstring."""
    in_deg = np.asarray(in_deg)
    nz = in_deg[in_deg > 0]
    mean = float(nz.mean()) if nz.size else 0.0
    return max(4, 4 * math.ceil(mean))


# ---------------------------------------------------------------------------
# Neuron-axis sharded lowering
# ---------------------------------------------------------------------------


class ShardArrays(NamedTuple):
    """Stacked per-shard arrays: leading axis ``S`` = shard id, sharded
    ``P(axis)`` into a ``shard_map`` so device ``d`` sees shard ``d``'s
    slice.  ``rule_slots`` is the one replicated leaf (it carries ``R`` in
    its shape for every shard alike).

    Shapes: ``S`` shards, ``mloc = ceil(m/S)`` neurons per shard, ``nloc``
    = max rules per shard (padded with never-applicable dummies *after*
    the real, neuron-sorted prefix — the segment tables only cover the
    real prefix), ``Kin`` = max in-degree, ``Hmax`` = max halo segment
    between any shard pair.

    ``in_idx`` indexes the *extended* per-device produce buffer
    ``[local (mloc) | halo (S·Hmax) | zero (1)]``: a remote in-neighbor
    owned by shard ``o`` at halo slot ``s`` is ``mloc + o·Hmax + s``;
    padding points at the trailing zero (``mloc + S·Hmax``).
    ``send_idx[d, p]`` lists the local neuron indices shard ``d`` must
    ship to peer ``p`` (ascending, padded with ``mloc`` = a zero slot),
    so one tiled ``all_to_all`` realizes every halo.
    """

    rule_neuron: jnp.ndarray    # (S, nloc) i32 — local neuron of each rule
    consume: jnp.ndarray        # (S, nloc) i32
    produce: jnp.ndarray        # (S, nloc) i32
    regex_base: jnp.ndarray     # (S, nloc) i32
    regex_period: jnp.ndarray   # (S, nloc) i32
    covering: jnp.ndarray       # (S, nloc) bool
    seg_start: jnp.ndarray      # (S, mloc) i32
    seg_count: jnp.ndarray      # (S, mloc) i32
    rule_slots: jnp.ndarray     # (R,) i32 == arange(R)  [replicated]
    in_idx: jnp.ndarray         # (S, mloc, Kin) i32 — extended space
    send_idx: jnp.ndarray       # (S, S, Hmax) i32 — local ids, pad mloc
    out_local: jnp.ndarray      # (S,) i32 — local output neuron or mloc
    init_loc: jnp.ndarray       # (S, mloc) i32 — C_0 slices (zero padded)
    global_idx: jnp.ndarray     # (S, mloc) i32 — global neuron id per
    #   column (pads get the unused ids m..S·mloc-1); feeds zobrist
    #   positions + archive reassembly under any partition


class ShardView(NamedTuple):
    """One shard's de-stacked arrays, duck-typing the ``CompiledSparseSNP``
    fields that :func:`repro.core.semantics.applicability`,
    :func:`~repro.core.semantics.sparse_branch_info` and
    :func:`~repro.core.semantics.packed_rule_table` read — so the sharded
    device step reuses the sparse reference math verbatim on its local
    neuron slice."""

    rule_neuron: jnp.ndarray
    consume: jnp.ndarray
    produce: jnp.ndarray
    regex_base: jnp.ndarray
    regex_period: jnp.ndarray
    covering: jnp.ndarray
    seg_start: jnp.ndarray
    seg_count: jnp.ndarray
    rule_slots: jnp.ndarray

    @property
    def num_rules(self) -> int:
        return self.rule_neuron.shape[0]

    @property
    def num_neurons(self) -> int:
        return self.seg_start.shape[0]


class DenseShardArrays(NamedTuple):
    """Per-shard *dense* kernel operands (DESIGN.md §3 "Kernel lowering"),
    attached by ``PallasBackend.lower`` so the fused dense kernel can
    consume a shard: ``C' = C + halo·hadj + S·M_local``.  Stacked like
    :class:`ShardArrays` (leading axis ``S``, sharded ``P(axis)``).

    ``M_local[d]`` restricts each local rule's row of ``M_Π`` to shard
    ``d``'s columns (``-consume`` at the owner, ``produce`` on *local*
    out-neighbors; dummy padding rules are all-zero — they never fire
    anyway).  ``hadj[d][s, j] = 1`` iff halo slot ``s`` of the extended
    index space feeds local neuron ``j`` — remote produce enters as one
    extra matmul instead of a gather."""

    M_local: jnp.ndarray        # (S, nloc, mloc) i32
    onehot: jnp.ndarray         # (S, nloc, mloc) i8 — rule→local neuron
    hadj: jnp.ndarray           # (S, S·Hmax, mloc) i8


@dataclasses.dataclass(frozen=True, eq=False)
class ShardedCompiled:
    """Neuron-axis partitioned lowering: stacked shard encodings + halo
    metadata.  Produced by :func:`compile_sharded`, consumed by
    ``explore_distributed`` (DESIGN.md §2); the static ints live outside
    the array pytree so they stay Python constants under ``jit``.
    ``dense`` is the optional dense-kernel view of the same shards
    (:class:`DenseShardArrays`), attached by ``PallasBackend.lower``."""

    arrays: ShardArrays
    plan: SystemPlan
    num_neurons: int            # true m (before padding to S·mloc)
    num_rules: int              # true n (before dummy padding)
    shard_size: int             # mloc
    num_shards: int             # S
    halo_width: int             # Hmax
    dense: Optional[DenseShardArrays] = None
    occupancy: Optional[np.ndarray] = None   # (S,) degree weight per shard

    @property
    def init_config(self) -> jnp.ndarray:
        """Full (m,) initial configuration, reassembled from the slices
        via the column→global-neuron map (identity for contiguous
        partitions, a scatter for degree-weighted ones)."""
        flat = self.arrays.init_loc.reshape(-1)
        gidx = self.arrays.global_idx.reshape(-1)
        return jnp.zeros_like(flat).at[gidx].set(flat)[: self.num_neurons]


def is_sharded(obj) -> bool:
    return isinstance(obj, ShardedCompiled)


def _degree_weights(system: SNPSystem) -> np.ndarray:
    """Per-neuron work weight: in-degree + out-degree + 1.  Degree drives
    both the gather width a neuron costs per step (in-adjacency rows) and
    the halo traffic it can induce (out-synapses crossing shards); the +1
    floors isolated neurons at one slot of work."""
    syn = np.asarray(system.synapses, np.int64).reshape(-1, 2)
    w = np.ones((system.num_neurons,), np.int64)
    if syn.size:
        w += np.bincount(syn[:, 0], minlength=system.num_neurons)
        w += np.bincount(syn[:, 1], minlength=system.num_neurons)
    return w


def partition_neurons(system: SNPSystem, num_shards: int,
                      partition: str = "contiguous"
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray]:
    """Neuron→shard assignment: ``(shard_of (m,), local_of (m,),
    global_idx (S, mloc), occupancy (S,))``.

    ``"contiguous"`` is the historical slicing (neuron ``j`` → shard
    ``j // mloc``).  ``"degree"`` is LPT-style greedy bin-packing under
    the hard capacity ``mloc``: neurons in descending degree-weight order
    (ties by index — deterministic) each go to the least-loaded shard
    with a free slot (ties to the lowest shard id).  On a power-law
    graph the hubs land on *different* shards, so per-shard occupancy
    (summed :func:`_degree_weights`) flattens instead of tracking
    whichever contiguous slice the hubs fell into.

    ``global_idx[d, c]`` is the global neuron a shard column holds; pad
    columns take the unused ids ``m..S·mloc-1`` so every column has a
    distinct global position (the zobrist position space stays injective,
    and pads — always zero spikes — contribute a constant to every
    hash)."""
    if partition not in _PARTITIONS:
        raise ValueError(
            f"unknown partition {partition!r}; one of {_PARTITIONS}")
    S, m = num_shards, system.num_neurons
    mloc = -(-m // S)
    w = _degree_weights(system)
    if partition == "contiguous":
        ids = np.arange(m, dtype=np.int64)
        shard_of = (ids // mloc).astype(np.int32)
        local_of = (ids % mloc).astype(np.int32)
        global_idx = np.arange(S * mloc, dtype=np.int32).reshape(S, mloc)
    else:
        shard_of = np.zeros((m,), np.int32)
        local_of = np.zeros((m,), np.int32)
        load = np.zeros((S,), np.int64)
        cnt = np.zeros((S,), np.int64)
        for j in np.argsort(-w, kind="stable"):
            free = np.flatnonzero(cnt < mloc)
            d = int(free[np.argmin(load[free])])
            shard_of[j] = d
            local_of[j] = cnt[d]
            load[d] += w[j]
            cnt[d] += 1
        global_idx = np.zeros((S, mloc), np.int32)
        global_idx[shard_of, local_of] = np.arange(m, dtype=np.int32)
        pad = m
        for d in range(S):
            for c in range(int(cnt[d]), mloc):
                global_idx[d, c] = pad
                pad += 1
    occupancy = np.zeros((S,), np.int64)
    np.add.at(occupancy, shard_of, w)
    return shard_of, local_of, global_idx, occupancy


def partition_stats(occupancy: np.ndarray) -> dict:
    """Imbalance summary of a shard assignment: max / mean per-shard
    occupancy and their ratio (1.0 = perfectly level).  The planner and
    the ``explore/partition`` bench tier report these."""
    occ = np.asarray(occupancy, np.float64)
    mean = float(occ.mean()) if occ.size else 0.0
    mx = float(occ.max()) if occ.size else 0.0
    return {"max": mx, "mean": mean,
            "imbalance": (mx / mean) if mean else 1.0}


def compile_sharded(system: SNPSystem, plan: SystemPlan) -> ShardedCompiled:
    """Lower ``system`` to ``plan.num_shards`` neuron-axis shards.

    Host-side numpy, same vectorized-adjacency discipline as the other
    compilers (the only Python loops are over ``S`` and ``S²`` shard
    pairs).  Every shard gets identical array *shapes* (rules padded with
    never-applicable dummies, halos padded to the max pair width) so the
    stacked arrays ride one ``shard_map`` program.
    """
    # Local import: matrix imports stay plan-free (plan -> matrix only).
    from .matrix import _lower, _ragged_arange

    if plan.semantics == "delays":
        # The halo exchange has no notion of countdown/pending state yet;
        # raise here too so explore_distributed (which reaches this
        # compiler directly) cannot silently run delays sharded.
        raise ValueError(
            "neuron-axis sharding does not support semantics='delays' "
            "(the halo exchange carries spike counts only); run delayed "
            "systems single-device")
    if plan.encoding == "hybrid":
        # The per-shard encodings are ELL-only (hub tails widen the halo
        # instead of spilling to COO), and the compile contract
        # (backend.py) forbids silently downgrading a requested encoding
        # — refuse instead.
        raise ValueError(
            "neuron-axis sharding does not support the hybrid ELL+COO "
            "encoding (the sharded step gathers over per-shard ELL "
            "rows only); use encoding='ell' with num_shards > 1")
    if plan.encoding not in ("auto", "ell"):
        # Same contract when explore_distributed reaches here directly,
        # bypassing the backend's _require_encoding check.
        raise ValueError(
            f"neuron-axis sharding lowers to per-shard ELL encodings; "
            f"plan encoding {plan.encoding!r} cannot be realized "
            "(supported: 'auto', 'ell')")
    S = plan.num_shards
    m = system.num_neurons
    low = _lower(system)
    n = low.neuron.shape[0]
    mloc = -(-m // S)
    # Neuron→shard assignment: everything below speaks shard_of/local_of,
    # so contiguous slices and degree-weighted packing share one lowering
    # (contiguous reduces to the historical // mloc arithmetic exactly).
    shard_of, local_of, global_idx, occupancy = partition_neurons(
        system, S, plan.partition)

    # -- rules, re-indexed to local neurons, padded with dummies ----------
    # Grouped by shard, sorted by *local* neuron (stable): the segment
    # tables index rules by local id, and under a degree partition local
    # order no longer matches the lowering's global-neuron sort.
    r_shard = shard_of[low.neuron]
    r_local = local_of[low.neuron]
    rorder = np.lexsort((r_local, r_shard))
    counts = np.bincount(r_shard, minlength=S)
    nloc = int(max(1, counts.max()))
    starts = np.cumsum(counts) - counts

    rn = np.full((S, nloc), mloc - 1, np.int32)
    cons = np.ones((S, nloc), np.int32)
    prod = np.zeros((S, nloc), np.int32)
    base = np.full((S, nloc), _NEVER_BASE, np.int32)
    period = np.zeros((S, nloc), np.int32)
    cov = np.zeros((S, nloc), bool)
    seg_count = np.zeros((S, mloc), np.int32)
    for d in range(S):
        k = int(counts[d])
        sl = rorder[int(starts[d]): int(starts[d]) + k]
        rn[d, :k] = r_local[sl]
        cons[d, :k] = low.consume[sl]
        prod[d, :k] = low.produce[sl]
        base[d, :k] = low.regex_base[sl]
        period[d, :k] = low.regex_period[sl]
        cov[d, :k] = low.covering[sl]
        seg_count[d] = np.bincount(rn[d, :k], minlength=mloc)
    seg_start = (np.cumsum(seg_count, axis=1) - seg_count).astype(np.int32)
    R = int(max(1, seg_count.max()))

    # -- halo metadata: which locals each shard ships to each peer --------
    src, dst = low.src.astype(np.int64), low.dst.astype(np.int64)
    ssh, dsh = shard_of[src], shard_of[dst]
    halo = {}
    hmax = 1
    for o in range(S):
        for d in range(S):
            if o == d:
                continue
            need = np.unique(src[(dsh == d) & (ssh == o)])
            if need.size:
                halo[(o, d)] = need
                hmax = max(hmax, int(need.size))
    # slot p of the (o, d) halo carries the p-th *globally-sorted* needed
    # source; its local id on shard o is local_of[need[p]] (not ascending
    # under a degree partition — the order just has to match in_idx below)
    send_idx = np.full((S, S, hmax), mloc, np.int32)
    for (o, d), need in halo.items():
        send_idx[o, d, : need.size] = local_of[need]

    # -- in-adjacency in extended [local | halo | zero] index space -------
    in_deg = np.bincount(dst, minlength=m)
    kin = int(max(1, in_deg.max() if in_deg.size else 0))
    z = mloc + S * hmax
    in_idx = np.full((S, mloc, kin), z, np.int32)
    if src.size:
        order = np.lexsort((src, dst))
        s_s, d_s = src[order], dst[order]
        slot = _ragged_arange(in_deg)
        e_dsh, e_ssh = shard_of[d_s], shard_of[s_s]
        ext = np.where(e_ssh == e_dsh, local_of[s_s], -1)
        for (o, d), need in halo.items():
            sel = (e_ssh == o) & (e_dsh == d)
            if sel.any():
                pos = np.searchsorted(need, s_s[sel])
                ext[sel] = mloc + o * hmax + pos
        in_idx[e_dsh, local_of[d_s], slot] = ext

    out_local = np.full((S,), mloc, np.int32)
    if system.output_neuron >= 0:
        out_local[shard_of[system.output_neuron]] = \
            local_of[system.output_neuron]

    init_loc = np.zeros((S, mloc), np.int32)
    init_loc[shard_of, local_of] = np.asarray(system.initial_spikes,
                                              np.int32)

    arrays = ShardArrays(
        rule_neuron=jnp.asarray(rn), consume=jnp.asarray(cons),
        produce=jnp.asarray(prod), regex_base=jnp.asarray(base),
        regex_period=jnp.asarray(period), covering=jnp.asarray(cov),
        seg_start=jnp.asarray(seg_start), seg_count=jnp.asarray(seg_count),
        rule_slots=jnp.arange(R, dtype=jnp.int32),
        in_idx=jnp.asarray(in_idx), send_idx=jnp.asarray(send_idx),
        out_local=jnp.asarray(out_local),
        init_loc=jnp.asarray(init_loc),
        global_idx=jnp.asarray(global_idx),
    )
    return ShardedCompiled(arrays=arrays, plan=plan, num_neurons=m,
                           num_rules=n, shard_size=mloc, num_shards=S,
                           halo_width=hmax, occupancy=occupancy)


def lower_shard_dense(comp: ShardedCompiled) -> ShardedCompiled:
    """Attach the dense-kernel operands (:class:`DenseShardArrays`) to a
    sharded lowering.  Host-side numpy (same contract as the compilers);
    idempotent — an already-lowered object passes through."""
    if comp.dense is not None:
        return comp
    from .matrix import _ragged_arange  # plan -> matrix only (no cycle)
    a = comp.arrays
    S, mloc, hmax = comp.num_shards, comp.shard_size, comp.halo_width
    nloc = a.rule_neuron.shape[1]
    rn = np.asarray(a.rule_neuron)
    cons = np.asarray(a.consume)
    prod = np.asarray(a.produce)
    base = np.asarray(a.regex_base)
    seg_start = np.asarray(a.seg_start)
    seg_count = np.asarray(a.seg_count)
    in_idx = np.asarray(a.in_idx)

    M = np.zeros((S, nloc, mloc), np.int32)
    onehot = np.zeros((S, nloc, mloc), np.int8)
    hadj = np.zeros((S, S * hmax, mloc), np.int8)
    for d in range(S):
        real = np.nonzero(base[d] != _NEVER_BASE)[0]
        M[d, real, rn[d, real]] = -cons[d, real]
        onehot[d, real, rn[d, real]] = 1
        # local synapses: in_idx entries below mloc are local sources; a
        # source's every rule writes its produce into the target column.
        jj, kk = np.nonzero(in_idx[d] < mloc)
        src = in_idx[d][jj, kk]
        cnt = seg_count[d, src].astype(np.int64)
        rr = np.repeat(seg_start[d, src], cnt) + _ragged_arange(cnt)
        np.add.at(M[d], (rr, np.repeat(jj, cnt)), prod[d, rr])
        # halo slots feeding local neurons (extended-space indices).
        hj, hk = np.nonzero((in_idx[d] >= mloc) &
                            (in_idx[d] < mloc + S * hmax))
        hadj[d][in_idx[d][hj, hk] - mloc, hj] = 1
    return dataclasses.replace(comp, dense=DenseShardArrays(
        M_local=jnp.asarray(M), onehot=jnp.asarray(onehot),
        hadj=jnp.asarray(hadj)))


def shard_view(arrays: ShardArrays) -> ShardView:
    """Per-device view of stacked arrays whose leading shard axis has
    already been split away by ``shard_map`` (each field is ``(1, ...)``
    except the replicated ``rule_slots``)."""
    return ShardView(
        rule_neuron=arrays.rule_neuron[0], consume=arrays.consume[0],
        produce=arrays.produce[0], regex_base=arrays.regex_base[0],
        regex_period=arrays.regex_period[0], covering=arrays.covering[0],
        seg_start=arrays.seg_start[0], seg_count=arrays.seg_count[0],
        rule_slots=arrays.rule_slots,
    )
