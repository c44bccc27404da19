"""Breadth-first exploration of an SNP system's computation tree.

Implements Algorithm 1 of the paper as a single-device, fully on-device
loop: the whole BFS is one jitted ``lax.while_loop`` whose body expands the
frontier, hashes every successor, dedups against the visited set
(sort-based, exactly-once emission), and compacts the new configurations
into the next frontier.  The host syncs exactly once — to read the final
archive — so the paper's host/device ping-pong (strings to Python, vectors
back) is gone entirely, including the per-level ``frontier_n`` poll the
first version of this engine still paid (DESIGN.md §2).

The transition itself is pluggable: every entry point takes a ``backend=``
(name or :class:`~repro.core.backend.StepBackend`) selecting how successors
are expanded — ``"ref"`` (pure-jnp oracle), ``"pallas"`` (fused dense
kernel), or ``"sparse"``/``"sparse_pallas"`` (ELL gather/segment-sum for
large bounded-degree systems); see :mod:`repro.core.backend`.  Each
backend also owns its lowering: pass an :class:`SNPSystem` and the engine
calls ``backend.compile`` (dense or sparse encoding as appropriate), or
pass a pre-compiled object to reuse it across calls.  Backends agree
bit-for-bit on valid entries, so archives and traces are
backend-independent.

Static-shape discipline: the frontier capacity ``F``, branch fan-out cap
``T`` and visited/archive capacity ``V`` are compile-time constants; all
overflow conditions are detected and reported, never silently dropped:

* ``branch_overflow``   — some config had Ψ > T (only its first T branches
  were explored);
* ``frontier_overflow`` — more than F new configs in one step.  The excess
  are *not* marked visited, so they are re-generated and expanded later:
  exploration stays sound, only the "discovered" count may double-count;
* ``visited_overflow``  — visited set is full; same soundness argument.

The multi-chip versions live in :mod:`repro.core.distributed`:
hash-partitioned BFS (``explore_distributed``) and data-parallel batched
trace serving (``run_traces_distributed``, bit-identical to
:func:`run_traces` — DESIGN.md §4).  The serving front end over
:func:`run_traces` (request batching, async futures drain) is
:class:`repro.serve.snp_service.SNPTraceService`.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from .backend import (BackendLike, compile_with_plan, get_backend,
                      lower_with_backend, resolve_entry_info)
from .failover import run_with_failover
from .hashing import SENTINEL, config_hash
from .hashtable import (HashTable, first_occurrence, insert_unique, lookup,
                        make_table)
from .matrix import CompiledAny, is_compiled
from .plan import SystemPlan
from .semantics import fired_lookup
from .system import SNPSystem

__all__ = ["ExploreState", "ExploreResult", "TraceOut", "explore",
           "resolve_dedup", "successor_set", "emission_gaps", "run_trace",
           "run_traces"]


def _resolve_comp(system, be, plan: Optional[SystemPlan]) -> CompiledAny:
    """Single-device lowering: a pre-compiled encoding passes through the
    backend's ``lower`` hook (so an encoding the backend's kernel cannot
    realize raises instead of being silently reinterpreted), an
    ``SNPSystem`` lowers via ``backend.compile(system, plan=...)``.  Plans
    asking for a neuron-axis partition belong to ``explore_distributed``."""
    if plan is not None and plan.num_shards > 1:
        raise ValueError(
            "plan.num_shards > 1 (neuron-axis sharding) is only consumed "
            "by repro.core.distributed.explore_distributed")
    return lower_with_backend(be, system, plan) if is_compiled(system) \
        else compile_with_plan(be, system, plan)


class ExploreState(NamedTuple):
    """Full BFS device state.  The visited-set representation depends on
    the (static) ``dedup`` mode: ``"hash"`` stores open-addressing table
    slots (``visited_hi/lo/payload`` are ``(S,)`` with ``S =
    table_slots(V)``, ``visited_n`` the live-key count), ``"sort"`` the
    historical lexicographically-sorted ``(V,)`` hash arrays (payload is
    a zero-length placeholder).  Either way the state is one pytree, so
    checkpoint snapshots carry the dedup structure with no special
    casing — a resume rebuilds the table bit-identically."""

    frontier: jnp.ndarray       # (F, m) int32
    frontier_n: jnp.ndarray     # () int32 — valid prefix length
    visited_hi: jnp.ndarray     # (V,)|(S,) uint32 — see docstring
    visited_lo: jnp.ndarray     # (V,)|(S,) uint32
    visited_payload: jnp.ndarray  # (S,)|(0,) int32 — archive row per slot
    visited_n: jnp.ndarray      # () int32
    archive: jnp.ndarray        # (V, m) int32 — discovery order
    archive_n: jnp.ndarray      # () int32
    step: jnp.ndarray           # () int32
    branch_overflow: jnp.ndarray    # () bool
    frontier_overflow: jnp.ndarray  # () bool
    visited_overflow: jnp.ndarray   # () bool


@dataclass(frozen=True)
class ExploreResult:
    configs: np.ndarray         # (n_discovered, m) in discovery order
    num_discovered: int
    steps: int
    exhausted: bool             # True => tree fully explored (no overflow, frontier drained)
    branch_overflow: bool
    frontier_overflow: bool
    visited_overflow: bool

    def as_strings(self) -> List[str]:
        """Configs in the paper's ``allGenCk`` 'a-b-c' string format."""
        return ["-".join(str(int(v)) for v in row) for row in self.configs]


@functools.partial(jax.jit,
                   static_argnames=("frontier_cap", "visited_cap", "dedup"))
def _init_state(comp: CompiledAny, frontier_cap: int, visited_cap: int,
                init: Optional[jnp.ndarray] = None,
                dedup: str = "hash") -> ExploreState:
    # One program per shape: run eagerly, the hash table's first insert
    # compiled a new ``while`` every call.
    # State row width: m for the paper's systems, 3m under delayed
    # semantics ([spikes | countdown | pending] — DESIGN.md).
    m = getattr(comp, "state_width", comp.num_neurons)
    c0 = comp.init_config if init is None else jnp.asarray(init, jnp.int32)
    frontier = jnp.zeros((frontier_cap, m), jnp.int32).at[0].set(c0)
    hi0, lo0 = config_hash(c0)
    if dedup == "hash":
        table, _, _ = insert_unique(
            make_table(visited_cap), hi0[None], lo0[None],
            jnp.ones((1,), bool), jnp.zeros((1,), jnp.int32))
        vhi, vlo, vpay = table.slots_hi, table.slots_lo, table.slot_payload
    else:
        vhi = jnp.full((visited_cap,), SENTINEL, jnp.uint32).at[0].set(hi0)
        vlo = jnp.full((visited_cap,), SENTINEL, jnp.uint32).at[0].set(lo0)
        vpay = jnp.zeros((0,), jnp.int32)
    archive = jnp.zeros((visited_cap, m), jnp.int32).at[0].set(c0)
    false = jnp.asarray(False)
    return ExploreState(
        frontier=frontier, frontier_n=jnp.asarray(1, jnp.int32),
        visited_hi=vhi, visited_lo=vlo, visited_payload=vpay,
        visited_n=jnp.asarray(1, jnp.int32),
        archive=archive, archive_n=jnp.asarray(1, jnp.int32),
        step=jnp.asarray(0, jnp.int32),
        branch_overflow=false, frontier_overflow=false, visited_overflow=false,
    )


def _sort_dedup_verdict(state: ExploreState, hi, lo, cand_valid, V: int):
    """Historical sort-based dedup: visited entries and candidates in one
    keyspace, one ``lax.sort`` per wave — ``O((V+K)·log(V+K))``.  Returns
    the per-candidate new-mask (first occurrence of an unseen hash)."""
    K = hi.shape[0]
    all_hi = jnp.concatenate([state.visited_hi, hi])
    all_lo = jnp.concatenate([state.visited_lo, lo])
    # candidates carry their index as payload; visited carry K (dropped).
    payload = jnp.concatenate(
        [jnp.full((V,), K, jnp.int32), jnp.arange(K, dtype=jnp.int32)]
    )
    is_cand = jnp.concatenate(
        [jnp.zeros((V,), jnp.int32), cand_valid.astype(jnp.int32)]
    )
    # Keys: (hi, lo, 1-is_cand ... ) — visited first within equal hashes so a
    # candidate equal to a visited entry sees eq_prev=True.  Sorting
    # (hi, lo, ~cand) keeps visited (0) ahead of candidates (1).
    s_hi, s_lo, s_cand, s_payload = jax.lax.sort(
        (all_hi, all_lo, is_cand, payload), num_keys=3
    )
    eq_prev = jnp.concatenate([
        jnp.zeros((1,), bool),
        (s_hi[1:] == s_hi[:-1]) & (s_lo[1:] == s_lo[:-1]),
    ])
    new_sorted = (s_cand == 1) & ~eq_prev
    # scatter back to candidate order (payload == K for visited -> dropped)
    return jnp.zeros((K,), bool).at[s_payload].set(new_sorted, mode="drop")


def _explore_step(state: ExploreState, comp: CompiledAny,
                  max_branches: int, backend,
                  dedup: str = "hash") -> ExploreState:
    """One BFS level: expand, hash, dedup, compact.  Traceable; the body of
    the on-device while_loop in :func:`_explore_loop`.

    ``dedup="hash"`` (default) resolves the wave against the
    device-resident open-addressing table in ``O(K·probe)`` gathers —
    lookup (no writes), intra-wave first-occurrence on a scratch table,
    then insertion of only the ``n_ins`` selected candidates, so excess
    discoveries beyond the frontier cap are *not* marked visited and
    regenerate later, exactly like the sorted path.  ``dedup="sort"``
    keeps the historical full-sort (the bench baseline).  Both produce
    bit-identical archives outside the visited-overflow regime (where the
    drop *policy* differs: sorted merge drops the largest hashes, the
    table drops probe-bound losers — both sound, both flagged)."""
    F, m = state.frontier.shape
    V = state.archive.shape[0]
    T = max_branches

    live = jnp.arange(F) < state.frontier_n
    out = backend.expand(state.frontier, comp, T)

    cand = out.configs.reshape(F * T, m)
    cand_valid = (out.valid & live[:, None]).reshape(F * T)
    branch_ovf = jnp.any(out.overflow & live)

    hi, lo = config_hash(cand)
    hi = jnp.where(cand_valid, hi, SENTINEL)
    lo = jnp.where(cand_valid, lo, SENTINEL)

    probe_ovf = jnp.asarray(False)
    if dedup == "hash":
        table = HashTable(state.visited_hi, state.visited_lo,
                          state.visited_payload, state.visited_n)
        found, _ = lookup(table, hi, lo, cand_valid)
        first, ovf_f = first_occurrence(hi, lo, cand_valid)
        new_mask = cand_valid & first & ~found
        probe_ovf = ovf_f
    else:
        new_mask = _sort_dedup_verdict(state, hi, lo, cand_valid, V)

    n_new = jnp.sum(new_mask, dtype=jnp.int32)
    # new candidates first (stable), then everything else
    order = jnp.argsort(jnp.logical_not(new_mask), stable=True)
    n_ins = jnp.minimum(n_new, F)  # only these become frontier AND visited
    take = jnp.arange(F)
    sel = order[:F]
    next_frontier = cand[sel]
    ins_mask = take < n_ins

    if dedup == "hash":
        # --- table insert of the selected prefix only (payload = archive row)
        table, _, ovf_i = insert_unique(
            table, hi[sel], lo[sel], ins_mask,
            (state.archive_n + take).astype(jnp.int32))
        probe_ovf = probe_ovf | ovf_i
        m_hi, m_lo, m_pay = table.slots_hi, table.slots_lo, table.slot_payload
        visited_n = table.count
        visited_ovf = (state.visited_overflow | probe_ovf
                       | (state.visited_n + n_ins > V))
    else:
        # --- visited merge (entries beyond capacity fall off the sorted tail)
        ins_hi = jnp.where(ins_mask, hi[sel], SENTINEL)
        ins_lo = jnp.where(ins_mask, lo[sel], SENTINEL)
        m_hi, m_lo = jax.lax.sort(
            (jnp.concatenate([state.visited_hi, ins_hi]),
             jnp.concatenate([state.visited_lo, ins_lo])),
            num_keys=2,
        )
        m_hi, m_lo = m_hi[:V], m_lo[:V]
        m_pay = state.visited_payload
        visited_n = jnp.minimum(state.visited_n + n_ins, V)
        visited_ovf = state.visited_overflow | (state.visited_n + n_ins > V)

    # --- archive append in discovery order
    arch_idx = jnp.where(ins_mask, state.archive_n + take, V)
    archive = state.archive.at[arch_idx].set(next_frontier, mode="drop")
    archive_n = jnp.minimum(state.archive_n + n_ins, V)

    return ExploreState(
        frontier=next_frontier,
        frontier_n=n_ins,
        visited_hi=m_hi, visited_lo=m_lo, visited_payload=m_pay,
        visited_n=visited_n,
        archive=archive, archive_n=archive_n,
        step=state.step + 1,
        branch_overflow=state.branch_overflow | branch_ovf,
        frontier_overflow=state.frontier_overflow | (n_new > F),
        visited_overflow=visited_ovf,
    )


@functools.partial(
    jax.jit, static_argnames=("max_steps", "max_branches", "backend", "dedup"))
def _explore_loop(state: ExploreState, comp: CompiledAny, max_steps: int,
                  max_branches: int, backend,
                  dedup: str = "hash") -> ExploreState:
    """Entire BFS as one on-device ``lax.while_loop``: runs until the
    frontier drains or ``max_steps`` levels, with zero host round-trips."""

    def cond(s: ExploreState):
        return (s.step < max_steps) & (s.frontier_n > 0)

    def body(s: ExploreState):
        return _explore_step(s, comp, max_branches, backend, dedup)

    return jax.lax.while_loop(cond, body, state)


def _explore_chunked(comp, be, state: ExploreState, *, max_steps: int,
                     max_branches: int, checkpoint_dir: Optional[str],
                     checkpoint_every: int, fault_injector,
                     dedup: str = "hash") -> ExploreState:
    """Drive :func:`_explore_loop` with optional checkpoint/resume.

    Without a ``checkpoint_dir`` this is the historical single
    ``_explore_loop`` call.  With one, the BFS runs in chunks of
    ``checkpoint_every`` levels, snapshotting the full
    :class:`ExploreState` (frontier, visited hashes, archive, overflow
    flags) via the atomic-rename checkpoint machinery between device
    loops; on entry the latest complete snapshot is restored.  The loop
    condition uses the *absolute* step, so chunked runs are bit-identical
    to an uninterrupted one, and a run killed mid-chunk resumes from its
    last snapshot and re-executes only that chunk (recovery by
    re-execution — free by determinism).  ``fault_injector`` (a
    :class:`repro.runtime.faults.FaultInjector`) is consulted before
    every device loop, so tests can kill any chunk deterministically.
    """
    if checkpoint_dir is None:
        if fault_injector is not None:
            fault_injector.on_device_call()
        return _explore_loop(state, comp, max_steps, max_branches, be, dedup)
    from repro.checkpoint.checkpoint import (latest_step, restore_checkpoint,
                                             save_checkpoint)
    if checkpoint_every < 1:
        raise ValueError("checkpoint_every must be >= 1")
    if latest_step(checkpoint_dir) is not None:
        host = jax.tree.map(np.asarray, state)
        restored, _, _ = restore_checkpoint(checkpoint_dir, host)
        state = ExploreState(*(jnp.asarray(x) for x in restored))
    while True:
        step, fn = (int(x) for x in
                    jax.device_get((state.step, state.frontier_n)))
        if not (step < max_steps and fn > 0):
            return state
        if fault_injector is not None:
            fault_injector.on_device_call()
        bound = min(max_steps, step + checkpoint_every)
        state = _explore_loop(state, comp, bound, max_branches, be, dedup)
        save_checkpoint(checkpoint_dir, int(state.step),
                        jax.tree.map(np.asarray, state))


def resolve_dedup(dedup: str, *, frontier_cap: int, visited_cap: int,
                  max_branches: int) -> str:
    """Resolve ``"auto"`` to a concrete dedup scheme for this workload
    shape (both schemes produce bit-identical archives outside
    visited-overflow, so this only moves wall-time).

    The sorted path re-sorts the full capacity-``V`` archive beside the
    wave every level — its cost grows with ``visited_cap`` even when few
    configurations are visited — while the hash table's probe loops cost
    roughly a flat per-wave amount on top of ``O(K·probe)`` work
    (``K = frontier_cap · max_branches``).  Measured on CPU the table
    overtakes the sort once the visited capacity clears ~16k entries and
    dominates the wave (EXPERIMENTS.md §Explore); below that the sort's
    three fused ops beat the table's dispatch-bound probe loops."""
    if dedup == "auto":
        wave = frontier_cap * max_branches
        return "hash" if visited_cap >= max(16384, 8 * wave) else "sort"
    if dedup not in ("hash", "sort"):
        raise ValueError(f"unknown dedup mode {dedup!r}")
    return dedup


def explore(
    system: SNPSystem | CompiledAny,
    *,
    max_steps: int = 64,
    frontier_cap: int = 256,
    visited_cap: int = 4096,
    max_branches: int = 64,
    init: Optional[Sequence[int]] = None,
    backend: Optional[BackendLike] = None,
    plan: Optional[SystemPlan] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 32,
    fault_injector=None,
    dedup: str = "auto",
) -> ExploreResult:
    """BFS-explore the computation tree (paper Algorithm 1).

    Stops when the frontier drains (both paper stopping criteria are
    subsumed: dead configs — including the zero vector — produce no
    successors, and already-seen configs are never re-inserted) or after
    ``max_steps`` levels.  The loop is a single device-side
    ``lax.while_loop``; the host sees only the final state.

    ``backend`` selects the transition implementation (``"ref"``,
    ``"pallas"``, ``"sparse"``, ``"sparse_pallas"``, or any registered
    :class:`~repro.core.backend.StepBackend` instance); an ``SNPSystem`` is
    lowered by the backend's own ``compile``; the archive is identical
    across backends.  ``backend=None`` (the default) hands the choice to
    the query planner: the default ``SystemPlan(mode="auto")`` picks the
    fastest known backend/encoding/block configuration for this workload
    shape (autotune cache → cost model → heuristic — DESIGN.md §3
    "Planner & autotuner"); pre-compiled inputs keep their historical
    backend (``"ref"`` dense, ``"sparse"`` for sparse encodings).

    ``plan`` (:class:`~repro.core.plan.SystemPlan`) tunes the storage
    layout the backend lowers to (e.g. ``encoding="hybrid"`` for
    heavy-tailed graphs) and the planning mode; the default plan is
    bit-identical to passing none (all backends agree on valid entries).

    ``checkpoint_dir`` enables checkpoint/resume: the BFS snapshots its
    full device state every ``checkpoint_every`` levels (atomic rename,
    content-verified — :mod:`repro.checkpoint`) and restores the latest
    snapshot on entry, so a killed run re-invoked with the same arguments
    — e.g. under :func:`repro.runtime.faults.run_supervised` — resumes
    bit-identically instead of starting over.  The capacities must match
    the checkpointed run's (a mismatch raises at restore).
    ``fault_injector`` deterministically kills scheduled device loops for
    tests and the fault bench tier.

    A planner-picked backend (``backend=None`` auto path) that fails at
    compile, lower, or run time degrades down the encoding-compatible
    chain (:mod:`repro.core.failover`) with a warning — a backend the
    caller *named* raises instead.

    ``dedup`` selects the visited-set structure: ``"hash"`` keeps a
    device-resident open-addressing table — ``O(K·probe)`` per wave
    regardless of visited size — while ``"sort"`` is the historical
    full-sort path, ``O((V+K)·log(V+K))`` per wave (kept as the bench
    baseline and a differential-testing oracle).  ``"auto"`` (default)
    applies :func:`resolve_dedup`: the sort's per-wave cost scales with
    the visited *capacity* while the table's is roughly flat, so the
    table wins once ``visited_cap`` dominates the wave size
    ``frontier_cap · max_branches`` (measured crossover — EXPERIMENTS.md
    §Explore) and the sort keeps small/wave-dominated workloads.
    Archives are bit-identical between the two outside visited-overflow
    (see :func:`_explore_step`).

    The call is one host span ``snp.explore`` on the profiler's clock
    (``jax.profiler.TraceAnnotation``), with its phases nested inside:
    ``snp.plan`` (backend and plan resolution), ``snp.lower`` (the
    backend's compile or ``lower``), ``snp.explore.init`` (the initial
    state's dispatch), ``snp.explore.wait`` (the device loop until done,
    no transfer) and ``snp.explore.readback`` (the archive's transfer and
    the result).  With no profiler session a span costs about a
    microsecond; under ``jax.profiler.trace(dir)`` they land in the
    trace beside the device's operations (TensorBoard or Perfetto).
    """
    dedup = resolve_dedup(dedup, frontier_cap=frontier_cap,
                          visited_cap=visited_cap, max_branches=max_branches)
    with TraceAnnotation("snp.explore"):
        # Branch work per step is bounded by frontier_cap × max_branches.
        be, plan, planned = resolve_entry_info(
            system, backend, plan, workload=(frontier_cap, max_branches))
        if plan is not None and plan.num_shards > 1:
            # caller error: raise, don't degrade
            _resolve_comp(system, be, plan)
        init_arr = None if init is None else jnp.asarray(init, jnp.int32)

        def attempt(be, plan):
            comp = _resolve_comp(system, be, plan)
            with TraceAnnotation("snp.explore.init"):
                state = _init_state(comp, frontier_cap, visited_cap,
                                    init_arr, dedup)
            return _explore_chunked(
                comp, be, state, max_steps=max_steps,
                max_branches=max_branches, checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
                fault_injector=fault_injector, dedup=dedup)

        state = run_with_failover(attempt, be, plan, degradable=planned)
        # the device loop's end, apart from the transfer that follows
        with TraceAnnotation("snp.explore.wait"):
            jax.block_until_ready(state)
        # single host sync: one explicit device_get of the final state (the
        # explicit form keeps the whole call legal under a d2h transfer guard)
        with TraceAnnotation("snp.explore.readback"):
            arch, n, fn, step, b_ovf, f_ovf, v_ovf = jax.device_get(
                (state.archive, state.archive_n, state.frontier_n,
                 state.step, state.branch_overflow, state.frontier_overflow,
                 state.visited_overflow))
            n = int(n)
            ovf = (bool(b_ovf), bool(f_ovf), bool(v_ovf))
            return ExploreResult(
                configs=arch[:n],
                num_discovered=n,
                steps=int(step),
                exhausted=int(fn) == 0 and not any(ovf),
                branch_overflow=ovf[0],
                frontier_overflow=ovf[1],
                visited_overflow=ovf[2],
            )


# ---------------------------------------------------------------------------
# Small-system utilities (host-driven, used by tests & the paper repro)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("max_branches", "backend"))
def _succ_one(config, comp, max_branches, backend):
    out = backend.expand(config, comp, max_branches)
    return out.configs, out.valid, out.emissions, out.overflow


def successor_set(
    system: SNPSystem | CompiledAny, config: Sequence[int],
    max_branches: int = 64, backend: BackendLike = "ref",
    plan: Optional[SystemPlan] = None,
) -> List[Tuple[Tuple[int, ...], int]]:
    """Distinct (successor, emission) pairs of one configuration."""
    be = get_backend(backend)
    comp = _resolve_comp(system, be, plan)
    c = jnp.asarray(config, jnp.int32)
    cfgs, valid, emis, ovf = _succ_one(c, comp, max_branches, be)
    if bool(ovf):
        raise ValueError("branch overflow; raise max_branches")
    seen, out = set(), []
    for i in np.nonzero(np.asarray(valid))[0]:
        key = (tuple(int(v) for v in cfgs[i]), int(emis[i]))
        if key not in seen:
            seen.add(key)
            out.append(key)
    return out


def emission_gaps(
    comp: SNPSystem | CompiledAny, *, max_time: int, max_gap: int,
    max_branches: int = 64, backend: BackendLike = "ref",
) -> set[int]:
    """All gaps between the first two environment emissions, over every
    computation path of length <= ``max_time``.

    The number computed by an SNP generator is exactly this gap (paper §2.1);
    for the paper's Π in exact mode the result must be {2, 3, ...} ∩ bound.
    BFS over *augmented* states (config, elapsed-since-first-emission) keeps
    the search polynomial even though the path count is exponential.
    """
    comp = comp if is_compiled(comp) else get_backend(backend).compile(comp)
    # phase A: no emission yet; phase B: (config, elapsed) since 1st emission
    init = tuple(int(v) for v in np.asarray(comp.init_config))
    phase_a: set = {init}
    phase_b: set = set()
    gaps: set[int] = set()
    for _ in range(max_time):
        new_a: set = set()
        new_b: set = set()
        for cfg in phase_a:
            for nxt, emis in successor_set(comp, cfg, max_branches, backend):
                if emis > 0:
                    new_b.add((nxt, 0))
                else:
                    new_a.add(nxt)
        for cfg, elapsed in phase_b:
            if elapsed + 1 > max_gap:
                continue
            for nxt, emis in successor_set(comp, cfg, max_branches, backend):
                if emis > 0:
                    gaps.add(elapsed + 1)
                else:
                    new_b.add((nxt, elapsed + 1))
        phase_a, phase_b = new_a, new_b
        if not phase_a and not phase_b:
            break
    return gaps


# ---------------------------------------------------------------------------
# Trace serving: the batched scan and its single-path wrapper.  The batched
# path (`run_traces`) is the serving primitive; `run_trace` is a B=1 view of
# it, and `core.distributed.run_traces_distributed` shards its batch axis
# over a mesh (both bit-identical by per-trace PRNG keys).
# ---------------------------------------------------------------------------


class TraceOut(NamedTuple):
    """:func:`run_traces` output — a NamedTuple, so both field access and
    4-way unpacking work.  ``branch_overflow[b, t]`` flags that trace b
    had more than ``max_branches`` successors at step t (only the first T
    were candidates): truncated branching is reported, never silent.  The
    serving layer surfaces it as ``TraceResult.branch_overflow`` and a
    service counter."""

    configs: jnp.ndarray          # (B, steps, m) int32
    emissions: jnp.ndarray        # (B, steps) int32
    alive: jnp.ndarray            # (B, steps) bool
    branch_overflow: jnp.ndarray  # (B, steps) bool


def successors_per_step(backend, max_branches: int) -> int:
    """Successors a trace step builds per trace: 1 where the backend has
    ``step_chosen`` (choose, then step once), ``max_branches`` where the
    scan expands every candidate and keeps one."""
    return 1 if hasattr(backend, "step_chosen") else max_branches


def _rule_slots(comp: CompiledAny) -> int:
    """``R``, the most rules one neuron holds."""
    if hasattr(comp, "rule_slots"):
        return int(comp.rule_slots.shape[0])
    return int(np.bincount(np.asarray(comp.rule_neuron)).max())


def trace_wait_args(backend, comp: CompiledAny, max_branches: int) -> dict:
    """Arguments of the ``snp.traces.wait`` span: the successors a
    trace-step builds per trace, how it finds each neuron's fired rule
    (``"rules"`` or ``"table"``, :func:`~repro.core.semantics.fired_lookup`)
    and the rule slots ``R``."""
    successors = successors_per_step(backend, max_branches)
    return dict(successors=successors,
                fired=fired_lookup(comp, successors),
                rule_slots=_rule_slots(comp))


def trace_inits(init, batch: int, width: int) -> Optional[jnp.ndarray]:
    """``init`` as int32 rows for a batch of ``batch`` traces of state
    width ``width``: ``None`` stays ``None`` (the system's initial
    configuration), ``(width,)`` is every trace's start, ``(batch,
    width)`` one start per trace; any other shape raises."""
    if init is None:
        return None
    # asarray first: numpy rows become int32 on the host, where
    # asarray(init, int32) compiles a device convert for every new shape
    init = jnp.asarray(init).astype(jnp.int32)
    if init.shape not in ((width,), (batch, width)):
        raise ValueError(
            f"init must be ({width},) or ({batch}, {width}) for {batch} "
            f"traces of {width} columns, got shape {init.shape}")
    return init


def _state_width(system, plan: Optional[SystemPlan]) -> int:
    """Columns of one configuration row of ``system`` as ``plan`` lowers
    it: ``m``, or ``3m`` under the delayed semantics."""
    if is_compiled(system):
        return system.state_width
    delayed = getattr(plan, "semantics", "no_delays") == "delays"
    return system.num_neurons * (3 if delayed else 1)


@functools.partial(
    jax.jit, static_argnames=("steps", "max_branches", "policy", "backend"))
def _traces_scan(comp, keys, init=None, *, steps, max_branches, policy,
                 backend):
    """B independent trajectories, one ``lax.scan`` over time.

    ``keys`` (B, 2) — per-trace PRNG streams, split exactly as the
    single-trace path splits its key, so trace b depends only on
    ``keys[b]`` and batching never changes a trajectory.  ``init`` is
    ``None`` (every trace starts at ``comp.init_config``), one ``(m,)``
    start for every trace, or ``(B, m)`` one per trace (checked by
    :func:`trace_inits`).

    The branch index depends only on the branch count and the key, so a
    backend with ``step_chosen`` draws it first and builds one successor
    per trace; any other backend expands all ``T`` candidates and keeps
    the one at that index.  Both give the same trajectories.
    """
    B = keys.shape[0]
    c0s = jnp.broadcast_to(comp.init_config if init is None else init,
                           (B,) + comp.init_config.shape)

    def body(carry, _):
        cfgs, keys = carry
        if policy == "random":
            pair = jax.vmap(jax.random.split)(keys)        # (B, 2, 2)
            keys, subs = pair[:, 0], pair[:, 1]

            def choose(n_valid):
                return jax.vmap(
                    lambda k, n: jax.random.randint(
                        k, (), 0, jnp.maximum(n, 1)))(subs, n_valid)
        else:
            def choose(n_valid):
                return jnp.zeros((B,), jnp.int32)
        if hasattr(backend, "step_chosen"):
            out = backend.step_chosen(cfgs, comp, max_branches, choose)
            n_valid, pick, picked_emis = out.n_valid, out.configs, \
                out.emissions
        else:
            out = backend.expand(cfgs, comp, max_branches)  # (B, T, m)
            n_valid = jnp.sum(out.valid, axis=-1, dtype=jnp.int32)  # (B,)
            idx = choose(n_valid)
            pick = jnp.take_along_axis(
                out.configs, idx[:, None, None], axis=1)[:, 0]  # (B, m)
            picked_emis = jnp.take_along_axis(
                out.emissions, idx[:, None], axis=1)[:, 0]
        has = n_valid > 0
        nxt = jnp.where(has[:, None], pick, cfgs)
        emis = jnp.where(has, picked_emis, 0)
        ovf = out.overflow & has
        return (nxt, keys), (nxt, emis, has, ovf)

    (_, _), (cfgs, emis, alive, ovf) = jax.lax.scan(
        body, (c0s, keys), None, length=steps)
    # scan stacks time first: (steps, B, ...) -> (B, steps, ...)
    return TraceOut(jnp.swapaxes(cfgs, 0, 1), jnp.swapaxes(emis, 0, 1),
                    jnp.swapaxes(alive, 0, 1), jnp.swapaxes(ovf, 0, 1))


def run_traces(
    system: SNPSystem | CompiledAny, *, steps: int,
    seeds: Sequence[int] | np.ndarray | jnp.ndarray,
    policy: str = "first", max_branches: int = 64,
    backend: Optional[BackendLike] = None,
    plan: Optional[SystemPlan] = None,
    init=None,
):
    """Batched trajectory serving: B independent paths in one jitted scan.

    Returns a :class:`TraceOut` — ``(configs (B, steps, m), emissions
    (B, steps), alive (B, steps), branch_overflow (B, steps))`` with
    ``B = len(seeds)``.  ``init`` is where the traces start: ``None`` (the
    system's initial configuration), one ``(m,)`` configuration for every
    trace, or ``(B, m)`` one per trace; any other shape raises.  Row b is
    bit-identical to ``run_trace(..., seed=seeds[b], init=init[b])`` with
    the same policy/backend — one transition per step for the whole batch,
    which is the serving-path hot loop.  Each step builds only the
    successor each trace keeps where the backend has ``step_chosen``
    (``"sparse"``), and all ``max_branches`` candidates through ``expand``
    otherwise; the ``snp.traces.wait`` span records which as
    ``successors`` per trace-step, with ``fired`` and ``rule_slots``
    (:func:`trace_wait_args`), and the ``snp.traces`` span the rows of
    ``init`` as ``init_rows`` (1, or B for one start per trace).
    ``backend=None`` (the default) hands the choice to the query planner
    under the default ``SystemPlan(mode="auto")`` — see :func:`explore`;
    traces are backend-independent, so the planner only moves wall-time,
    and a failing planner pick degrades down the chain
    (:mod:`repro.core.failover`) instead of raising.
    """
    if policy not in ("first", "random"):
        raise ValueError(f"unknown policy {policy!r}")
    seeds = jnp.asarray(seeds, jnp.uint32)
    if seeds.ndim != 1:
        raise ValueError(f"seeds must be 1-D, got shape {seeds.shape}")
    B = int(seeds.shape[0])
    init_rows = 1 if np.ndim(init) < 2 else np.shape(init)[0]
    with TraceAnnotation("snp.traces", batch=B, init_rows=init_rows):
        be, plan, planned = resolve_entry_info(
            system, backend, plan, workload=(B, max_branches))
        if plan is not None and plan.num_shards > 1:
            # caller error: raise, don't degrade
            _resolve_comp(system, be, plan)
        init = trace_inits(init, B, _state_width(system, plan))
        keys = jax.vmap(jax.random.PRNGKey)(seeds)             # (B, 2)

        def attempt(be, plan):
            comp = _resolve_comp(system, be, plan)
            # one (B, m) row per trace whatever ``init`` is: every form
            # runs the same compiled scan
            c0s = jnp.broadcast_to(
                comp.init_config if init is None else init,
                (B,) + comp.init_config.shape)
            out = _traces_scan(comp, keys, c0s, steps=steps,
                               max_branches=max_branches, policy=policy,
                               backend=be)
            with TraceAnnotation(
                    "snp.traces.wait",
                    **trace_wait_args(be, comp, max_branches)):
                # first-run failures degrade too
                jax.block_until_ready(out.configs)
            return out

        return run_with_failover(attempt, be, plan, degradable=planned)


def run_trace(
    system: SNPSystem | CompiledAny, *, steps: int,
    policy: str = "first", seed: int = 0, max_branches: int = 64,
    backend: Optional[BackendLike] = None,
    plan: Optional[SystemPlan] = None,
    init=None,
):
    """Single-path simulation (deterministic or uniformly random branch).

    Returns a :class:`TraceOut` of (configs (steps, m), emissions
    (steps,), alive (steps,), branch_overflow (steps,)), from ``init``
    (an ``(m,)`` configuration; ``None``: the system's initial one).
    The 'serving' mode of the engine: one trajectory, spike train out.
    Implemented as a B=1 :func:`run_traces` batch, so the single- and
    batched-serving paths can never drift apart.
    """
    if init is not None and np.ndim(init) != 1:
        raise ValueError(f"init of one trace must be 1-D, got shape "
                         f"{np.shape(init)}")
    out = run_traces(
        system, steps=steps, seeds=[seed], policy=policy,
        max_branches=max_branches, backend=backend, plan=plan, init=init)
    return TraceOut(*(x[0] for x in out))
