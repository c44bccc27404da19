"""Pluggable step backends: one transition API behind every consumer.

The paper's contribution is that the SNP transition is a single
device-friendly primitive ``C' = C + S·M_Π`` (eq. 2).  Historically each
consumer (``engine.explore``, ``core.distributed``, ``run_trace``) called
the pure-jnp reference semantics directly, so alternative implementations
of the same primitive — the fused Pallas kernel today, a sparse/CSR
backend next (Hernández-Tello et al. 2024) — had no way into any real
workload.  This module is that seam:

* :class:`StepBackend` — the protocol: ``expand(configs, comp,
  max_branches) -> StepOut`` plus capability/padding metadata.  ``expand``
  must be pure and traceable (consumers call it inside ``jit``,
  ``lax.while_loop``, ``lax.scan`` and ``shard_map``), and all registered
  backends must agree bit-for-bit on the *valid* entries of
  :class:`~repro.core.semantics.StepOut` for spike counts < 2^24.
  A backend may also offer ``step_chosen``, one successor per row at an
  index the caller picks from the branch count; the trace scan uses it
  where it exists and picks from ``expand`` otherwise.
* :class:`RefBackend` (``"ref"``) — the pure-jnp oracle
  (:func:`~repro.core.semantics.next_configs`).
* :class:`PallasBackend` (``"pallas"``) — the fused TPU kernel
  (:func:`repro.kernels.snp_step.ops.snp_step`); compiled on a TPU and
  emulated (Pallas interpret mode) anywhere else — ``interpret=None``
  resolves from the platform at trace time
  (:func:`repro.core.plan.resolve_interpret`).  Does not materialize the
  spiking vectors, so ``StepOut.spiking`` is ``None``.
* :class:`SparseBackend` (``"sparse"``) — gather/segment-sum over the
  ELL/segment encoding (:class:`~repro.core.matrix.CompiledSparseSNP`);
  ``O(B·T·m·degree)`` work and memory, the scaling path for large systems
  (Hernández-Tello et al. 2024).
* :class:`SparsePallasBackend` (``"sparse_pallas"``) — the fused Pallas
  kernel over the same sparse encoding
  (:func:`repro.kernels.snp_step.sparse_ops.snp_step_sparse`).
* a name registry — :func:`register_backend` / :func:`get_backend` /
  :func:`available_backends` — so new backends land as plugins without
  touching the consumers.

Each backend also owns its *compilation*, driven by the **lowering
registry** (DESIGN.md §3 "Kernel lowering"): every backend declares
``supported_encodings()`` — the :class:`~repro.core.plan.SystemPlan`
encodings its step can realize, first entry = its native layout — and a
``lower(compiled, plan)`` hook that annotates a built encoding with
whatever its kernel consumes (e.g. ``PallasBackend`` attaches the dense
per-shard operands to a :class:`~repro.core.plan.ShardedCompiled`).
``backend.compile(system, plan=...)`` is then one shared template:
resolve the plan's encoding against the registry, build it through the
shared compilers (dense :class:`~repro.core.matrix.CompiledSNP`, ELL /
hybrid :class:`~repro.core.matrix.CompiledSparseSNP`, neuron-axis
:class:`~repro.core.plan.ShardedCompiled`), and hand it to ``lower``.
The **default plan is bit-identical** to each backend's historical
encoding, and a plan a backend cannot honor is a ``ValueError``, never a
silent reinterpretation or downgrade.  The same holds for a shape a
compiled kernel cannot run: the Pallas backends' ``compile_refusal``
names it, ``compile``/``lower`` raise it, and the planner never picks a
refused backend for that shape.  Consumers resolve backends by name
and call ``compile`` once, so a new encoding lights up every workload
with no consumer changes — and plan choice is orthogonal to backend
choice across the whole matrix.

Backends are frozen dataclasses: hashable, so they ride through
``jax.jit(..., static_argnames=("backend",))`` unchanged.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, Optional, Protocol, Tuple, Union, runtime_checkable

import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from .matrix import (CompiledAny, CompiledSNP, CompiledSparseSNP,
                     compile_system, compile_system_sparse, is_delayed)
from .plan import (KernelConfig, ShardedCompiled, SystemPlan,
                   compile_sharded, is_sharded, lower_shard_dense,
                   resolve_interpret)
from .semantics import (ChosenOut, StepOut, delayed_next_configs,
                        next_configs, sparse_chosen_config,
                        sparse_delayed_chosen_config,
                        sparse_delayed_next_configs, sparse_next_configs)
from .system import SNPSystem

__all__ = [
    "StepBackend",
    "RefBackend",
    "PallasBackend",
    "SparseBackend",
    "SparsePallasBackend",
    "register_backend",
    "get_backend",
    "available_backends",
    "compile_with_plan",
    "lower_with_backend",
    "resolve_entry",
    "resolve_entry_info",
    "resolve_kernel",
    "supported_under",
    "supports_sharded",
]


@runtime_checkable
class StepBackend(Protocol):
    """One synchronous SNP transition step, pluggable per workload.

    Implementations must be hashable (frozen dataclasses) so consumers can
    pass them as static jit arguments, and ``expand`` must be traceable.

    Capability / padding metadata:

    * ``name``              — registry name (``backend="<name>"`` end-to-end).
    * ``supports_nd_batch`` — ``expand`` accepts arbitrary leading batch
      dims ``(..., m)``; backends that flatten internally still set True.
    * ``pad_multiple``      — batch sizes are padded internally to a
      multiple of this (1 = no padding); callers sizing frontiers/batches
      can round to it to avoid wasted lanes.
    * ``materializes_spiking`` — whether ``StepOut.spiking`` is populated
      (``None`` otherwise).

    Optional method (not declared below, so ``isinstance`` does not demand
    it): ``step_chosen(configs (B, m), comp, max_branches, choose) ->``
    :class:`~repro.core.semantics.ChosenOut` — one successor per row
    instead of all ``T``.  ``n_valid`` (B,) int32 is
    ``sum(expand(...).valid, -1)``, ``choose(n_valid)`` returns one branch
    index per row, and ``configs[b]``/``emissions[b]`` must be
    bit-identical to ``expand``'s entries at that index (``overflow`` to
    its ``overflow``).  The trace scan takes this path wherever the
    backend has the method and picks from ``expand`` otherwise, so a
    failover onto a backend without it changes no trace.  Exploration
    always calls ``expand``: its frontier is the whole candidate set.
    """

    name: str
    supports_nd_batch: bool
    pad_multiple: int
    materializes_spiking: bool

    def compile(self, system: SNPSystem,
                plan: Optional[SystemPlan] = None) -> CompiledAny:
        """Lower ``system`` to the encoding this backend's ``expand``
        consumes.  The contract every implementation must honor:

        * **host-side, not traceable** — runs numpy/Python freely; never
          called inside ``jit``/``scan``/``shard_map``.
        * **returns a compiled encoding** — an object for which
          :func:`repro.core.matrix.is_compiled` is True, and whose arrays
          form a jax pytree (consumers pass it through ``jit`` and
          ``shard_map`` as data, replicated ``P()`` on meshes).
        * **deterministic** — structurally equal systems (``SNPSystem`` is
          a frozen dataclass) must lower to semantically identical
          encodings.  Consumers rely on this to memoize: every entry point
          compiles at most once per call, and
          :class:`~repro.serve.snp_service.SNPTraceService` keeps a
          FIFO-bounded ``{system: compiled}`` cache keyed by structural
          equality, so ``compile`` may be arbitrarily expensive but must
          not be stateful.
        * **owns the encoding choice** — dense vs. sparse is invisible to
          consumers; ``expand`` must reject a foreign encoding with
          ``TypeError`` (see ``_require_sparse``) rather than
          mis-interpret it.  Pre-compiled objects passed by callers skip
          ``compile`` entirely, so the check lives in ``expand``.
        * **honors the plan or refuses it** — ``plan=None`` (or the
          default :class:`~repro.core.plan.SystemPlan`) must produce the
          backend's historical encoding **bit-identically**; an encoding
          request the backend cannot realize (``supported_encodings``)
          raises ``ValueError``; ``plan.num_shards > 1`` lowers through
          :func:`repro.core.plan.compile_sharded` where ``"sharded"`` is
          supported and raises elsewhere.
        """
        ...

    def supported_encodings(self,
                            semantics: str = "no_delays"
                            ) -> Tuple[str, ...]:
        """Plan encodings this backend's lowering can realize *under the
        given semantics tier* — a subset of ``("dense", "ell", "hybrid",
        "sharded")``, **first entry = the native layout**
        ``encoding="auto"`` resolves to.  ``"sharded"`` additionally marks
        that the backend's step can consume one shard of a
        :class:`~repro.core.plan.ShardedCompiled` inside
        ``explore_distributed``.  An empty tuple means the backend cannot
        run that semantics at all; the built-ins all run
        ``semantics="delays"`` single-device but none shard it yet, so
        a sharded delays plan raises (never a silent downgrade)."""
        ...

    def lower(self, compiled: "CompiledLike",
              plan: SystemPlan) -> "CompiledLike":
        """Annotate a built encoding with whatever this backend's step
        consumes (host-side, deterministic, idempotent — same contract as
        ``compile``, whose template calls it last).  Also invoked by
        consumers on *pre-compiled* objects, so a backend can refuse an
        encoding its kernel cannot lower (``ValueError``) instead of
        silently downgrading at expand time.  The default is identity."""
        ...

    def expand(self, configs: jnp.ndarray, comp: CompiledAny,
               max_branches: int) -> StepOut:
        """All successors of ``configs`` (..., m): a :class:`StepOut` with
        ``configs`` (..., T, m), ``valid``/``emissions`` (..., T) and
        ``overflow`` (...,)."""
        ...


CompiledLike = Union[CompiledAny, ShardedCompiled]


def _require_sparse(comp, backend_name: str) -> CompiledSparseSNP:
    if not isinstance(comp, CompiledSparseSNP):
        raise TypeError(
            f"backend {backend_name!r} needs a CompiledSparseSNP "
            "(use compile_system_sparse / backend.compile), got "
            f"{type(comp).__name__}")
    return comp


def _plan_or_default(plan: Optional[SystemPlan]) -> SystemPlan:
    return SystemPlan() if plan is None else plan


def _kernel_shape(compiled: "CompiledLike") -> Tuple[int, int, str, int]:
    """``(m, n, semantics, halo width)`` a fused kernel sees for an
    encoding: one shard's slice and halo under a neuron-axis partition."""
    if is_sharded(compiled):
        return (compiled.shard_size, compiled.arrays.rule_neuron.shape[1],
                "no_delays", compiled.num_shards * compiled.halo_width)
    return (compiled.num_neurons, compiled.num_rules,
            "delays" if is_delayed(compiled) else "no_delays", 0)


def _refuse_uncompilable(backend: "StepBackend", m: int, n: int,
                         semantics: str = "no_delays", halo: int = 0) -> None:
    """Raise the backend's ``compile_refusal`` for this shape, if any, as
    a ``ValueError`` — at lower time, before anything reaches the TPU
    compiler (backends without the hook never refuse)."""
    refusal = getattr(backend, "compile_refusal", None)
    why = refusal(m, n, semantics=semantics, halo=halo) if refusal else None
    if why:
        raise ValueError(
            f"backend {backend.name!r} cannot compile its kernel at m={m}, "
            f"n={n} ({semantics}): {why}; pick another backend")


def supported_under(backend: "StepBackend", semantics: str
                    ) -> Tuple[str, ...]:
    """``backend.supported_encodings`` under a semantics tier, tolerating
    third-party backends that predate the semantics parameter: those keep
    answering for ``no_delays`` and are declared incapable (empty tuple)
    of anything else."""
    sup_fn = getattr(backend, "supported_encodings", None)
    if sup_fn is None:
        return ()
    try:
        return sup_fn(semantics=semantics)
    except TypeError:
        return sup_fn() if semantics == "no_delays" else ()


def _registry_compile(backend: "StepBackend", system: SNPSystem,
                      plan: Optional[SystemPlan]) -> CompiledLike:
    """The shared ``compile`` template every registered backend delegates
    to: resolve the plan's encoding against ``supported_encodings()``
    under the plan's semantics tier, build it through the shared
    compilers, hand it to ``lower``."""
    plan = _plan_or_default(plan)
    sup = backend.supported_encodings(semantics=plan.semantics)
    if plan.num_shards > 1:
        # Sharded plans lower to per-shard ELL encodings for every
        # backend (DESIGN.md §2); compile_sharded owns the encoding
        # validation there (it refuses hybrid/dense), so only the
        # 'sharded' capability is the backend's to declare.
        if "sharded" not in sup:
            raise ValueError(
                f"backend {backend.name!r} cannot realize a neuron-axis "
                f"sharded plan under semantics={plan.semantics!r} "
                f"(supported encodings: {sup}); pick a backend whose "
                "lowering supports 'sharded' there")
        return backend.lower(compile_sharded(system, plan), plan)
    # Refuse before building: a dense encoding of a wide system is large.
    _refuse_uncompilable(backend, system.num_neurons, system.num_rules,
                         plan.semantics)
    enc = sup[0] if plan.encoding == "auto" else plan.encoding
    if enc not in sup:
        raise ValueError(
            f"backend {backend.name!r} cannot realize plan encoding "
            f"{plan.encoding!r} under semantics={plan.semantics!r} "
            f"(supported: {sup}); pick a matching backend or drop the "
            "plan")
    if enc == "dense":
        built = compile_system(system, semantics=plan.semantics)
    else:
        built = compile_system_sparse(
            system, hub_threshold=plan.resolved_hub_threshold(system),
            semantics=plan.semantics)
    return backend.lower(built, plan)


def compile_with_plan(backend: "StepBackend", system: SNPSystem,
                      plan: Optional[SystemPlan]) -> CompiledAny:
    """``backend.compile`` with an optional plan, tolerating third-party
    backends that predate the plan parameter (they only ever see the
    default plan, which is the identity — the entry points always carry a
    plan now, so the identity check matters, not just ``None``)."""
    with TraceAnnotation("snp.lower"):
        if plan is None or plan == SystemPlan():
            return backend.compile(system)
        return backend.compile(system, plan=plan)


def lower_with_backend(backend: "StepBackend", compiled: CompiledLike,
                       plan: Optional[SystemPlan]) -> CompiledLike:
    """``backend.lower`` on a pre-compiled encoding, tolerating
    third-party backends that predate the lowering registry (identity)."""
    lower = getattr(backend, "lower", None)
    if lower is None:
        return compiled
    with TraceAnnotation("snp.lower"):
        return lower(compiled, _plan_or_default(plan))


def _check_kernel_plan(backend: "StepBackend", plan: SystemPlan) -> None:
    """Lower-time validation of ``plan.kernel`` against the backend it
    landed on — a block shape a backend cannot honor is a ``ValueError``
    with a real message, never a silently ignored field."""
    cfg = plan.kernel
    if cfg is None:
        return
    if not hasattr(backend, "block_b"):
        raise ValueError(
            f"backend {backend.name!r} has no kernel block parameters; "
            f"drop SystemPlan.kernel={cfg} or pick a Pallas-kernel "
            "backend ('pallas', 'sparse_pallas')")
    if cfg.block_n is not None and not hasattr(backend, "block_n"):
        raise ValueError(
            f"plan kernel sets block_n={cfg.block_n}, but backend "
            f"{backend.name!r} keeps the whole neuron axis resident per "
            "block (no rule-axis tiling); drop block_n — only the dense "
            "'pallas' lowering tiles that axis")


def resolve_kernel(backend: "StepBackend",
                   plan: Optional[SystemPlan]) -> "StepBackend":
    """Fold ``plan.kernel`` into ``backend``: a new (frozen, hashable)
    instance carrying the plan's block shape, so every downstream cache
    keyed on the backend — jit static args, ``distributed``'s lru-cached
    shard functions — keys on the block configuration automatically.
    Identity when the plan carries no kernel config; ``ValueError`` when
    the backend cannot honor it (:func:`_check_kernel_plan`).  The
    per-axis ``None`` fields keep the backend's own defaults, so the same
    compiled encoding re-lowers at different block shapes without
    rebuilding."""
    plan = _plan_or_default(plan)
    cfg = plan.kernel
    if cfg is None:
        return backend
    _check_kernel_plan(backend, plan)
    fields = {f: v for f in ("block_b", "block_t", "block_n")
              if (v := getattr(cfg, f)) is not None and hasattr(backend, f)}
    return dataclasses.replace(backend, **fields) if fields else backend


def resolve_entry_info(system, backend: Optional["BackendLike"],
                       plan: Optional[SystemPlan], *,
                       workload: Optional[Tuple[int, int]] = None,
                       ) -> Tuple["StepBackend", SystemPlan, bool]:
    """:func:`resolve_entry` plus *who chose*: the third element is True
    exactly when the query planner picked the backend (so a failure may
    gracefully degrade down :data:`repro.core.failover.DEGRADE_ORDER`)
    and False when the caller pinned it by name or plan (pinning is a
    contract — a pinned backend's failure raises)."""
    with TraceAnnotation("snp.plan"):
        plan = _plan_or_default(plan)
        planned = False
        if backend is None:
            if (plan.backend is None and plan.mode in ("auto", "measure")
                    and plan.encoding == "auto" and plan.kernel is None
                    and isinstance(system, SNPSystem)):
                plan = SystemPlan.for_system(
                    system, num_shards=plan.num_shards, workload=workload,
                    mode=plan.mode, semantics=plan.semantics)
                planned = True
            name = plan.backend
            if name is None:
                name = "sparse" if isinstance(system, CompiledSparseSNP) \
                    else "ref"
                planned = False
            be = get_backend(name)
        else:
            be = get_backend(backend)
        return resolve_kernel(be, plan), plan, planned


def resolve_entry(system, backend: Optional["BackendLike"],
                  plan: Optional[SystemPlan], *,
                  workload: Optional[Tuple[int, int]] = None,
                  ) -> Tuple["StepBackend", SystemPlan]:
    """Shared backend/plan resolution for the engine entry points
    (``explore``/``run_traces`` and the distributed pair).

    When the caller names no backend and leaves the plan open
    (``mode="auto"|"measure"``, no pinned backend/encoding/kernel), the
    query planner decides: ``SystemPlan.for_system`` consults the
    autotune cache, then the analytic cost model, then the static degree
    heuristic (DESIGN.md §3 "Planner & autotuner"), with ``workload=(B,
    T)`` the batch/branch shape the entry point is about to run.  A named
    backend, a pinned plan, or ``mode="static"`` bypasses planning and
    preserves the historical behavior (``"ref"`` for raw systems and
    dense/sharded compileds, ``"sparse"`` for sparse ones).  Either way
    the plan's kernel config is folded into the returned backend
    (:func:`resolve_kernel`)."""
    be, plan, _ = resolve_entry_info(system, backend, plan,
                                     workload=workload)
    return be, plan


def supports_sharded(backend: "StepBackend") -> bool:
    """Whether the backend may serve a neuron-axis-sharded run
    (registry-declared; third-party backends without the registry hooks
    default to no).  The built-in kernel backends step each shard through
    their own fused kernels; any other backend declaring ``"sharded"`` is
    served by the jnp sparse shard math, which every registered backend
    must match bit-for-bit anyway (see the ``expand`` contract)."""
    sup = getattr(backend, "supported_encodings", None)
    return sup is not None and "sharded" in sup()


@dataclass(frozen=True)
class RefBackend:
    """Pure-jnp reference semantics (the repo's oracle).  Under a sharded
    plan, ``explore_distributed`` runs the jnp sparse math on each shard's
    slice (DESIGN.md §2)."""

    name: str = "ref"
    supports_nd_batch: bool = True
    pad_multiple: int = 1
    materializes_spiking: bool = True

    def supported_encodings(self,
                            semantics: str = "no_delays"
                            ) -> Tuple[str, ...]:
        # Delays run single-device only: the halo exchange has no notion
        # of countdown/pending yet, so sharded delays must raise.
        return ("dense",) if semantics == "delays" else ("dense", "sharded")

    def lower(self, compiled: CompiledLike, plan: SystemPlan) -> CompiledLike:
        _check_kernel_plan(self, plan)  # no kernel: plan.kernel is an error
        return compiled

    def compile(self, system: SNPSystem,
                plan: Optional[SystemPlan] = None) -> CompiledLike:
        return _registry_compile(self, system, plan)

    def expand(self, configs: jnp.ndarray, comp: CompiledSNP,
               max_branches: int) -> StepOut:
        if is_delayed(comp):
            return delayed_next_configs(configs, comp, max_branches)
        return next_configs(configs, comp, max_branches)


@dataclass(frozen=True)
class PallasBackend:
    """Fused Pallas transition kernel (decode + S·M + C in VMEM).

    ``interpret=None`` (default) compiles the kernel on a TPU and emulates
    it with jittable lax ops anywhere else; a bool pins the mode.  Block
    shapes are clamped to the problem size by the ops wrapper, so the
    defaults are safe for small systems too.  A compiled kernel refuses
    at lower time a system whose VMEM working set would not fit
    (:meth:`compile_refusal`).  Under a sharded plan,
    ``lower`` attaches the dense per-shard operands
    (:func:`repro.core.plan.lower_shard_dense`) and the same kernel body
    consumes one shard per device: ``C' = C + halo·H_adj + S·M_local``.
    """

    name: str = "pallas"
    interpret: Optional[bool] = None
    block_b: int = 8
    block_t: int = 32
    block_n: int = 128
    supports_nd_batch: bool = True   # flattens leading dims internally
    materializes_spiking: bool = False

    @property
    def pad_multiple(self) -> int:
        return self.block_b

    @property
    def kernel_config(self) -> KernelConfig:
        """This instance's block shape as a plan-carriable config."""
        return KernelConfig(block_b=self.block_b, block_t=self.block_t,
                            block_n=self.block_n)

    def with_kernel(self, kernel: KernelConfig) -> "PallasBackend":
        """A re-blocked instance (``None`` fields keep this one's)."""
        return resolve_kernel(self, SystemPlan(kernel=kernel))

    def supported_encodings(self,
                            semantics: str = "no_delays"
                            ) -> Tuple[str, ...]:
        return ("dense",) if semantics == "delays" else ("dense", "sharded")

    def compile_refusal(self, m: int, n: int, *,
                        semantics: str = "no_delays",
                        halo: int = 0) -> Optional[str]:
        """Why the compiled kernel cannot step ``m`` neurons and ``n``
        rules at this block shape, or ``None``.  The neuron axis is not
        tiled, so the estimated VMEM working set
        (:func:`repro.kernels.snp_step.kernel.vmem_bytes`) must fit the
        kernel's limit; an emulated kernel is never refused."""
        if resolve_interpret(self.interpret):
            return None
        from repro.kernels.snp_step.kernel import VMEM_LIMIT_BYTES, vmem_bytes
        need = vmem_bytes(m, n, block_b=self.block_b, block_t=self.block_t,
                          block_n=self.block_n, halo=halo,
                          delayed=semantics == "delays")
        if need <= VMEM_LIMIT_BYTES:
            return None
        return (f"its VMEM working set at blocks (bb={self.block_b}, "
                f"bt={self.block_t}, bn={self.block_n}) is about "
                f"{need / 2**20:.0f} MiB, over the "
                f"{VMEM_LIMIT_BYTES / 2**20:.0f} MiB limit (the neuron axis "
                "is not tiled)")

    def lower(self, compiled: CompiledLike, plan: SystemPlan) -> CompiledLike:
        _check_kernel_plan(self, plan)
        _refuse_uncompilable(self, *_kernel_shape(compiled))
        if is_sharded(compiled):
            return lower_shard_dense(compiled)
        return compiled

    def compile(self, system: SNPSystem,
                plan: Optional[SystemPlan] = None) -> CompiledLike:
        return _registry_compile(self, system, plan)

    def expand(self, configs: jnp.ndarray, comp: CompiledSNP,
               max_branches: int) -> StepOut:
        # Lazy import: keeps repro.core importable if the Pallas toolchain
        # is absent, and avoids a core <-> kernels import cycle at load.
        from repro.kernels.snp_step.ops import snp_step

        w = configs.shape[-1]  # m, or 3m under delayed semantics
        batch = configs.shape[:-1]
        flat = configs.reshape(-1, w)
        out, valid, emis, overflow = snp_step(
            flat, comp, max_branches=max_branches,
            block_b=self.block_b, block_t=self.block_t,
            block_n=self.block_n, interpret=self.interpret,
        )
        T = max_branches
        return StepOut(
            configs=out.reshape(*batch, T, w),
            valid=valid.reshape(*batch, T),
            emissions=emis.reshape(*batch, T),
            overflow=overflow.reshape(batch),
            spiking=None,
        )


@dataclass(frozen=True)
class SparseBackend:
    """Gather/segment-sum step over the ELL/segment encoding.

    Replaces the dense ``S·M`` einsum with (1) per-neuron mixed-radix
    decode, (2) a selection-table lookup of the fired rule per neuron, and
    (3) a ``K_in``-wide gather over the synapse in-adjacency — never
    materializing the ``(B, T, n)`` one-hot spiking tensor or the dense
    ``(n, m)`` matrix.  Work and memory scale with ``nnz(M_Π)``
    (``O(B·T·m·degree)``) instead of ``O(B·T·n·m)``; valid entries are
    bit-identical to ``"ref"`` for spike counts < 2^24.  ``step_chosen``
    runs the same three stages on one branch per row, ``O(B·m·degree)``.
    """

    name: str = "sparse"
    supports_nd_batch: bool = True
    pad_multiple: int = 1
    materializes_spiking: bool = False

    def supported_encodings(self,
                            semantics: str = "no_delays"
                            ) -> Tuple[str, ...]:
        return ("ell", "hybrid") if semantics == "delays" \
            else ("ell", "hybrid", "sharded")

    def lower(self, compiled: CompiledLike, plan: SystemPlan) -> CompiledLike:
        _check_kernel_plan(self, plan)  # no kernel: plan.kernel is an error
        return compiled

    def compile(self, system: SNPSystem,
                plan: Optional[SystemPlan] = None
                ) -> Union[CompiledSparseSNP, ShardedCompiled]:
        return _registry_compile(self, system, plan)

    def expand(self, configs: jnp.ndarray, comp: CompiledSparseSNP,
               max_branches: int) -> StepOut:
        comp = _require_sparse(comp, self.name)
        if is_delayed(comp):
            return sparse_delayed_next_configs(configs, comp, max_branches)
        return sparse_next_configs(configs, comp, max_branches)

    def step_chosen(self, configs: jnp.ndarray, comp: CompiledSparseSNP,
                    max_branches: int, choose) -> ChosenOut:
        """The successor of each (B, m) row at ``choose(n_valid)``, built
        alone (see the protocol's note on ``step_chosen``)."""
        comp = _require_sparse(comp, self.name)
        if is_delayed(comp):
            return sparse_delayed_chosen_config(configs, comp, max_branches,
                                                choose)
        return sparse_chosen_config(configs, comp, max_branches, choose)


@dataclass(frozen=True)
class SparsePallasBackend:
    """Fused Pallas kernel over the sparse encoding (decode + selection
    lookup + in-adjacency gather in VMEM), for pure-ELL **and** hybrid
    ELL+COO plans — the COO tail runs as an in-kernel scatter-free
    segment-sum stage over the compiler's ``coo_bounds``/``hub_slot``
    metadata (DESIGN.md §3 "Kernel lowering").  Under a sharded plan the
    same body consumes one shard per device through the extended
    ``[local | halo | zero]`` index space.

    ``interpret=None`` (default) resolves from the platform like
    :class:`PallasBackend`; the grid is ``(B/bb, T/bt)`` with the whole
    neuron axis resident per block, so the working set is ``O(bb·bt·m)``
    — the ops wrapper clamps blocks to the problem size.  The kernel's
    in-VMEM gathers do not lower for a TPU yet, so every compiled lowering
    is refused (:meth:`compile_refusal`).
    """

    name: str = "sparse_pallas"
    interpret: Optional[bool] = None
    block_b: int = 8
    block_t: int = 32
    supports_nd_batch: bool = True   # flattens leading dims internally
    materializes_spiking: bool = False

    @property
    def pad_multiple(self) -> int:
        return self.block_b

    @property
    def kernel_config(self) -> KernelConfig:
        """This instance's block shape as a plan-carriable config (no
        ``block_n`` — the neuron axis is never tiled)."""
        return KernelConfig(block_b=self.block_b, block_t=self.block_t)

    def with_kernel(self, kernel: KernelConfig) -> "SparsePallasBackend":
        """A re-blocked instance (``None`` fields keep this one's)."""
        return resolve_kernel(self, SystemPlan(kernel=kernel))

    def supported_encodings(self,
                            semantics: str = "no_delays"
                            ) -> Tuple[str, ...]:
        return ("ell", "hybrid") if semantics == "delays" \
            else ("ell", "hybrid", "sharded")

    def compile_refusal(self, m: int, n: int, *,
                        semantics: str = "no_delays",
                        halo: int = 0) -> Optional[str]:
        """Why the compiled kernel cannot step this shape, or ``None``:
        every shape, until its gathers are rewritten (an emulated kernel
        is never refused)."""
        if resolve_interpret(self.interpret):
            return None
        return ("Mosaic lowers only same-shape 2-D gathers, and the kernel "
                "gathers over its (bb, bt, m) produce rows and its "
                "(bb, m, R) rule table")

    def lower(self, compiled: CompiledLike, plan: SystemPlan) -> CompiledLike:
        _check_kernel_plan(self, plan)
        _refuse_uncompilable(self, *_kernel_shape(compiled))
        # A hybrid encoding the kernel cannot lower must raise here, at
        # lowering time — never a silent downgrade to the jnp path.  Only
        # hand-built encodings can trip this: compile_system_sparse always
        # emits the COO segment metadata.
        if isinstance(compiled, CompiledSparseSNP) and compiled.is_hybrid \
                and (compiled.coo_bounds is None
                     or compiled.hub_slot is None):
            raise ValueError(
                "sparse_pallas cannot lower this hybrid ELL+COO encoding: "
                "it lacks the COO segment metadata (coo_bounds/hub_slot) "
                "the fused kernel's segment-sum stage consumes; lower the "
                "system through compile_system_sparse / backend.compile")
        return compiled

    def compile(self, system: SNPSystem,
                plan: Optional[SystemPlan] = None
                ) -> Union[CompiledSparseSNP, ShardedCompiled]:
        return _registry_compile(self, system, plan)

    def expand(self, configs: jnp.ndarray, comp: CompiledSparseSNP,
               max_branches: int) -> StepOut:
        from repro.kernels.snp_step.sparse_ops import snp_step_sparse

        comp = self.lower(_require_sparse(comp, self.name),
                          SystemPlan.default())
        w = configs.shape[-1]  # m, or 3m under delayed semantics
        batch = configs.shape[:-1]
        flat = configs.reshape(-1, w)
        out, valid, emis, overflow = snp_step_sparse(
            flat, comp, max_branches=max_branches,
            block_b=self.block_b, block_t=self.block_t,
            interpret=self.interpret,
        )
        T = max_branches
        return StepOut(
            configs=out.reshape(*batch, T, w),
            valid=valid.reshape(*batch, T),
            emissions=emis.reshape(*batch, T),
            overflow=overflow.reshape(batch),
            spiking=None,
        )


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, StepBackend] = {}

BackendLike = Union[str, StepBackend]


def register_backend(backend: StepBackend, *, overwrite: bool = False) -> None:
    """Register ``backend`` under ``backend.name``.

    Later backends (sparse/CSR, multi-kernel, TPU-native) plug in here; the
    consumers (`explore`, `run_trace(s)`, `explore_distributed`,
    `snp_service`, benchmarks) pick them up by name with zero changes.
    """
    if not overwrite and backend.name in _REGISTRY:
        raise ValueError(f"backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_backend(name: BackendLike) -> StepBackend:
    """Resolve a backend by registry name (or pass an instance through).

    Instances are duck-checked against the *pre-registry* core of the
    protocol (``name`` + ``expand``) rather than the full
    :class:`StepBackend`, so third-party backends that predate the
    lowering registry hooks keep resolving — the tolerant
    :func:`lower_with_backend` / :func:`supports_sharded` helpers cover
    the missing methods downstream."""
    if isinstance(name, str):
        try:
            return _REGISTRY[name]
        except KeyError:
            raise ValueError(
                f"unknown step backend {name!r}; "
                f"available: {available_backends()}"
            ) from None
    if hasattr(name, "expand") and hasattr(name, "name"):
        return name
    raise TypeError(f"expected backend name or StepBackend, got {type(name)}")


register_backend(RefBackend())
register_backend(PallasBackend())
register_backend(SparseBackend())
register_backend(SparsePallasBackend())
