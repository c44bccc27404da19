"""Cost-model query planner + ``(bb, bt)`` kernel autotuner.

The paper's matrix form gives four interchangeable lowerings of the same
``M_Π`` transition (dense / ELL / hybrid, each with a fused Pallas
kernel), and the committed bench baseline shows the right choice is
workload-dependent: the dense Pallas kernel loses to ``ref`` at every
measured shape while ``sparse_pallas`` wins only below a density/size
crossover — the central performance question the sparse SNP-on-GPU
follow-up work (arXiv 2408.04343) identifies for these systems.  This
module makes the choice automatic.  Decision flow (DESIGN.md §3
"Planner & autotuner")::

    workload signature (m, n, K_in, B, T)
        │
        ├─ 1. autotune cache ──  on-disk JSON of measured winners,
        │                        seeded from the committed BENCH_snp.json
        │                        so fresh checkouts and CI get sane
        │                        defaults without measuring
        ├─ 2. analytic model ──  per-backend log-log cost curves
        │                        us ≈ A·W^p over the dense work proxy
        │                        W = B·T·n·m, calibrated against the
        │                        bench baseline (interpret-mode kernels
        │                        are never extrapolated far past their
        │                        measured support)
        │   (every stage skips a backend whose kernel ``compile_refusal``
        │    names this shape on this platform)
        └─ 3. degree heuristic — ``SystemPlan.for_system(mode="static")``
                                 (the caller falls through when this
                                 module returns ``None``)

Entry points: :func:`plan_for` (what ``SystemPlan.for_system`` calls for
``mode="auto"|"measure"``), :func:`measure_best` (the inline sweep),
:func:`lookup`/:func:`store_choice` (cache), :func:`predict_us` (model
introspection, used by ``examples/explore_distributed.py --plan auto``).

The cache lives at ``$REPRO_AUTOTUNE_CACHE`` (else
``~/.cache/repro-snp/autotune.json``), keyed on the full workload
signature ``m{m}_n{n}_kin{kin}_B{B}_T{T}`` (bench-seeded entries use a
``kin*`` wildcard — the baseline rows don't record in-degree).  A
corrupt or poisoned file degrades to the analytic model with a
``UserWarning``; it never crashes a plan."""

from __future__ import annotations

import dataclasses
import json
import math
import os
import re
import warnings
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from .plan import (KernelConfig, SystemPlan, _in_degrees,
                   auto_hub_threshold)
from .system import SNPSystem

__all__ = [
    "DEFAULT_WORKLOAD",
    "TunedChoice",
    "WorkloadSignature",
    "cache_path",
    "load_cache",
    "lookup",
    "measure_best",
    "model_choice",
    "plan_for",
    "predict_us",
    "save_cache",
    "signature_of",
    "store_choice",
]

# Workload shape assumed when the caller gives no (B, T) hint: the
# engine defaults (frontier_cap is larger, but 64×32 sits mid-sweep).
DEFAULT_WORKLOAD: Tuple[int, int] = (64, 32)

_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_BASELINE_ENV = "REPRO_BENCH_BASELINE"
_CACHE_VERSION = 1

# Backends whose Pallas kernels currently run in interpret mode on CPU:
# their measured cost curves stop being trustworthy far outside the
# fitted support (interpret overhead explodes super-linearly — the
# committed baseline shows dense pallas at 6.65x ref by m=512), so the
# model never extrapolates them past _EXTRAPOLATION_GUARD × max fitted W.
_INTERPRET_KERNELS = ("pallas", "sparse_pallas")
_EXTRAPOLATION_GUARD = 4.0

# Fallback log-log fits us ≈ exp(logA + p·log W), W = B·T·n·m, computed
# from the committed BENCH_snp.json (snp_step + snp_step_large tiers).
# Used only when no baseline file is readable: {backend: (p, logA, Wmax)}.
_FALLBACK_FITS = {
    "ref": (0.5090, 1.0699, 1.718e10),
    "pallas": (0.5288, 1.2964, 2.147e9),
    "sparse": (0.4735, 1.0151, 1.374e11),
    "sparse_pallas": (0.4601, 0.7715, 1.342e8),
}

# Block shapes the committed bench sweep runs its kernel backends at
# (benchmarks/bench_snp.py BACKENDS) — seeded cache entries carry them so
# a seed-driven plan reproduces the measured configuration.
_BENCH_KERNELS = {
    "pallas": KernelConfig(block_b=8, block_t=16, block_n=128),
    "sparse_pallas": KernelConfig(block_b=8, block_t=16),
}

_ROW_SHAPE = re.compile(r"m(\d+)_n(\d+)_B(\d+)_T(\d+)$")


@dataclasses.dataclass(frozen=True)
class WorkloadSignature:
    """The ``(m, n, K_in, B, T, semantics)`` key a tuning decision is
    valid for: neurons, rules, max in-degree, frontier batch, branch cap,
    transition-semantics tier.  Delayed steps cost more than delay-free
    ones at the same shape (3m-wide state, the reopen/gate stage), so the
    two tiers never share a cache entry."""

    m: int
    n: int
    kin: int
    B: int
    T: int
    semantics: str = "no_delays"

    @property
    def work(self) -> float:
        """Dense work proxy ``W = B·T·n·m`` — what one step touches in
        the paper's ``C' = C + S·M_Π`` form (S is (B·T, n), M_Π (n, m))."""
        return float(self.B) * self.T * self.n * self.m

    def _suffix(self) -> str:
        # Suffix only under delays: every pre-existing cache/seed key
        # stays valid for the default tier.
        return "_delays" if self.semantics == "delays" else ""

    def key(self) -> str:
        return (f"m{self.m}_n{self.n}_kin{self.kin}"
                f"_B{self.B}_T{self.T}{self._suffix()}")

    def wildcard_key(self) -> str:
        """Key with the in-degree wildcarded — bench-seeded entries only
        know the ``(m, n, B, T)`` shape."""
        return f"m{self.m}_n{self.n}_kin*_B{self.B}_T{self.T}{self._suffix()}"


@dataclasses.dataclass(frozen=True)
class TunedChoice:
    """One planning decision: backend + encoding + block shape, with the
    measured/predicted step cost and where the decision came from
    (``seed`` = committed baseline, ``cache`` = a prior measure run,
    ``model`` = analytic fit, ``measure`` = timed right now)."""

    backend: str
    encoding: str = "auto"
    hub_threshold: Optional[int] = None
    block_b: Optional[int] = None
    block_t: Optional[int] = None
    block_n: Optional[int] = None
    us_per_step: Optional[float] = None
    source: str = "model"

    def kernel(self) -> Optional[KernelConfig]:
        if (self.block_b is None and self.block_t is None
                and self.block_n is None):
            return None
        return KernelConfig(block_b=self.block_b, block_t=self.block_t,
                            block_n=self.block_n)


def signature_of(system: SNPSystem, *,
                 workload: Optional[Tuple[int, int]] = None,
                 semantics: str = "no_delays") -> WorkloadSignature:
    """The workload signature of running ``system`` at ``workload=(B, T)``
    (``DEFAULT_WORKLOAD`` when the caller has no hint)."""
    B, T = workload if workload is not None else DEFAULT_WORKLOAD
    in_deg = _in_degrees(system)
    kin = int(in_deg.max()) if in_deg.size else 0
    return WorkloadSignature(m=system.num_neurons, n=system.num_rules,
                             kin=kin, B=int(B), T=int(T),
                             semantics=semantics)


# ---------------------------------------------------------------------------
# On-disk cache
# ---------------------------------------------------------------------------


def cache_path() -> Path:
    env = os.environ.get(_CACHE_ENV)
    if env:
        return Path(env)
    return Path.home() / ".cache" / "repro-snp" / "autotune.json"


def load_cache(path: Optional[Path] = None) -> Dict[str, dict]:
    """The cache's ``{signature key: entry dict}`` map.  A missing file
    is an empty cache; an unreadable/corrupt one warns and degrades to
    empty (the planner falls through to the analytic model)."""
    path = cache_path() if path is None else Path(path)
    if not path.exists():
        return {}
    try:
        payload = json.loads(path.read_text())
        entries = payload["entries"]
        if not isinstance(entries, dict):
            raise TypeError("entries is not a mapping")
        return entries
    except Exception as exc:  # corrupt/poisoned file: degrade, don't crash
        warnings.warn(
            f"autotune cache {path} is unreadable ({exc}); ignoring it — "
            "planning falls back to the analytic cost model",
            UserWarning, stacklevel=2)
        return {}


def save_cache(entries: Dict[str, dict],
               path: Optional[Path] = None) -> None:
    """Atomic write (tmp + rename) so a crashed measure run can't leave a
    half-written file for :func:`load_cache` to choke on."""
    path = cache_path() if path is None else Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.name + ".tmp")
    tmp.write_text(json.dumps(
        {"version": _CACHE_VERSION, "entries": entries},
        indent=1, sort_keys=True))
    tmp.replace(path)


def _entry_to_choice(entry, source: Optional[str] = None
                     ) -> Optional[TunedChoice]:
    """Validated :class:`TunedChoice` from one cache entry, or ``None``
    for a poisoned entry (wrong types, unknown backend name, bad block
    values) — a bad entry is skipped, never fatal."""
    from .backend import available_backends  # lazy: backend imports plan
    try:
        if not isinstance(entry, dict):
            return None
        name = entry["backend"]
        if name not in available_backends():
            return None
        choice = TunedChoice(
            backend=str(name),
            encoding=str(entry.get("encoding", "auto")),
            hub_threshold=entry.get("hub_threshold"),
            block_b=entry.get("block_b"),
            block_t=entry.get("block_t"),
            block_n=entry.get("block_n"),
            us_per_step=entry.get("us_per_step"),
            source=source or str(entry.get("source", "cache")),
        )
        choice.kernel()  # raises on invalid block values
        if choice.encoding not in ("auto", "dense", "ell", "hybrid"):
            return None
        return choice
    except Exception:
        return None


def _choice_to_entry(choice: TunedChoice) -> dict:
    return {
        "backend": choice.backend,
        "encoding": choice.encoding,
        "hub_threshold": choice.hub_threshold,
        "block_b": choice.block_b,
        "block_t": choice.block_t,
        "block_n": choice.block_n,
        "us_per_step": choice.us_per_step,
        "source": choice.source,
    }


def store_choice(sig: WorkloadSignature, choice: TunedChoice,
                 path: Optional[Path] = None) -> None:
    """Persist ``choice`` as the winner for ``sig`` (exact-key entry)."""
    entries = load_cache(path)
    entries[sig.key()] = _choice_to_entry(choice)
    save_cache(entries, path)


# ---------------------------------------------------------------------------
# Bench-baseline seeding
# ---------------------------------------------------------------------------


def _baseline_path() -> Optional[Path]:
    env = os.environ.get(_BASELINE_ENV)
    if env:
        p = Path(env)
        return p if p.exists() else None
    p = Path(__file__).resolve().parents[3] / "BENCH_snp.json"
    return p if p.exists() else None


def _baseline_rows() -> List[Tuple[str, int, int, int, int, float]]:
    """``(backend, m, n, B, T, us_per_call)`` per single-device step row
    of the committed bench baseline (``snp_step`` + ``snp_step_large``
    tiers — the tiers whose rows time exactly one fused expansion)."""
    path = _baseline_path()
    if path is None:
        return []
    try:
        payload = json.loads(path.read_text())
        rows = payload["rows"]
    except Exception:
        return []
    out = []
    from .backend import available_backends  # lazy: backend imports plan
    names = available_backends()
    for row in rows:
        try:
            parts = str(row["name"]).split("/")
            if parts[0] not in ("snp_step", "snp_step_large"):
                continue
            shape = _ROW_SHAPE.search(parts[-1])
            backend = next(p for p in parts[1:] if p in names)
            if shape is None:
                continue
            m, n, B, T = map(int, shape.groups())
            out.append((backend, m, n, B, T, float(row["us_per_call"])))
        except Exception:
            continue
    return out


def _seed_entries() -> Dict[str, dict]:
    """Wildcard-kin cache entries from the committed baseline: per
    ``(m, n, B, T)`` shape, the fastest measured backend at the block
    shape the bench ran it with."""
    best: Dict[Tuple[int, int, int, int], Tuple[str, float]] = {}
    for backend, m, n, B, T, us in _baseline_rows():
        key = (m, n, B, T)
        if key not in best or us < best[key][1]:
            best[key] = (backend, us)
    entries = {}
    for (m, n, B, T), (backend, us) in best.items():
        cfg = _BENCH_KERNELS.get(backend)
        entries[f"m{m}_n{n}_kin*_B{B}_T{T}"] = _choice_to_entry(
            TunedChoice(
                backend=backend,
                block_b=cfg.block_b if cfg else None,
                block_t=cfg.block_t if cfg else None,
                block_n=cfg.block_n if cfg else None,
                us_per_step=us, source="seed"))
    return entries


def lookup(sig: WorkloadSignature, *,
           sharded: bool = False) -> Optional[TunedChoice]:
    """Cache consultation: exact signature key first, then the
    wildcard-kin key; measured disk entries shadow bench seeds.  Returns
    ``None`` on a miss (or when every hit is poisoned/unusable)."""
    disk = load_cache()
    seeds = _seed_entries()
    for key in (sig.key(), sig.wildcard_key()):
        for table, source in ((disk, None), (seeds, "seed")):
            if key in table:
                choice = _entry_to_choice(table[key], source=source)
                if choice is not None and _usable(
                        choice, sharded=sharded, semantics=sig.semantics) \
                        and _compilable(choice, sig):
                    return choice
    return None


def _usable(choice: TunedChoice, *, sharded: bool,
            semantics: str = "no_delays") -> bool:
    from .backend import get_backend
    sup = get_backend(choice.backend).supported_encodings(
        semantics=semantics)
    if sharded:
        return "sharded" in sup
    if not sup:
        return False
    return choice.encoding == "auto" or choice.encoding in sup


def _compilable(choice: TunedChoice, sig: WorkloadSignature) -> bool:
    """Whether ``choice``'s kernel, at its block shape, compiles for
    ``sig``'s system on this platform — the backend's own
    ``compile_refusal``, so the planner never picks what ``lower`` would
    refuse (backends without the hook always compile)."""
    from .backend import get_backend, resolve_kernel
    be = get_backend(choice.backend)
    if getattr(be, "compile_refusal", None) is None:
        return True
    if choice.kernel() is not None:
        be = resolve_kernel(be, SystemPlan(kernel=choice.kernel()))
    return be.compile_refusal(sig.m, sig.n, semantics=sig.semantics) is None


# ---------------------------------------------------------------------------
# Analytic cost model
# ---------------------------------------------------------------------------


def _fitted_curves() -> Dict[str, Tuple[float, float, float]]:
    """Per-backend ``(p, logA, Wmax)`` log-log least-squares fits of
    step cost against the work proxy ``W`` over the baseline rows
    (``us ≈ exp(logA)·W^p``); :data:`_FALLBACK_FITS` when no baseline
    file is readable."""
    pts: Dict[str, List[Tuple[float, float]]] = {}
    for backend, m, n, B, T, us in _baseline_rows():
        if us > 0:
            pts.setdefault(backend, []).append((float(B) * T * n * m, us))
    fits = {}
    for backend, ps in pts.items():
        lw = np.log([w for w, _ in ps])
        lu = np.log([u for _, u in ps])
        if len(ps) >= 2:
            p, logA = np.polyfit(lw, lu, 1)
        else:  # single point: assume the shared ~sqrt scaling exponent
            p = 0.5
            logA = float(lu[0] - p * lw[0])
        fits[backend] = (float(p), float(logA), max(w for w, _ in ps))
    return fits or dict(_FALLBACK_FITS)


def predict_us(sig: WorkloadSignature, backend: str) -> Optional[float]:
    """Model-predicted µs per fused step for ``backend`` at ``sig``, or
    ``None`` when the model has no curve for that backend."""
    fit = _fitted_curves().get(backend)
    if fit is None:
        return None
    p, logA, _ = fit
    return math.exp(logA + p * math.log(max(sig.work, 1.0)))


def model_choice(sig: WorkloadSignature, *,
                 sharded: bool = False) -> Optional[TunedChoice]:
    """Cheapest backend under the analytic model.  Interpret-mode Pallas
    backends are excluded once ``W`` leaves their fitted support
    (module constants) — their curves undersell how badly interpret
    overhead scales."""
    from .backend import available_backends, get_backend
    fits = _fitted_curves()
    names = available_backends()
    best: Optional[TunedChoice] = None
    for backend, (p, logA, wmax) in sorted(fits.items()):
        if backend not in names:
            continue
        sup = get_backend(backend).supported_encodings(
            semantics=sig.semantics)
        if not sup or (sharded and "sharded" not in sup):
            continue
        if (backend in _INTERPRET_KERNELS
                and sig.work > _EXTRAPOLATION_GUARD * wmax):
            continue
        us = math.exp(logA + p * math.log(max(sig.work, 1.0)))
        if best is None or us < best.us_per_step:
            cfg = _BENCH_KERNELS.get(backend)
            choice = TunedChoice(
                backend=backend,
                block_b=cfg.block_b if cfg else None,
                block_t=cfg.block_t if cfg else None,
                block_n=cfg.block_n if cfg else None,
                us_per_step=us, source="model")
            if _compilable(choice, sig):
                best = choice
    return best


# ---------------------------------------------------------------------------
# Inline measurement (mode="measure")
# ---------------------------------------------------------------------------


def default_candidates(sig: WorkloadSignature, *,
                       sharded: bool = False) -> List[TunedChoice]:
    """The candidate grid :func:`measure_best` sweeps: every registered
    backend at its native encoding; kernel backends additionally at a
    couple of block shapes.  Interpret-mode kernels are dropped outside
    their trusted work range (same guard as the model) so a measure run
    on a large system doesn't spend minutes timing a known-bad config."""
    from .backend import available_backends, get_backend
    dense_blocks = [(8, 16, 128), (8, 32, 128)]
    sparse_blocks = [(8, 16, None), (4, 8, None)]
    out: List[TunedChoice] = []
    for name in sorted(available_backends()):
        sup = get_backend(name).supported_encodings(
            semantics=sig.semantics)
        if not sup or (sharded and "sharded" not in sup):
            continue
        if name in _INTERPRET_KERNELS:
            fit = _fitted_curves().get(name)
            wmax = fit[2] if fit else _FALLBACK_FITS.get(
                name, (0, 0, 0))[2]
            if sig.work > _EXTRAPOLATION_GUARD * wmax:
                continue
            blocks = dense_blocks if "dense" in sup else sparse_blocks
            out.extend(TunedChoice(backend=name, block_b=bb, block_t=bt,
                                   block_n=bn)
                       for bb, bt, bn in blocks)
        else:
            out.append(TunedChoice(backend=name))
    return [c for c in out if _compilable(c, sig)]


def _time_step(be, comp, configs, T: int, *, reps: int) -> float:
    """Median µs of one fused expansion: one warmup call absorbs
    compilation, then ``reps`` timed ``block_until_ready`` calls."""
    import time

    import jax

    @jax.jit
    def fn(c):
        return be.expand(c, comp, max_branches=T)

    jax.block_until_ready(fn(configs))
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(configs))
        samples.append((time.perf_counter() - t0) * 1e6)
    samples.sort()
    return samples[len(samples) // 2]


def measure_best(system: SNPSystem, sig: WorkloadSignature, *,
                 num_shards: int = 1, reps: int = 3,
                 candidates: Optional[List[TunedChoice]] = None,
                 persist: bool = True) -> Optional[TunedChoice]:
    """Time the candidate grid on ``system`` at ``sig``'s ``(B, T)`` and
    return the winner (persisted to the cache so ``mode="auto"`` finds
    it next time).  Candidates that fail to compile/realize are skipped;
    ``None`` only when every candidate failed."""
    import jax.numpy as jnp

    from .backend import get_backend, resolve_kernel
    sharded = num_shards > 1
    cands = candidates if candidates is not None else \
        default_candidates(sig, sharded=sharded)
    rng = np.random.default_rng(0)
    m = system.num_neurons
    spikes = rng.integers(0, 5, size=(sig.B, m))
    if sig.semantics == "delays":
        # Delayed state rows are 3m wide: [spikes | countdown | pending].
        spikes = np.concatenate(
            [spikes, np.zeros((sig.B, 2 * m), spikes.dtype)], axis=1)
    configs = jnp.asarray(spikes, jnp.int32)
    best: Optional[TunedChoice] = None
    for cand in cands:
        try:
            # Measure at the single-device lowering even when planning a
            # sharded run: the per-shard kernel is the same body, and a
            # measure sweep must not commandeer the device mesh.
            plan = choice_to_plan(cand, system, mode="static",
                                  semantics=sig.semantics)
            be = resolve_kernel(get_backend(cand.backend), plan)
            comp = be.compile(system, plan=plan)
            us = _time_step(be, comp, configs, sig.T, reps=reps)
        except Exception:
            continue
        timed = dataclasses.replace(cand, us_per_step=us, source="measure")
        if best is None or us < best.us_per_step:
            best = timed
    if best is not None and persist:
        try:
            store_choice(sig, best)
        except OSError:
            pass  # read-only checkout: the measurement still stands
    return best


# ---------------------------------------------------------------------------
# Planner entry point
# ---------------------------------------------------------------------------


def choice_to_plan(choice: TunedChoice, system: SNPSystem, *,
                   num_shards: int = 1, mode: str = "auto",
                   semantics: str = "no_delays"
                   ) -> Optional[SystemPlan]:
    """A :class:`SystemPlan` realizing ``choice`` on ``system``, or
    ``None`` when the choice can't be realized (e.g. a cache entry naming
    an encoding its backend doesn't support under the semantics tier).
    ``encoding="auto"`` choices resolve sparse-family backends through
    the degree heuristic (ELL vs hybrid), everything else to the
    backend's native layout."""
    from .backend import get_backend
    sup = get_backend(choice.backend).supported_encodings(
        semantics=semantics)
    if not sup:
        return None
    if num_shards > 1:
        if "sharded" not in sup:
            return None
        # Hub regime: spread the heavy in-degree neurons across shards
        # (same degree test as the hybrid-encoding flip below).
        in_deg = _in_degrees(system)
        h = auto_hub_threshold(in_deg)
        kin = int(in_deg.max()) if in_deg.size else 0
        part = "degree" if kin > 2 * h else "contiguous"
        # Per-shard lowerings are ELL-only (compile_sharded).
        return SystemPlan(encoding="ell", num_shards=num_shards,
                          mode=mode, backend=choice.backend,
                          kernel=choice.kernel(), semantics=semantics,
                          partition=part)
    encoding, hub = choice.encoding, choice.hub_threshold
    if encoding == "auto" and sup[0] == "ell":
        in_deg = _in_degrees(system)
        h = auto_hub_threshold(in_deg)
        kin = int(in_deg.max()) if in_deg.size else 0
        if kin > 2 * h and "hybrid" in sup:
            encoding, hub = "hybrid", h
    if encoding != "auto" and encoding not in sup:
        return None
    return SystemPlan(encoding=encoding, hub_threshold=hub, mode=mode,
                      backend=choice.backend, kernel=choice.kernel(),
                      semantics=semantics)


def plan_for(system: SNPSystem, *, num_shards: int = 1,
             workload: Optional[Tuple[int, int]] = None,
             measure: bool = False,
             semantics: str = "no_delays") -> Optional[SystemPlan]:
    """The decision flow (module docstring): measure inline when asked,
    else cache → analytic model.  ``None`` sends the caller
    (``SystemPlan.for_system``) back to the static degree heuristic."""
    sig = signature_of(system, workload=workload, semantics=semantics)
    sharded = num_shards > 1
    if measure:
        choice = measure_best(system, sig, num_shards=num_shards)
        mode = "measure"
    else:
        choice = lookup(sig, sharded=sharded) \
            or model_choice(sig, sharded=sharded)
        mode = "auto"
    if choice is None:
        return None
    return choice_to_plan(choice, system, num_shards=num_shards, mode=mode,
                          semantics=semantics)
