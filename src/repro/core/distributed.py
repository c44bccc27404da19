"""Multi-chip SNP workloads (shard_map): tree exploration + trace serving.

Two entry points share the mesh plumbing:

* :func:`explore_distributed` — hash-partitioned BFS over the computation
  tree (frontier and visited set sharded by config hash);
* :func:`run_traces_distributed` — data-parallel batched trajectory
  serving: the batch axis of :func:`repro.core.engine.run_traces` sharded
  over the mesh, bit-identical to the single-device path (DESIGN.md §4).

The paper runs on one GPU; at fleet scale both the frontier and the visited
set must shard.  The exploration scheme (DESIGN.md §2):

* **hash ownership** — configuration with hash ``h`` is owned by device
  ``h mod n_dev``.  Ownership decides (a) which visited-shard a config is
  deduped against and (b) which frontier-shard expands it.  Uniform hashing
  doubles as load balancing: each BFS level spreads across chips in
  expectation regardless of tree shape.
* **expand locally, exchange by owner** — each device expands its frontier
  shard through the same pluggable :class:`~repro.core.backend.StepBackend`
  as the single-chip engine (``backend="ref"`` or ``"pallas"``; the fused
  kernel on TPU), bins successors by owner, and exchanges them with one tiled
  ``all_to_all``.  Received candidates are deduped against the *local*
  visited shard only — no global synchronization beyond the one collective.
* **static capacities** — per-destination send slots, frontier and visited
  shards are fixed-size; every overflow is detected and psum-reported.
  Dropped candidates are simply *not marked visited*, so they are
  regenerated and explored later: soundness is preserved (same argument as
  the single-chip engine).

For **large m** (the ROADMAP's ``m >= 10^5`` regime) the dense-row
exchange above stops scaling: every shipped candidate costs ``O(m)``.
Passing a :class:`~repro.core.plan.SystemPlan` with ``num_shards == ndev``
flips ``explore_distributed`` into the **neuron-axis-sharded** scheme
(DESIGN.md §2): the frontier, archive and every candidate carry only their
``mloc = ceil(m/ndev)`` neuron slice per device; expansion steps the local
slice through the selected backend — the jnp sparse math or a fused
Pallas kernel consuming the shard's extended-index encoding (DESIGN.md §3
"Kernel lowering") — and exchanges only the *touched segments*: the fired
produce of halo neurons along synapses that cross a shard boundary, a
static ``O(cut)`` payload per step instead of ``O(m)`` rows.  The batch-hash ownership scheme stays: global config hashes are
recovered from additive per-slice partials
(:func:`~repro.core.hashing.zobrist_hash` + one ``psum``) and each device
still dedups exactly the candidates it hash-owns against its local
visited shard.

The per-step program is one jit(shard_map(...)) over a 1-D device axis —
on the production mesh this is the flattened ``(pod, data, model)`` axes
(SNP exploration is pure data parallelism; the model axes contribute their
devices to the frontier partition).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from .backend import (BackendLike, PallasBackend, SparsePallasBackend,
                      compile_with_plan, lower_with_backend, resolve_entry,
                      resolve_entry_info, supports_sharded)
from .engine import ExploreResult, TraceOut, _traces_scan
from .failover import run_with_failover
from .hashing import SENTINEL, config_hash, zobrist_hash
from .hashtable import (HashTable, _base_slot, _canonical, first_occurrence,
                        insert_unique, lookup, table_slots)
from .matrix import CompiledAny, is_compiled
from .plan import (DenseShardArrays, ShardArrays, ShardedCompiled,
                   SystemPlan, compile_sharded, is_sharded, shard_view)
from .semantics import (_decode_digits, _fired_packed, in_adjacency_sum,
                        packed_rule_table, sparse_branch_info)
from .system import SNPSystem

__all__ = ["explore_distributed", "run_traces_distributed"]


# ---------------------------------------------------------------------------
# Checkpoint/resume for the fused device loops.  Both exploration schemes
# run their BFS as one ``lax.while_loop`` under shard_map; the absolute
# step and the convergence scalar ride the carry, so chunking the loop on
# absolute step bounds (``checkpoint_every`` levels per device call) is
# bit-identical to an uninterrupted run.  The state tuple is snapshotted
# between chunks through the atomic-rename machinery and a re-invoked run
# restores the latest snapshot (re-sharded onto the live mesh via each
# template leaf's sharding) and continues bit-identically.
# ---------------------------------------------------------------------------


def _restore_loop_state(checkpoint_dir, state: tuple):
    """(state, start_step): the latest snapshot re-device_put with the
    live state's shardings, or the fresh state at step 0."""
    from repro.checkpoint.checkpoint import latest_step, restore_checkpoint
    if checkpoint_dir is None:
        return state, 0
    last = latest_step(checkpoint_dir)
    if last is None:
        return state, 0
    host = jax.tree.map(np.asarray, tuple(state))
    restored, step, _ = restore_checkpoint(checkpoint_dir, host, step=last)
    put = tuple(jax.device_put(arr, ref.sharding)
                for arr, ref in zip(restored, state))
    return put, step


def _save_loop_state(checkpoint_dir, step: int, state: tuple) -> None:
    from repro.checkpoint.checkpoint import save_checkpoint
    save_checkpoint(checkpoint_dir, step, jax.tree.map(np.asarray,
                                                       tuple(state)))


def _run_fused_loop(loop_fn, lead, state, *, max_steps, checkpoint_dir,
                    checkpoint_every, fault_injector):
    """Drive a fused BFS while-loop to convergence.

    Without checkpointing this is ONE device call covering all
    ``max_steps`` levels: the convergence poll is the while-loop predicate
    on device, so no host transfer happens between BFS levels.  With
    ``checkpoint_dir`` the same executable is called per chunk
    (``checkpoint_every`` absolute levels each; ``bound`` is a traced
    scalar) — bit-identical to the uninterrupted run, with only the two
    loop scalars read back between chunks.  ``state`` is the loop carry
    with ``step`` at ``[-2]`` and the convergence count at ``[-1]``."""
    if checkpoint_dir is None:
        if fault_injector is not None:
            fault_injector.on_device_call()
        return loop_fn(*lead, *state, jnp.asarray(max_steps, jnp.int32))
    state, _ = _restore_loop_state(checkpoint_dir, state)
    step, total_new = (int(x) for x in jax.device_get(
        (state[-2], state[-1])))
    while step < max_steps and total_new > 0:
        bound = min(step + checkpoint_every, max_steps)
        if fault_injector is not None:
            fault_injector.on_device_call()
        state = loop_fn(*lead, *state, jnp.asarray(bound, jnp.int32))
        step, total_new = (int(x) for x in jax.device_get(
            (state[-2], state[-1])))
        if step < max_steps and total_new > 0:
            _save_loop_state(checkpoint_dir, step, state)
    return state


def _flat_mesh(mesh: Optional[Mesh]) -> Tuple[Mesh, str]:
    """Resolve ``mesh`` to a 1-D mesh + axis name, flattening N-d meshes
    (SNP serving and exploration are pure data parallelism, so every mesh
    axis contributes its devices to the one batch/frontier axis)."""
    if mesh is None:
        return Mesh(np.array(jax.devices()), ("x",)), "x"
    if len(mesh.axis_names) == 1:
        return mesh, mesh.axis_names[0]
    return Mesh(mesh.devices.reshape(-1), ("x",)), "x"


def _dense_body(comp, carry, *, axis, ndev, max_branches, send_cap,
                visited_cap, backend):
    """One BFS level of the dense-row scheme (runs inside the fused
    ``lax.while_loop`` under shard_map over ``axis``).  ``ndev`` is the
    static mesh size (it sizes bincounts and send buffers); dedup is the
    per-device hash-table shard (``core.hashtable``), so a level costs
    ``O(R·probe)`` gathers instead of re-sorting the visited shard."""
    (frontier, frontier_valid, vhi, vlo, vpay, vcount, archive, archive_n,
     flags, step, _) = carry
    F, m = frontier.shape
    T = max_branches
    K = F * T
    C = send_cap

    # --- expand local frontier -------------------------------------------
    out = backend.expand(frontier, comp, T)
    cand = out.configs.reshape(K, m)
    valid = (out.valid & frontier_valid[:, None]).reshape(K)
    branch_ovf = jnp.any(out.overflow & frontier_valid)

    # --- bin successors by hash owner and exchange ------------------------
    hi, lo = config_hash(cand)
    owner = jnp.where(valid, (hi % np.uint32(ndev)).astype(jnp.int32), ndev)
    order = jnp.argsort(owner, stable=True)
    owner_sorted = owner[order]
    counts = jnp.bincount(jnp.minimum(owner, ndev), length=ndev + 1)[:ndev]
    group_start = jnp.cumsum(counts) - counts
    pos = jnp.arange(K, dtype=jnp.int32) - jnp.where(
        owner_sorted < ndev, group_start[jnp.minimum(owner_sorted, ndev - 1)], 0)
    send_ovf = jnp.any(counts > C)
    slot = jnp.where(
        (owner_sorted < ndev) & (pos < C),
        owner_sorted * C + pos,
        ndev * C,  # dropped
    )
    send_cfg = jnp.zeros((ndev * C, m), jnp.int32).at[slot].set(
        cand[order], mode="drop")
    send_val = jnp.zeros((ndev * C,), jnp.int32).at[slot].set(
        (owner_sorted < ndev).astype(jnp.int32), mode="drop")
    # ship the (8-byte) hashes with the payload: rehashing the received
    # candidates costs ~m*4 bytes of elementwise traffic per config, the
    # wire cost of sending them is negligible (§Perf cell C)
    send_hi = jnp.zeros((ndev * C,), jnp.uint32).at[slot].set(
        hi[order], mode="drop")
    send_lo = jnp.zeros((ndev * C,), jnp.uint32).at[slot].set(
        lo[order], mode="drop")

    recv_cfg = jax.lax.all_to_all(send_cfg, axis, 0, 0, tiled=True)
    recv_val = jax.lax.all_to_all(send_val, axis, 0, 0, tiled=True)
    rhi = jax.lax.all_to_all(send_hi, axis, 0, 0, tiled=True)
    rlo = jax.lax.all_to_all(send_lo, axis, 0, 0, tiled=True)

    # --- dedup received candidates against the local table shard ----------
    rvalid = recv_val == 1
    table = HashTable(vhi, vlo, vpay, vcount[0])
    found, _ = lookup(table, rhi, rlo, rvalid)
    first, ovf_f = first_occurrence(rhi, rlo, rvalid)
    new_mask = rvalid & first & ~found

    n_new = jnp.sum(new_mask, dtype=jnp.int32)
    sel = jnp.argsort(~new_mask, stable=True)[:F]
    n_ins = jnp.minimum(n_new, F)
    ins = jnp.arange(F) < n_ins
    next_frontier = recv_cfg[sel]
    frontier_ovf = n_new > F

    # only the selected prefix becomes visited (payload = archive row), so
    # excess discoveries regenerate later — same soundness as the engine
    table, _, ovf_i = insert_unique(
        table, rhi[sel], rlo[sel], ins,
        (archive_n + jnp.arange(F)).astype(jnp.int32))
    visited_ovf = ovf_f | ovf_i | (vcount[0] + n_ins > visited_cap)

    arch_idx = jnp.where(ins, archive_n + jnp.arange(F), archive.shape[0])
    archive = archive.at[arch_idx].set(next_frontier, mode="drop")
    archive_n = jnp.minimum(archive_n + n_ins, archive.shape[0])

    flags = flags | jnp.stack([branch_ovf | send_ovf, frontier_ovf,
                               visited_ovf])
    total_new = jax.lax.psum(n_ins, axis)
    return (next_frontier, ins, table.slots_hi, table.slots_lo,
            table.slot_payload, table.count[None], archive, archive_n,
            flags, step + 1, total_new)


def _dense_loop(comp, frontier, fvalid, vhi, vlo, vpay, vcount, archive,
                archive_n, flags, step, total_new, bound, *, axis, ndev,
                max_branches, send_cap, visited_cap, backend):
    """The whole dense-row BFS (up to ``bound`` absolute levels) as one
    ``lax.while_loop`` under shard_map: the historical host-side
    ``int(total_new) == 0`` poll is now the loop predicate on the
    psum-replicated convergence scalar, so the run performs **zero host
    transfers** between BFS levels.  ``bound`` is a traced replicated
    scalar — chunked (checkpointing) calls reuse one executable."""
    carry = (frontier, fvalid, vhi, vlo, vpay, vcount, archive, archive_n,
             flags, step, total_new)

    def cond(c):
        return (c[-2] < bound) & (c[-1] > 0)

    def body(c):
        return _dense_body(comp, c, axis=axis, ndev=ndev,
                           max_branches=max_branches, send_cap=send_cap,
                           visited_cap=visited_cap, backend=backend)

    return jax.lax.while_loop(cond, body, carry)


# ---------------------------------------------------------------------------
# Neuron-axis sharded exploration (SystemPlan.num_shards == ndev)
# ---------------------------------------------------------------------------


def _psum_u32(x, axis):
    """psum for uint32 lanes: wraparound int32 all-reduce, bitcast back."""
    s = jax.lax.psum(jax.lax.bitcast_convert_type(x, jnp.int32), axis)
    return jax.lax.bitcast_convert_type(s, jnp.uint32)


def _sharded_body(arrs: ShardArrays, dense, carry, *, axis, ndev,
                  mloc, hmax, max_branches, visited_cap, backend):
    """Per-device body of the neuron-axis-sharded BFS level.

    Device ``d`` holds only the ``(F, mloc)`` neuron slice of the
    (replicated-membership) frontier; all *bookkeeping* (validity, branch
    counts, dedup verdicts, selection) is computed identically on every
    device from psum/all_gather-combined scalars, so the devices stay in
    lockstep without any O(m) exchange:

    1. local branch info on the slice; the mixed-radix strides cross shard
       boundaries, so each local stride is multiplied by the product of
       the *downstream* shards' branch totals (one ``all_gather`` of ndev
       scalars per config);
    2. fired produce/consume per local neuron; the halo exchange ships
       only the produce values along boundary-crossing synapses (static
       ``send_idx`` metadata from the plan) with one tiled ``all_to_all``;
    3. candidate slices = local slice + local delta, through the
       ``backend``'s step: the jnp sparse math (``ref``/``sparse``) or a
       fused kernel consuming the extended [local | halo] encoding
       (``pallas``/``sparse_pallas`` — DESIGN.md §3 "Kernel lowering");
       the collective stays out here, so kernel bodies hold no
       collectives and the halo values are backend-independent;
    4. global hashes from additive per-slice partials (one psum) — the
       zobrist positions are the shard's ``global_idx`` column map, so a
       degree-permuted partition hashes identically to a contiguous one;
       each device dedups the candidates it hash-owns against its local
       hash-table shard and the verdicts are psum-combined;
    5. every device appends the same selected candidates (its slice of
       them) to its archive shard.
    """
    (frontier, fvalid, vhi, vlo, vpay, vcount, archive, archive_n, flags,
     step, _) = carry
    F = frontier.shape[0]
    T = max_branches
    K = F * T
    V = visited_cap
    A = archive.shape[0]
    S = ndev
    idx = jax.lax.axis_index(axis)
    view = shard_view(arrs)

    # --- local branch info + cross-shard radix combine --------------------
    info = sparse_branch_info(frontier, view)
    tots = jax.lax.all_gather(info.psi, axis)                # (S, F)
    after = (jnp.arange(S) > idx)[:, None]
    below = jnp.prod(jnp.where(after, tots, 1.0), axis=0)    # (F,)
    psi = jnp.prod(tots, axis=0)                             # (F,) replicated
    stride = info.stride * below[:, None]
    alive = jax.lax.psum(
        jnp.any(info.app, axis=-1).astype(jnp.int32), axis) > 0

    t = jnp.arange(T, dtype=jnp.int32)

    # Dispatch on the concrete built-in kernel backends (their block/
    # interpret knobs are part of the contract here); any other backend
    # declaring 'sharded' — including third-party registrations — is
    # served by the jnp sparse math below, which every registered backend
    # must match bit-for-bit anyway (backend.py contract).
    if isinstance(backend, (PallasBackend, SparsePallasBackend)):
        # Kernel path: decode the fired produce only at the (static) send
        # positions — same f32 math on the same values as the full decode,
        # so the halo payload is bit-identical to the jnp path — exchange
        # it, then run the whole expansion inside the fused kernel.
        from repro.kernels.snp_step.ops import snp_step_dense_shard
        from repro.kernels.snp_step.sparse_ops import snp_step_sparse_shard

        send_ids = arrs.send_idx[0].reshape(-1)              # (S·hmax,)
        smask = send_ids < mloc
        sid = jnp.minimum(send_ids, mloc - 1)
        if isinstance(backend, SparsePallasBackend):
            # the sparse kernel consumes the whole table anyway
            tab = packed_rule_table(info, view)              # (F, mloc, R)
            tab_s = jnp.take(tab, sid, axis=1)               # (F, SH, R)
        else:
            # the dense kernel works from rank/app/M_local — build the
            # packed table only at the send positions (a subset view of
            # the per-neuron segments yields the same math per neuron)
            tab_s = packed_rule_table(
                info, view._replace(seg_start=view.seg_start[sid],
                                    seg_count=view.seg_count[sid]))
        sub = info._replace(stride=jnp.take(stride, sid, axis=-1),
                            choices=jnp.take(info.choices, sid, axis=-1))
        digits_s = _decode_digits(t, sub)                    # (F, T, SH)
        packed_s = _fired_packed(digits_s, tab_s)
        prod_send = jnp.where(smask[None, None, :], packed_s & 0xFFFF, 0)
        halo = jax.lax.all_to_all(
            prod_send.reshape(F, T, S, hmax), axis, 2, 2,
            tiled=True).reshape(F, T, S * hmax)
        if isinstance(backend, SparsePallasBackend):
            out = snp_step_sparse_shard(
                frontier, stride, info.choices, psi, tab, arrs.in_idx[0],
                halo, max_branches=T, block_b=backend.block_b,
                block_t=backend.block_t, interpret=backend.interpret)
        else:
            out = snp_step_dense_shard(
                frontier, info.rank, info.app, stride, info.choices, psi,
                dense.onehot[0], dense.M_local[0], dense.hadj[0], halo,
                max_branches=T, block_b=backend.block_b,
                block_t=backend.block_t, block_n=backend.block_n,
                interpret=backend.interpret)
        cand = out.reshape(K, mloc)
    else:
        # jnp path ("ref"/"sparse"): fired actions on the whole slice,
        # halo send gathered from the full produce table.
        tab = packed_rule_table(info, view)                  # (F, mloc, R)
        digits = _decode_digits(t, info._replace(stride=stride))
        packed_f = _fired_packed(digits, tab)                # (F, T, mloc)
        prod_f = packed_f & 0xFFFF
        cons_f = packed_f >> 16

        prod_pad = jnp.concatenate(
            [prod_f, jnp.zeros((F, T, 1), jnp.int32)], axis=-1)
        send = jnp.take(prod_pad, arrs.send_idx[0].reshape(-1), axis=-1)
        recv = jax.lax.all_to_all(
            send.reshape(F, T, S, hmax), axis, 2, 2, tiled=True)
        prod_ext = jnp.concatenate(
            [prod_f, recv.reshape(F, T, S * hmax),
             jnp.zeros((F, T, 1), jnp.int32)], axis=-1)
        delta = in_adjacency_sum(prod_ext, arrs.in_idx[0]) - cons_f
        cand = (frontier[:, None, :] + delta).reshape(K, mloc)
    valid = ((t[None, :].astype(jnp.float32) < psi[:, None])
             & alive[:, None] & fvalid[:, None]).reshape(K)
    branch_ovf = jnp.any((psi > float(T)) & fvalid)

    # --- global hashes from additive slice partials -----------------------
    hi, lo = zobrist_hash(cand, positions=arrs.global_idx[0])
    hi = jnp.where(valid, _psum_u32(hi, axis), SENTINEL)
    lo = jnp.where(valid, _psum_u32(lo, axis), SENTINEL)

    # --- dedup: each device judges the candidates it hash-owns against
    # its local table shard; verdicts psum-combine to the global new-mask
    owner = jnp.where(valid, (hi % np.uint32(S)).astype(jnp.int32), S)
    mine = owner == idx
    table = HashTable(vhi, vlo, vpay, vcount[0])
    found, _ = lookup(table, hi, lo, mine)
    first, ovf_f = first_occurrence(hi, lo, mine)
    new_local = mine & first & ~found
    new_mask = jax.lax.psum(new_local.astype(jnp.int32), axis) > 0

    # --- replicated selection + per-device state updates ------------------
    n_new = jnp.sum(new_mask, dtype=jnp.int32)
    order = jnp.argsort(jnp.logical_not(new_mask), stable=True)
    sel = order[:F]
    n_ins = jnp.minimum(n_new, F)
    ins = jnp.arange(F) < n_ins
    next_frontier = cand[sel]

    sel_mine = mine[sel] & ins
    n_mine = jnp.sum(sel_mine, dtype=jnp.int32)
    table, _, ovf_i = insert_unique(
        table, hi[sel], lo[sel], sel_mine,
        (archive_n + jnp.arange(F)).astype(jnp.int32))
    visited_ovf = ovf_f | ovf_i | ((vcount[0] + n_mine) > V)

    arch_idx = jnp.where(ins, archive_n + jnp.arange(F), A)
    archive = archive.at[arch_idx].set(next_frontier, mode="drop")
    archive_n = jnp.minimum(archive_n + n_ins, A)

    flags = flags | jnp.stack([branch_ovf, n_new > F, visited_ovf])[None, :]
    # n_ins is already the replicated global count (selection is replicated)
    return (next_frontier, ins, table.slots_hi, table.slots_lo,
            table.slot_payload, table.count[None], archive, archive_n,
            flags, step + 1, n_ins)


def _sharded_loop(arrs, dense, carry, bound, **kw):
    """Fused neuron-sharded BFS: one ``lax.while_loop`` over levels with
    the psum-replicated new-config count as the convergence predicate —
    zero host transfers until the frontier drains or ``bound`` absolute
    levels (same contract as :func:`_dense_loop`)."""

    def cond(c):
        return (c[-2] < bound) & (c[-1] > 0)

    def body(c):
        return _sharded_body(arrs, dense, c, **kw)

    return jax.lax.while_loop(cond, body, carry)


def _sharded_loop_dense(arrs, dense, *args, **kw):
    *state, bound = args
    return _sharded_loop(arrs, dense, tuple(state), bound, **kw)


def _sharded_loop_nodense(arrs, *args, **kw):
    *state, bound = args
    return _sharded_loop(arrs, None, tuple(state), bound, **kw)


def _explore_neuron_sharded(
    comp: ShardedCompiled, mesh: Mesh, axis: str, backend, *,
    max_steps: int, frontier_cap: int, visited_cap: int, max_branches: int,
    init: Optional[Sequence[int]] = None,
    checkpoint_dir: Optional[str] = None, checkpoint_every: int = 32,
    fault_injector=None,
) -> ExploreResult:
    """Host driver for the neuron-axis-sharded BFS.  ``frontier_cap`` is
    the *global* frontier width (its membership bookkeeping is replicated;
    only the neuron slices are per-device), ``visited_cap`` stays per
    device (hash-owned table shards, as in the dense-row scheme).
    ``backend`` (already resolved + ``lower``-ed into ``comp``) selects
    the per-shard step — jnp sparse math or a fused kernel (DESIGN.md
    §3).  All state is allocated device-side inside one jitted init (no
    host arrays scale with ``S·V``), and the BFS itself is the fused
    while-loop of :func:`_sharded_loop` — the host only syncs at chunk
    boundaries (checkpointing) or at final readout."""
    S, mloc = comp.num_shards, comp.shard_size
    F, V, T = frontier_cap, visited_cap, max_branches
    A = S * V   # global archive rows; each device stores its (A, mloc) slice
    SL = table_slots(V)
    arrs = comp.arrays
    m = comp.num_neurons

    shard = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())
    comp_specs = ShardArrays(
        rule_neuron=P(axis), consume=P(axis), produce=P(axis),
        regex_base=P(axis), regex_period=P(axis), covering=P(axis),
        seg_start=P(axis), seg_count=P(axis), rule_slots=P(),
        in_idx=P(axis), send_idx=P(axis), out_local=P(axis),
        init_loc=P(axis), global_idx=P(axis))

    def put(tree, specs):
        return jax.device_put(
            tree, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                               is_leaf=lambda x: isinstance(x, P)))

    arrs_dev = put(arrs, comp_specs)

    def _init(init_cols, gidx):
        # column-space init vector + one zobrist over the global position
        # map == the psum of the per-device slice hashes the loop computes
        hi0, lo0 = zobrist_hash(init_cols, positions=gidx)
        hic, loc = _canonical(hi0[None], lo0[None], jnp.ones((1,), bool))
        owner0 = (hic[0] % np.uint32(S)).astype(jnp.int32)
        base0 = _base_slot(hic, loc, SL).astype(jnp.int32)[0]
        init_slices = init_cols.reshape(S, mloc)
        frontier = jnp.zeros((S * F, mloc), jnp.int32).at[
            jnp.arange(S) * F].set(init_slices)
        fvalid = jnp.zeros((F,), bool).at[0].set(True)
        vhi = jnp.full((S * SL,), SENTINEL, jnp.uint32).at[
            owner0 * SL + base0].set(hic[0])
        vlo = jnp.full((S * SL,), SENTINEL, jnp.uint32).at[
            owner0 * SL + base0].set(loc[0])
        vpay = jnp.full((S * SL,), -1, jnp.int32).at[
            owner0 * SL + base0].set(0)
        vcount = jnp.zeros((S,), jnp.int32).at[owner0].set(1)
        archive = jnp.zeros((S * A, mloc), jnp.int32).at[
            jnp.arange(S) * A].set(init_slices)
        return (frontier, fvalid, vhi, vlo, vpay, vcount, archive,
                jnp.asarray(1, jnp.int32), jnp.zeros((S, 3), bool),
                jnp.asarray(0, jnp.int32), jnp.asarray(1, jnp.int32))

    state_shardings = (shard, repl, shard, shard, shard, shard, shard,
                       repl, shard, repl, repl)
    gidx = arrs.global_idx.reshape(-1)
    if init is None:
        init_cols = arrs.init_loc.reshape(-1)
    else:
        pad = S * mloc - m
        init_g = jnp.concatenate(
            [jnp.asarray(init, jnp.int32), jnp.zeros((pad,), jnp.int32)])
        init_cols = init_g[gidx]
    state = jax.jit(_init, out_shardings=state_shardings)(init_cols, gidx)

    kw = dict(axis=axis, ndev=S, mloc=mloc, hmax=comp.halo_width,
              max_branches=T, visited_cap=V, backend=backend)
    state_in = (P(axis), P(), P(axis), P(axis), P(axis), P(axis), P(axis),
                P(), P(axis), P(), P())
    state_out = state_in
    # The dense operands are the largest arrays in the scheme — only ship
    # them to devices when the selected backend's step actually consumes
    # them (a pre-lowered comp may carry them for a different backend).
    if comp.dense is not None and isinstance(backend, PallasBackend):
        # Dense kernel operands ride the same device axis as the shard
        # encodings (one slice per device).
        dense_specs = DenseShardArrays(
            M_local=P(axis), onehot=P(axis), hadj=P(axis))
        body = functools.partial(_sharded_loop_dense, **kw)
        in_specs = (comp_specs, dense_specs) + state_in + (P(),)
        lead = (arrs_dev, put(comp.dense, dense_specs))
    else:
        body = functools.partial(_sharded_loop_nodense, **kw)
        in_specs = (comp_specs,) + state_in + (P(),)
        lead = (arrs_dev,)

    loop_fn = jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=state_out,
            check_vma=False,
        ))

    state = _run_fused_loop(
        loop_fn, lead, state, max_steps=max_steps,
        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
        fault_injector=fault_injector)

    (_, _, _, _, _, _, archive, archive_n, flags, step,
     total_new) = jax.device_get(state)
    n = int(archive_n)
    if n:
        # columns back to global neuron order via the partition's
        # column→neuron map (identity for contiguous shards)
        cols = np.concatenate(list(archive.reshape(S, A, mloc)),
                              axis=1)[:n]
        configs = np.zeros((n, S * mloc), np.int32)
        configs[:, jax.device_get(gidx)] = cols
        configs = configs[:, :m]
    else:
        configs = np.zeros((0, m), np.int32)
    flags = flags.reshape(S, 3).any(axis=0)
    return ExploreResult(
        configs=configs,
        num_discovered=n,
        steps=int(step),
        exhausted=int(total_new) == 0 and not flags.any(),
        branch_overflow=bool(flags[0]),
        frontier_overflow=bool(flags[1]),
        visited_overflow=bool(flags[2]),
    )


def explore_distributed(
    system: SNPSystem | CompiledAny | ShardedCompiled,
    *,
    mesh: Optional[Mesh] = None,
    max_steps: int = 64,
    frontier_cap: int = 64,       # per device (global under a sharded plan)
    visited_cap: int = 2048,      # per device
    max_branches: int = 32,
    send_cap: Optional[int] = None,   # per (src,dst) pair
    init: Optional[Sequence[int]] = None,
    backend: Optional[BackendLike] = None,
    plan: Optional[SystemPlan] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 32,
    fault_injector=None,
) -> ExploreResult:
    """Hash-partitioned multi-device BFS.  Semantics identical to
    :func:`repro.core.engine.explore`; scaling is linear in devices for
    frontier/visited capacity and expansion FLOPs.

    ``checkpoint_dir``/``checkpoint_every`` snapshot the sharded device
    state between BFS levels (the host-driven per-step loop is the
    natural boundary) and resume from the latest snapshot on re-entry,
    exactly like the single-device :func:`~repro.core.engine.explore`;
    restored arrays are re-``device_put`` with the live mesh's shardings.
    ``fault_injector`` kills scheduled levels deterministically.

    ``backend`` selects the per-shard transition implementation (same
    registry as the single-chip engine — :mod:`repro.core.backend`); each
    device runs ``backend.expand`` on its frontier shard inside the
    shard_map body, so e.g. the fused Pallas kernel or the sparse ELL path
    serves the expansion on every chip with no changes here.

    ``plan`` (:class:`~repro.core.plan.SystemPlan`) selects the storage
    layout.  With ``plan.num_shards == ndev`` the run switches to the
    **neuron-axis-sharded** scheme (module docstring / DESIGN.md §2):
    every frontier/archive row carries only its device's neuron slice and
    the per-step exchange is the static halo of boundary-crossing
    synapses, ``O(touched)`` instead of ``O(m)``.  Any backend whose
    lowering registry declares ``"sharded"`` serves that path — the jnp
    sparse math (``"ref"``/``"sparse"``) or the fused kernels consuming a
    shard's extended-index encoding (``"pallas"``/``"sparse_pallas"``,
    DESIGN.md §3 "Kernel lowering"); ``frontier_cap`` is then the global
    frontier width.

    ``backend=None`` (the default) hands the choice to the query planner
    under the default ``SystemPlan(mode="auto")``, exactly like the
    single-device :func:`~repro.core.engine.explore` — the planner only
    picks sharded-capable backends when ``plan.num_shards > 1``."""
    mesh, axis = _flat_mesh(mesh)
    ndev = mesh.devices.size
    # resolve_entry also folds plan.kernel into the backend instance, and
    # the backend instance is what keys every downstream executable cache
    # (jit static args here, _traces_shard_fn's lru key below) — so two
    # block configurations can never collide into one cached executable.
    be, plan = resolve_entry(system, backend, plan,
                             workload=(frontier_cap, max_branches))
    sharded_plan = plan.num_shards > 1
    if is_sharded(system) or sharded_plan:
        if is_sharded(system):
            comp = system
        else:
            if not isinstance(system, SNPSystem):
                raise ValueError(
                    "neuron-axis sharded exploration needs the SNPSystem "
                    "(or a pre-lowered ShardedCompiled), not a single-"
                    f"device encoding ({type(system).__name__})")
            comp = compile_sharded(system, plan)
        if comp.num_shards != ndev:
            raise ValueError(
                f"plan.num_shards ({comp.num_shards}) must equal the mesh "
                f"device count ({ndev}); build the plan with "
                "sharding.specs.neuron_axis(ndev)")
        if not supports_sharded(be):
            raise ValueError(
                f"backend {be.name!r} does not declare the 'sharded' "
                "encoding in its lowering registry "
                "(StepBackend.supported_encodings), so it cannot step a "
                "neuron shard; every built-in backend supports it")
        comp = lower_with_backend(be, comp, comp.plan)
        return _explore_neuron_sharded(
            comp, mesh, axis, be, max_steps=max_steps,
            frontier_cap=frontier_cap, visited_cap=visited_cap,
            max_branches=max_branches, init=init,
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            fault_injector=fault_injector)
    comp = lower_with_backend(be, system, plan) if is_compiled(system) \
        else compile_with_plan(be, system, plan)
    m = comp.num_neurons
    F, V, T = frontier_cap, visited_cap, max_branches
    C = send_cap if send_cap is not None else max(16, (F * T) // max(ndev, 1))

    SL = table_slots(V)
    c0 = comp.init_config if init is None else jnp.asarray(init, jnp.int32)

    # global state, sharded on the leading device axis; everything is
    # allocated (and the init config hashed + table-inserted) inside one
    # jitted init — no host-side O(ndev·V) arrays, no host hashing.
    shard = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())

    def _init(c0):
        hi0, lo0 = config_hash(c0)
        hic, loc = _canonical(hi0[None], lo0[None], jnp.ones((1,), bool))
        owner0 = (hic[0] % np.uint32(ndev)).astype(jnp.int32)
        base0 = _base_slot(hic, loc, SL).astype(jnp.int32)[0]
        frontier = jnp.zeros((ndev * F, m), jnp.int32).at[owner0 * F].set(c0)
        fvalid = jnp.zeros((ndev * F,), bool).at[owner0 * F].set(True)
        vhi = jnp.full((ndev * SL,), SENTINEL, jnp.uint32).at[
            owner0 * SL + base0].set(hic[0])
        vlo = jnp.full((ndev * SL,), SENTINEL, jnp.uint32).at[
            owner0 * SL + base0].set(loc[0])
        vpay = jnp.full((ndev * SL,), -1, jnp.int32).at[
            owner0 * SL + base0].set(0)
        vcount = jnp.zeros((ndev,), jnp.int32).at[owner0].set(1)
        archive = jnp.zeros((ndev * V, m), jnp.int32).at[owner0 * V].set(c0)
        arch_n = jnp.zeros((ndev,), jnp.int32).at[owner0].set(1)
        return (frontier, fvalid, vhi, vlo, vpay, vcount, archive, arch_n,
                jnp.zeros((ndev, 3), bool), jnp.asarray(0, jnp.int32),
                jnp.asarray(1, jnp.int32))

    state_shardings = (shard,) * 9 + (repl, repl)
    state = jax.jit(_init, out_shardings=state_shardings)(c0)

    state_in = (P(axis),) * 9 + (P(), P())
    loop_fn = jax.jit(
        shard_map(
            functools.partial(_dense_loop, axis=axis, ndev=ndev,
                              max_branches=T, send_cap=C, visited_cap=V,
                              backend=be),
            mesh=mesh,
            in_specs=(P(),) + state_in + (P(),),
            out_specs=state_in,
            # pallas_call has no replication rule; every output spec is
            # explicit anyway, so the check adds nothing here.
            check_vma=False,
        ))

    state = _run_fused_loop(
        loop_fn, (comp,), state, max_steps=max_steps,
        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
        fault_injector=fault_injector)

    (_, _, _, _, _, _, archive, arch_n, flags, step,
     total_new) = jax.device_get(state)
    configs = np.concatenate([
        archive[d * V: d * V + int(arch_n[d])] for d in range(ndev)
    ]) if arch_n.sum() else np.zeros((0, m), np.int32)
    flags = flags.reshape(ndev, 3).any(axis=0)
    return ExploreResult(
        configs=configs,
        num_discovered=int(arch_n.sum()),
        steps=int(step),
        exhausted=int(total_new) == 0 and not flags.any(),
        branch_overflow=bool(flags[0]),
        frontier_overflow=bool(flags[1]),
        visited_overflow=bool(flags[2]),
    )


# ---------------------------------------------------------------------------
# Distributed trace serving: data-parallel run_traces over the mesh
# ---------------------------------------------------------------------------


def run_traces_distributed(
    system: SNPSystem | CompiledAny, *, steps: int,
    seeds: Sequence[int] | np.ndarray | jnp.ndarray,
    policy: str = "first", max_branches: int = 64,
    backend: Optional[BackendLike] = None,
    mesh: Optional[Mesh] = None,
    plan: Optional[SystemPlan] = None,
):
    """Mesh-sharded :func:`repro.core.engine.run_traces` (DESIGN.md §4).

    Trajectories are independent, so serving a batch over ``ndev`` devices
    is pure data parallelism: the batch axis is sharded over the (flattened)
    mesh, each device runs the same per-shard ``lax.scan``, and no
    collectives are needed.  Per-trace PRNG keys mean trace ``b`` depends
    only on ``seeds[b]``, so the result is **bit-identical** to the
    single-device :func:`~repro.core.engine.run_traces` — padding the batch
    up to a mesh multiple (with seed-0 dummies, sliced off on return) is
    therefore free.

    Returns a :class:`~repro.core.engine.TraceOut` of ``(configs
    (B, steps, m), emissions (B, steps), alive (B, steps),
    branch_overflow (B, steps))`` with ``B = len(seeds)``, exactly like
    the single-device path.
    """
    if policy not in ("first", "random"):
        raise ValueError(f"unknown policy {policy!r}")
    if plan is not None and plan.num_shards > 1:
        raise ValueError("trace serving shards the batch axis, not the "
                         "neuron axis; plan.num_shards > 1 is only "
                         "consumed by explore_distributed")
    seeds = np.asarray(seeds, np.uint32)
    if seeds.ndim != 1:
        raise ValueError(f"seeds must be 1-D, got shape {seeds.shape}")
    # The planner decides when backend=None (default SystemPlan mode
    # "auto"); _traces_shard_fn's lru cache keys on the resolved backend
    # *instance*, so a plan kernel's block shape is part of the key.
    be, plan, planned = resolve_entry_info(
        system, backend, plan, workload=(int(seeds.shape[0]), max_branches))
    mesh, axis = _flat_mesh(mesh)
    ndev = mesh.devices.size

    B = seeds.shape[0]
    Bp = ((max(B, 1) + ndev - 1) // ndev) * ndev
    padded = np.zeros((Bp,), np.uint32)
    padded[:B] = seeds
    keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(padded))     # (Bp, 2)

    def attempt(be, plan):
        comp = lower_with_backend(be, system, plan) if is_compiled(system) \
            else compile_with_plan(be, system, plan)
        c0s = jnp.broadcast_to(comp.init_config,
                               (Bp,) + comp.init_config.shape)   # (Bp, m)
        fn = _traces_shard_fn(mesh, axis, steps, max_branches, policy, be)
        out = fn(comp, c0s, keys)
        jax.block_until_ready(out.configs)
        return out

    out = run_with_failover(attempt, be, plan, degradable=planned)
    return TraceOut(*(x[:B] for x in out))


@functools.lru_cache(maxsize=128)
def _traces_shard_fn(mesh, axis, steps, max_branches, policy, backend):
    """One jitted shard_map per (mesh, statics): meshes compare by value,
    so a service calling with an equal mesh every flush reuses the
    executable instead of re-tracing per call."""
    return jax.jit(
        shard_map(
            functools.partial(_traces_scan, steps=steps,
                              max_branches=max_branches, policy=policy,
                              backend=backend),
            mesh=mesh,
            in_specs=(P(), P(axis), P(axis)),
            # one spec broadcast over every TraceOut leaf (batch-sharded)
            out_specs=P(axis),
            # same reasoning as explore_distributed: pallas_call has no
            # replication rule, and every output spec is explicit anyway
            check_vma=False,
        ))
