"""Device-resident hash-table dedup (``core.hashtable``).

Three layers:

* direct primitives — batched insert-if-absent verdicts, idempotence,
  probe wraparound at high load factor, the sentinel-key remap, and the
  bounded-probe overflow flag at capacity;
* hypothesis differential — insert-if-absent over random key batches
  (with forced duplicates and the sentinel key) against a Python dict
  oracle replaying the same first-occurrence rule;
* engine equivalence — ``explore(dedup="hash")`` must reproduce the
  sort-based archive **bit-for-bit** (row order included: the
  first-occurrence claim reproduces the stable sort's lowest-index
  winner) across the backend x encoding x semantics registry matrix.
"""

import numpy as np
import pytest

import conftest
from repro.core import SystemPlan, explore, paper_pi
from repro.core.generators import power_law, random_system
from repro.core.hashing import SENTINEL
from repro.core.hashtable import (insert_if_absent, lookup, make_table,
                                  table_slots)


def _keys(rng, n):
    return (rng.integers(0, 2**32, size=n, dtype=np.uint64).astype(np.uint32),
            rng.integers(0, 2**32, size=n, dtype=np.uint64).astype(np.uint32))


# ---------------------------------------------------------------------------
# direct primitives
# ---------------------------------------------------------------------------


def test_insert_if_absent_first_occurrence_and_idempotence():
    hi = np.array([1, 2, 1, 3, 2, 1], np.uint32)
    lo = np.array([9, 9, 9, 9, 9, 9], np.uint32)
    valid = np.ones(6, bool)
    table = make_table(16)
    table, new, ovf = insert_if_absent(table, hi, lo, valid)
    # lowest-index occurrence of each distinct key wins, duplicates lose
    np.testing.assert_array_equal(np.asarray(new),
                                  [True, True, False, True, False, False])
    assert not bool(ovf)
    assert int(table.count) == 3
    # re-inserting the same batch is a no-op
    table, new2, ovf = insert_if_absent(table, hi, lo, valid)
    assert not np.asarray(new2).any() and not bool(ovf)
    assert int(table.count) == 3
    found, _ = lookup(table, hi, lo, valid)
    assert np.asarray(found).all()


def test_invalid_lanes_never_insert():
    hi = np.array([5, 6, 7], np.uint32)
    lo = np.array([5, 6, 7], np.uint32)
    valid = np.array([True, False, True])
    table, new, _ = insert_if_absent(make_table(8), hi, lo, valid)
    np.testing.assert_array_equal(np.asarray(new), [True, False, True])
    assert int(table.count) == 2
    found, _ = lookup(table, hi, lo, np.ones(3, bool))
    np.testing.assert_array_equal(np.asarray(found), [True, False, True])


def test_sentinel_key_is_a_real_storable_key():
    """(SENTINEL, SENTINEL) is remapped away from the empty-slot marker,
    so a config whose hash happens to be all-ones still dedups."""
    hi = np.array([SENTINEL, SENTINEL], np.uint32)
    lo = np.array([SENTINEL, SENTINEL], np.uint32)
    table, new, _ = insert_if_absent(make_table(8), hi, lo,
                                     np.ones(2, bool))
    np.testing.assert_array_equal(np.asarray(new), [True, False])
    table, new2, _ = insert_if_absent(table, hi, lo, np.ones(2, bool))
    assert not np.asarray(new2).any()
    # the remap deliberately aliases (S, S) onto (S, S-1) — one extra
    # 2^-64-grade collision pair, not a correctness hole: the alias
    # dedups consistently rather than colliding with the empty marker
    lo2 = np.array([SENTINEL - 1], np.uint32)
    _, new3, _ = insert_if_absent(table, hi[:1], lo2, np.ones(1, bool))
    assert not np.asarray(new3).any()


def test_probe_wraparound_at_high_load():
    """Fill a tiny table close to its slot count: probes must wrap past
    the end of the array and still find empty slots / prior keys."""
    rng = np.random.default_rng(7)
    n = 12   # table_slots(12) == 32 slots, load 0.375 after one batch
    hi, lo = _keys(rng, n)
    table = make_table(n)
    table, new, ovf = insert_if_absent(table, hi, lo, np.ones(n, bool))
    assert np.asarray(new).all() and not bool(ovf)
    # a second distinct batch drives load towards 0.75 — still no flag
    hi2, lo2 = _keys(rng, n)
    table, new2, ovf = insert_if_absent(table, hi2, lo2, np.ones(n, bool))
    assert np.asarray(new2).all() and not bool(ovf)
    for h, l in ((hi, lo), (hi2, lo2)):
        found, _ = lookup(table, h, l, np.ones(n, bool))
        assert np.asarray(found).all()


def test_overflow_flag_at_capacity():
    """Driving the table past its slot count must raise the overflow
    flag (bounded probes give up) instead of looping or silently
    corrupting earlier entries."""
    rng = np.random.default_rng(3)
    table = make_table(4)          # 16 slots
    S = table.num_slots
    hi, lo = _keys(rng, 4 * S)
    table, _, ovf = insert_if_absent(table, hi, lo, np.ones(4 * S, bool))
    assert bool(ovf)
    assert int(table.count) <= S
    # keys reported found must really be present (flag, not corruption)
    found, _ = lookup(table, hi[:8], lo[:8], np.ones(8, bool))
    refound, _ = lookup(table, hi[:8], lo[:8], np.ones(8, bool))
    np.testing.assert_array_equal(np.asarray(found), np.asarray(refound))


def test_payloads_roundtrip():
    rng = np.random.default_rng(11)
    hi, lo = _keys(rng, 20)
    pay = np.arange(100, 120, dtype=np.int32)
    table, new, _ = insert_if_absent(make_table(32), hi, lo,
                                     np.ones(20, bool), payload=pay)
    assert np.asarray(new).all()
    found, got = lookup(table, hi, lo, np.ones(20, bool))
    assert np.asarray(found).all()
    np.testing.assert_array_equal(np.asarray(got), pay)


def test_table_slots_sizing():
    assert table_slots(4) == 16
    for cap in (5, 100, 2048, 4097):
        s = table_slots(cap)
        assert s >= 2 * cap and (s & (s - 1)) == 0
    with pytest.raises(ValueError, match="capacity"):
        table_slots(0)


def test_insert_if_absent_is_one_jittable_call():
    """The whole batched insert-if-absent traces as one jitted program
    (the table is a pytree carry)."""
    import jax
    rng = np.random.default_rng(5)
    hi, lo = _keys(rng, 16)
    fn = jax.jit(insert_if_absent)
    t2, new, ovf = fn(make_table(64), hi, lo, np.ones(16, bool))
    assert np.asarray(new).all() and not bool(ovf)
    assert int(t2.count) == 16


# ---------------------------------------------------------------------------
# hypothesis differential against a dict oracle
# ---------------------------------------------------------------------------


def test_insert_if_absent_matches_dict_oracle_property():
    hypothesis = pytest.importorskip(
        "hypothesis", reason="property tests need hypothesis")
    from hypothesis import given, settings, strategies as st

    key = st.tuples(st.integers(0, 2**32 - 1), st.integers(0, 2**32 - 1))
    # small key universe forces duplicates within and across batches;
    # always include the sentinel key once in the pool
    pool = st.lists(key, min_size=1, max_size=8).map(
        lambda ks: ks + [(int(SENTINEL), int(SENTINEL))])

    @settings(max_examples=40, deadline=None)
    @given(pool=pool, data=st.data())
    def run(pool, data):
        batches = data.draw(st.lists(
            st.lists(st.sampled_from(pool), min_size=1, max_size=12),
            min_size=1, max_size=4))
        table = make_table(64)
        seen = {}
        for batch in batches:
            hi = np.array([k[0] for k in batch], np.uint32)
            lo = np.array([k[1] for k in batch], np.uint32)
            table, new, ovf = insert_if_absent(table, hi, lo,
                                               np.ones(len(batch), bool))
            assert not bool(ovf)
            want = []
            batch_seen = set()
            for k in batch:
                fresh = k not in seen and k not in batch_seen
                want.append(fresh)
                if fresh:
                    seen[k] = True
                batch_seen.add(k)
            np.testing.assert_array_equal(np.asarray(new), want)
            assert int(table.count) == len(seen)

    run()


# ---------------------------------------------------------------------------
# engine equivalence: hash dedup == sort dedup, bit for bit
# ---------------------------------------------------------------------------


def _explore_both(system, *, plan=None, backend=None, **kw):
    a = explore(system, plan=plan, backend=backend, dedup="sort", **kw)
    b = explore(system, plan=plan, backend=backend, dedup="hash", **kw)
    return a, b


def _assert_same_result(a, b):
    np.testing.assert_array_equal(np.asarray(a.configs),
                                  np.asarray(b.configs))
    assert a.num_discovered == b.num_discovered
    assert a.steps == b.steps
    assert a.exhausted == b.exhausted
    assert (a.branch_overflow, a.frontier_overflow) == \
        (b.branch_overflow, b.frontier_overflow)


def test_hash_explore_bit_identical_registry_matrix(lowering_cell):
    """Row-for-row archive identity across every (backend, encoding,
    semantics) registry cell: the scatter-min first-occurrence claim
    must reproduce the stable sort's lowest-index winner everywhere."""
    name, plan = lowering_cell
    system = random_system(9, 2, 0.3, seed=1)
    if plan.semantics == "delays":
        system = conftest.delayed_variant(system)
    a, b = _explore_both(system, plan=plan, backend=name, max_steps=6,
                         frontier_cap=64, visited_cap=1024, max_branches=32)
    _assert_same_result(a, b)
    assert a.visited_overflow == b.visited_overflow


def test_hash_explore_bit_identical_under_overflow():
    """Branch + frontier overflow regime: truncation verdicts (which
    candidates survive into the frontier) must also agree, or the two
    paths would explore different subtrees."""
    a, b = _explore_both(power_law(40, 3, seed=3), max_steps=8,
                         frontier_cap=32, visited_cap=4096, max_branches=8)
    assert a.branch_overflow and a.frontier_overflow
    _assert_same_result(a, b)


def test_hash_explore_exhausts_finite_system():
    from repro.core.generators import counter
    a, b = _explore_both(counter(5), max_steps=48, frontier_cap=64,
                         visited_cap=512, max_branches=16)
    assert b.exhausted
    _assert_same_result(a, b)


def test_hash_explore_paper_pi():
    a, b = _explore_both(paper_pi(True), max_steps=32, frontier_cap=64,
                         visited_cap=512, max_branches=16)
    _assert_same_result(a, b)


def test_hash_explore_visited_overflow_is_flagged_and_sound():
    """Past the visited capacity the two drop policies legitimately
    differ; both must flag, and the hash archive must stay a subset of
    the truth."""
    system = power_law(40, 3, seed=3)
    big = explore(system, max_steps=8, frontier_cap=32,
                  visited_cap=65536, max_branches=8, dedup="sort")
    truth = {tuple(r) for r in np.asarray(big.configs)}
    for dedup in ("sort", "hash"):
        r = explore(system, max_steps=8, frontier_cap=32, visited_cap=64,
                    max_branches=8, dedup=dedup)
        assert r.visited_overflow
        assert {tuple(r) for r in np.asarray(r.configs)} <= truth


def test_explore_rejects_unknown_dedup():
    with pytest.raises(ValueError, match="dedup"):
        explore(paper_pi(True), dedup="bloom")


def test_dedup_auto_resolution():
    """The default picks the table only when the visited capacity clears
    the absolute floor AND dominates the wave; explicit modes pass
    through untouched (both produce identical archives, so the rule only
    moves wall-time)."""
    from repro.core.engine import resolve_dedup

    # counter/power-law shape: tiny wave, big visited capacity -> table
    assert resolve_dedup("auto", frontier_cap=16, visited_cap=16384,
                         max_branches=8) == "hash"
    # paper-pi tree-bench shape: wave as big as the capacity -> sort
    assert resolve_dedup("auto", frontier_cap=128, visited_cap=2048,
                         max_branches=16) == "sort"
    # big capacity but wave-dominated (pi_x4 shape) -> sort
    assert resolve_dedup("auto", frontier_cap=512, visited_cap=16384,
                         max_branches=64) == "sort"
    # small capacity never takes the table, however tiny the wave
    assert resolve_dedup("auto", frontier_cap=1, visited_cap=8192,
                         max_branches=1) == "sort"
    for explicit in ("hash", "sort"):
        assert resolve_dedup(explicit, frontier_cap=1, visited_cap=1,
                             max_branches=1) == explicit
    with pytest.raises(ValueError, match="dedup"):
        resolve_dedup("bloom", frontier_cap=1, visited_cap=1, max_branches=1)


def test_dedup_auto_bit_identical_to_both():
    sys_ = random_system(9, 2, 0.3, seed=1)
    kw = dict(max_steps=6, frontier_cap=64, visited_cap=1024, max_branches=16)
    auto = explore(sys_, dedup="auto", **kw)
    for explicit in ("sort", "hash"):
        ref = explore(sys_, dedup=explicit, **kw)
        assert auto.num_discovered == ref.num_discovered
        np.testing.assert_array_equal(
            auto.configs[:auto.num_discovered],
            ref.configs[:ref.num_discovered])


@pytest.mark.parametrize("dedup", ["hash", "sort"])
def test_explore_call_from_a_new_init_compiles_nothing(dedup, caplog):
    """A second ``explore`` call of the same shapes from another initial
    configuration compiles nothing: the initial state, the hash table's
    first insert included, is one program per shape."""
    import logging

    import jax
    system = power_law(60, 3, seed=2)
    kw = dict(max_steps=4, frontier_cap=8, visited_cap=512, max_branches=4,
              backend="sparse", dedup=dedup)
    init = np.asarray(system.initial_spikes)
    explore(system, init=init, **kw)
    jax.config.update("jax_log_compiles", True)
    try:
        with caplog.at_level(logging.WARNING, logger="jax"):
            explore(system, init=init[::-1], **kw)
    finally:
        jax.config.update("jax_log_compiles", False)
    assert not [r for r in caplog.records
                if "Compiling" in r.getMessage()], caplog.text
