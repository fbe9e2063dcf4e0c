"""Native C++ kv index: parity vs the python fallback + edge cases."""

import numpy as np
import pytest

from paddlebox_tpu.native import load_native
from paddlebox_tpu.ps.kv import (NativeKV, PyKV, TableFullError,
                                 dedup_first_seen_native)
from paddlebox_tpu.ps.table import _dedup_first_seen_py, dedup_first_seen


requires_native = pytest.mark.skipif(load_native() is None,
                                     reason="native lib unavailable")


@requires_native
def test_native_matches_python_randomized():
    rng = np.random.default_rng(0)
    nat = NativeKV(5000, load_native())
    py = PyKV(5000)
    for _ in range(20):
        keys = rng.integers(0, 3000, size=500).astype(np.uint64)
        np.testing.assert_array_equal(nat.assign(keys), py.assign(keys))
        probe = rng.integers(0, 6000, size=200).astype(np.uint64)
        np.testing.assert_array_equal(nat.lookup(probe), py.lookup(probe))
        rel = rng.integers(0, 3000, size=50).astype(np.uint64)
        # release order of freed rows differs is fine; compare sets + len
        r1, r2 = nat.release(rel), py.release(rel)
        assert sorted(r1.tolist()) == sorted(r2.tolist())
        assert len(nat) == len(py)
    k1, _ = nat.items()
    k2, _ = py.items()
    np.testing.assert_array_equal(np.sort(k1), np.sort(k2))


@requires_native
def test_native_edge_keys_and_reuse():
    nat = NativeKV(8, load_native())
    edge = np.array([0, 1, 2**64 - 1, 2**64 - 2], dtype=np.uint64)
    rows = nat.assign(edge)
    assert len(set(rows.tolist())) == 4
    np.testing.assert_array_equal(nat.assign(edge), rows)  # stable
    np.testing.assert_array_equal(nat.lookup(edge), rows)
    freed = nat.release(edge[:2])
    assert len(freed) == 2 and len(nat) == 2
    # released keys gone; rows recycled for new keys
    assert nat.lookup(edge[:1])[0] == -1
    r_new = nat.assign(np.array([12345], np.uint64))
    assert r_new[0] in freed


@requires_native
def test_native_capacity_exhaustion():
    nat = NativeKV(4, load_native())
    nat.assign(np.arange(4, dtype=np.uint64))
    with pytest.raises(TableFullError):
        nat.assign(np.array([99], np.uint64))
    # failed assign must not corrupt: existing keys still resolve
    assert all(nat.lookup(np.arange(4, dtype=np.uint64)) >= 0)


@requires_native
def test_native_churn_tombstone_rehash():
    """assign/release churn must not exhaust EMPTY slots (probe-loop hang)
    and must keep mappings exact across tombstone-triggered rehashes."""
    nat = NativeKV(64, load_native())
    py = PyKV(64)
    rng = np.random.default_rng(2)
    for round_ in range(200):  # 200*50 >> bucket count → many rehashes
        keys = (rng.integers(0, 2**62, size=50) + round_ * 1000).astype(np.uint64)
        r1, r2 = nat.assign(keys), py.assign(keys)
        assert len(set(r1.tolist())) == len(set(r2.tolist()))
        nat.release(keys)
        py.release(keys)
    assert len(nat) == 0
    # survivors after churn still resolve exactly
    keep = rng.integers(0, 2**62, size=40).astype(np.uint64)
    rows = nat.assign(keep)
    for round_ in range(100):
        junk = (rng.integers(2**62, 2**63, size=20)).astype(np.uint64)
        nat.assign(junk)
        nat.release(junk)
    np.testing.assert_array_equal(nat.lookup(keep), rows)


@requires_native
def test_assign_unique_matches_python():
    """Fused dedup+assign: same row mapping and a consistent inverse on
    both backends (unique ORDER may differ — native is first-occurrence,
    python is sorted — so compare through the maps they induce)."""
    rng = np.random.default_rng(3)
    nat = NativeKV(5000, load_native())
    py = PyKV(5000)
    for _ in range(10):
        keys = rng.integers(0, 800, size=600).astype(np.uint64)
        r1, inv1 = nat.assign_unique(keys)
        r2, inv2 = py.assign_unique(keys)
        assert len(r1) == len(r2) == len(np.unique(keys))
        # rows are dup-free within a call
        assert len(set(r1.tolist())) == len(r1)
        # the induced key→row map agrees with each backend's plain assign
        # (row NUMBERING differs across backends — first-occurrence vs
        # sorted assignment order — so no cross-backend row equality)
        np.testing.assert_array_equal(r1[inv1], nat.assign(keys))
        np.testing.assert_array_equal(r2[inv2], py.assign(keys))


@requires_native
def test_assign_unique_row_reuse_after_release():
    """Epoch scratch must not leak stale seen marks across calls when rows
    are released and reassigned to different keys."""
    nat = NativeKV(64, load_native())
    a = np.array([1, 2, 3], np.uint64)
    r_a, _ = nat.assign_unique(a)
    nat.release(a)
    b = np.array([7, 8, 9, 7], np.uint64)
    r_b, inv_b = nat.assign_unique(b)
    assert sorted(r_b.tolist()) == sorted(r_a.tolist())  # rows recycled
    assert len(r_b) == 3 and inv_b[0] == inv_b[3]
    np.testing.assert_array_equal(r_b[inv_b], nat.lookup(b))


@requires_native
def test_assign_unique_table_full_midway():
    nat = NativeKV(2, load_native())
    with pytest.raises(TableFullError):
        nat.assign_unique(np.array([1, 1, 2, 3], np.uint64))
    # keys assigned before the failure still resolve
    assert nat.lookup(np.array([1], np.uint64))[0] >= 0


@requires_native
def test_lookup_unique_miss_collapse():
    """Unknown keys share one sentinel entry; known keys resolve exactly;
    an all-miss batch yields a single sentinel unique."""
    sent = 9999
    nat = NativeKV(64, load_native())
    py = PyKV(64)
    known = np.array([10, 20, 30], np.uint64)
    nat.assign(known)
    py.assign(known)
    probe = np.array([20, 555, 10, 666, 20, 555], np.uint64)
    r1, inv1 = nat.lookup_unique(probe, sent)
    r2, inv2 = py.lookup_unique(probe, sent)
    np.testing.assert_array_equal(r1[inv1], r2[inv2])
    assert (r1[inv1][[1, 3, 5]] == sent).all()
    # native collapses all misses into one unique slot
    assert (r1 == sent).sum() == 1
    # all-miss batch
    r3, inv3 = nat.lookup_unique(np.array([777, 888], np.uint64), sent)
    assert len(r3) == 1 and r3[0] == sent and (inv3 == 0).all()


def test_pykv_lookup_unique_miss_collapse():
    """Python fallback must honor the same miss-collapse contract as the
    native index (duplicate-free unique rows for the scatter promise)."""
    py = PyKV(64)
    py.assign(np.array([10, 20, 30], np.uint64))
    probe = np.array([20, 555, 10, 666, 20, 555], np.uint64)
    r, inv = py.lookup_unique(probe, 9999)
    assert (r == 9999).sum() == 1            # one shared sentinel entry
    assert len(set(r.tolist())) == len(r)    # duplicate-free
    got = r[inv]
    assert (got[[1, 3, 5]] == 9999).all()
    np.testing.assert_array_equal(
        got[[0, 2, 4]], py.lookup(np.array([20, 10, 20], np.uint64)))
    # all-miss batch
    r2, inv2 = py.lookup_unique(np.array([777, 888], np.uint64), 9999)
    assert len(r2) == 1 and r2[0] == 9999 and (inv2 == 0).all()


def _arena_invariants(kv, chunk_bits, n_slots, keys, slots):
    rows, locs = kv.assign_slotted(keys, slots)
    cs_map, cr_map = kv.arena_export()
    cb = chunk_bits
    # every row decodes back through (slot, local) and the chunk map
    assert (locs >= 0).all()
    chunk_of = rows >> cb
    np.testing.assert_array_equal(cs_map[chunk_of], slots.astype(np.int32))
    recon = (cr_map[chunk_of] << cb) | (rows & ((1 << cb) - 1))
    np.testing.assert_array_equal(recon, locs)
    # stable on re-assign
    rows2, locs2 = kv.assign_slotted(keys, slots)
    np.testing.assert_array_equal(rows, rows2)
    np.testing.assert_array_equal(locs, locs2)
    return rows, locs


@pytest.mark.parametrize("impl", ["native", "py"])
def test_arena_slotted_assign_roundtrip(impl):
    if impl == "native" and load_native() is None:
        pytest.skip("native lib unavailable")
    kv = (NativeKV(1 << 12, load_native()) if impl == "native"
          else PyKV(1 << 12))
    kv.arena_enable(4, 8)  # 16-row chunks, 8 slots
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 500, size=400).astype(np.uint64)
    slots = (keys % 8).astype(np.uint16)  # slot stable per key
    _arena_invariants(kv, 4, 8, keys, slots)


@pytest.mark.parametrize("impl", ["native", "py"])
def test_arena_foreign_row_flags_minus_one(impl):
    if impl == "native" and load_native() is None:
        pytest.skip("native lib unavailable")
    kv = (NativeKV(256, load_native()) if impl == "native" else PyKV(256))
    kv.arena_enable(4, 4)
    k = np.array([7, 8], np.uint64)
    kv.assign(k)  # slotless → default arena
    rows, locs = kv.assign_slotted(k, np.array([1, 2], np.uint16))
    assert (locs == -1).all()  # foreign rows are flagged, not mislabeled
    # fresh keys under the right slot are fine
    rows2, locs2 = kv.assign_slotted(np.array([9], np.uint64),
                                     np.array([1], np.uint16))
    assert locs2[0] >= 0


@pytest.mark.parametrize("impl", ["native", "py"])
def test_arena_release_reuses_within_slot(impl):
    if impl == "native" and load_native() is None:
        pytest.skip("native lib unavailable")
    kv = (NativeKV(256, load_native()) if impl == "native" else PyKV(256))
    kv.arena_enable(3, 4)
    keys = np.arange(20, dtype=np.uint64)
    slots = np.full(20, 2, np.uint16)
    rows, _ = kv.assign_slotted(keys, slots)
    kv.release(keys[:5])
    nk = np.arange(100, 105, dtype=np.uint64)
    nrows, nlocs = kv.assign_slotted(nk, np.full(5, 2, np.uint16))
    assert set(nrows.tolist()) == set(rows[:5].tolist())  # reused in-slot
    assert (nlocs >= 0).all()


def test_arena_enable_after_assign_raises():
    kv = PyKV(64)
    kv.assign(np.array([1], np.uint64))
    with pytest.raises(RuntimeError):
        kv.arena_enable(4, 4)
    if load_native() is not None:
        nv = NativeKV(64, load_native())
        nv.assign(np.array([1], np.uint64))
        with pytest.raises(RuntimeError):
            nv.arena_enable(4, 4)


@pytest.mark.parametrize("impl", ["native", "py"])
def test_arena_out_of_range_slot_clamps_to_default(impl):
    """Slot ids >= n_slots must clamp to the default arena (local = -1),
    never index out of bounds."""
    if impl == "native" and load_native() is None:
        pytest.skip("native lib unavailable")
    kv = (NativeKV(256, load_native()) if impl == "native" else PyKV(256))
    kv.arena_enable(4, 4)
    rows, locs = kv.assign_slotted(np.array([1, 2], np.uint64),
                                   np.array([100, 4], np.uint16))
    assert (locs == -1).all()
    assert (rows >= 0).all()
    # in-range keys still work afterwards (no corruption)
    r2, l2 = kv.assign_slotted(np.array([3], np.uint64),
                               np.array([1], np.uint16))
    assert l2[0] >= 0 and len(kv) == 3


def _make_streams():
    rng = np.random.default_rng(7)
    base = rng.integers(1, 2 ** 31, size=200).astype(np.uint64)
    return {
        # small vocab -> heavy duplicate + hash-collision pressure
        "collision_heavy": rng.integers(1, 40, size=400).astype(np.uint64),
        "zipf": np.minimum(rng.zipf(1.3, size=500), 4000).astype(np.uint64),
        "uniform_distinct": rng.choice(
            np.arange(1, 1 << 20, dtype=np.uint64), 300, replace=False),
        # ids identical mod 2^32 — a 32-bit-truncating hash or compare
        # would alias every pair
        "hi64_collide_mod32": np.concatenate(
            [base, base | (np.uint64(1) << np.uint64(33))]),
    }


#: adversarial key streams, shared with tests/test_host_key_index.py
STREAMS = _make_streams()


def test_native_dedup_matches_oracle():
    if dedup_first_seen_native(STREAMS["zipf"]) is None:
        pytest.skip("native kv library unavailable")
    for name, keys in STREAMS.items():
        uniq_o, first_o, inv_o = _dedup_first_seen_py(keys)
        uniq, first, inv = dedup_first_seen_native(keys)
        np.testing.assert_array_equal(uniq, uniq_o, err_msg=name)
        np.testing.assert_array_equal(first, first_o, err_msg=name)
        np.testing.assert_array_equal(inv, inv_o, err_msg=name)


def test_dedup_first_seen_public_route_matches_oracle():
    """The seam everyone calls (native when buildable, python
    otherwise) is bitwise against the oracle either way."""
    for name, keys in STREAMS.items():
        uniq_o, first_o, inv_o = _dedup_first_seen_py(keys)
        uniq, first, inv = dedup_first_seen(keys)
        np.testing.assert_array_equal(uniq, uniq_o, err_msg=name)
        np.testing.assert_array_equal(first, first_o, err_msg=name)
        np.testing.assert_array_equal(inv, inv_o, err_msg=name)
