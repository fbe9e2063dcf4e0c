"""The compact wire's host build walks the index with a pass's DISTINCT
(key, slot) pairs (ISSUE 34): what it builds, and what it leaves in the
index and in ``slot_host``, is what a walk of every key in stream order
gives — the oracle here, the build's own code until PR 34."""

import jax
import numpy as np
import pytest

from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
from paddlebox_tpu.ps.kv import PyKV
from paddlebox_tpu.ps.table import (_dedup_slotted_first_seen_py,
                                    dedup_slotted_first_seen)
from paddlebox_tpu.train import ResidentPass

CAP = 1 << 10


def _table(n_slots: int, chunk_bits: int, python_index: bool = False):
    t = EmbeddingTable(mf_dim=4, capacity=CAP, cfg=SparseSGDConfig(),
                       unique_bucket_min=64, arena_slots=n_slots,
                       arena_chunk_bits=chunk_bits)
    if python_index:
        t.index = PyKV(CAP)
        t.index.arena_enable(chunk_bits, n_slots)
    return t


def _register(table, keys, slots):
    rows, _ = table.index.assign_slotted(
        np.asarray(keys, np.uint64), np.asarray(slots, np.uint16))
    table.slot_host[rows] = slots


def _batches(keys, slots, sizes, k_max, segs=False):
    """``_front``'s per-batch views of one key stream."""
    out, a = [], 0
    for n in sizes:
        out.append((np.asarray(keys[a:a + n], np.uint64),
                    np.asarray(slots[a:a + n], np.int16), k_max, 999,
                    np.arange(n, dtype=np.int32) if segs else None))
        a += n
    assert a == len(keys)
    return out


def _oracle(per_batch, table):
    """Every key of the stream through ``assign_slotted``, in stream
    order (the bulk branch before PR 34) -> (locs, rows_g, any local
    outside its slot's arena)."""
    nb, k_max = len(per_batch), per_batch[0][2]
    locs = np.zeros((nb, k_max), np.int32)
    rows_g = np.full((nb, k_max), table.capacity + 1, np.int32)
    keys_all = np.concatenate([k for k, *_ in per_batch])
    slots_all = np.concatenate([s for _, s, *_ in per_batch])
    r_all, l_all = table.index.assign_slotted(
        keys_all, slots_all.astype(np.uint16))
    table.slot_host[r_all] = slots_all
    a = 0
    for i, (k, *_) in enumerate(per_batch):
        locs[i, :len(k)] = l_all[a:a + len(k)]
        rows_g[i, :len(k)] = r_all[a:a + len(k)]
        a += len(k)
    return locs, rows_g, bool((l_all < 0).any())


def _items(table):
    ks, rs = table.index.items()
    order = np.argsort(ks)
    return ks[order], rs[order]


def _compact(per_batch, table, trivial):
    nrec = sum(len(k) for k, *_ in per_batch)
    floats = np.zeros((len(per_batch), 4, 5), np.float32)
    return ResidentPass._compact_tail(
        per_batch, floats, None, trivial, nrec, table,
        jax.device_put(floats), jax.device_put(np.zeros((2, 0), np.float32)))


def _zipf(rng, vocab, n):
    return np.minimum((vocab + 1.0) ** rng.random(n) - 1.0,
                      vocab - 1).astype(np.int64)


def _stream(case: str):
    """-> (n_slots, chunk_bits, registered (keys, slots), the pass's
    (keys, slots, batch sizes, k_max, segs), expect a pass)."""
    rng = np.random.default_rng(34)
    if case in ("registered", "some_new", "python_index"):
        # 6 slots, disjoint key spaces, one key a slot a record, Zipf
        vocab, n_slots, recs = 60, 6, 96
        ids = _zipf(rng, vocab, recs * n_slots).reshape(recs, n_slots)
        slots = np.tile(np.arange(n_slots), recs)
        keys = (ids + np.arange(n_slots) * 1000).reshape(-1)
        vk = (np.arange(vocab)[None, :]
              + np.arange(n_slots)[:, None] * 1000).reshape(-1)
        vs = np.repeat(np.arange(n_slots), vocab)
        if case != "registered":   # every third id waits for the pass
            vk, vs = vk[vk % 3 != 0], vs[vk % 3 != 0]
        return (n_slots, 3, (vk, vs),
                (keys, slots, [192, 192, 192], 200, False), True)
    if case == "alternating_new":
        # nothing registered, chunks of 4 rows, new keys of three slots
        # interleaved unevenly: a slot's next chunk is claimed when ITS
        # chunk fills, between the others' claims
        slots = rng.choice(3, size=240, p=[0.6, 0.3, 0.1])
        keys = _zipf(rng, 50, 240) + slots * 1000
        return 3, 2, None, (keys, slots, [100, 100, 40], 104, True), True
    if case == "foreign_slot":
        # key 7 is slot 0's, then comes under slot 1, last of the stream
        slots = np.array([0, 1, 0, 1, 0, 1, 1, 1], np.int64)
        keys = np.array([7, 1001, 8, 1002, 7, 1001, 1003, 7], np.int64)
        return 2, 2, None, (keys, slots, [4, 4], 4, False), False
    if case == "slot_beyond_arena":
        slots = np.array([0, 1, 2, 1], np.int64)   # the arena has 2
        keys = np.array([1, 1001, 2001, 1002], np.int64)
        return 2, 2, None, (keys, slots, [4], 4, False), False
    if case == "one_slot_sequences":
        # the sequence cells' shape: one slot, one key a position, Zipf
        # ids, id 0 opening each document
        keys = _zipf(rng, 300, 512)
        keys[::37] = 0
        return (1, 4, (np.arange(300), np.zeros(300, np.int64)),
                (keys, np.zeros(512, np.int64), [256, 256], 256, False),
                True)
    raise ValueError(case)


@pytest.mark.parametrize("case", [
    "registered", "some_new", "alternating_new", "foreign_slot",
    "slot_beyond_arena", "one_slot_sequences", "python_index"])
def test_compact_tail_is_the_walk_of_every_key(case):
    n_slots, bits, registered, (keys, slots, sizes, k_max, segs), ok = \
        _stream(case)
    t_new = _table(n_slots, bits, case == "python_index")
    t_old = _table(n_slots, bits, case == "python_index")
    for t in (t_new, t_old):
        if registered is not None:
            _register(t, *registered)
    per_batch = _batches(keys, slots, sizes, k_max, segs)
    rp = _compact(per_batch, t_new, trivial=not segs)
    if case == "slot_beyond_arena":   # refused before the index is read
        assert rp is None and len(t_new.index) == 0
        return
    locs, rows_g, foreign = _oracle(per_batch, t_old)
    assert foreign == (not ok)
    for a, b in zip(_items(t_new), _items(t_old)):
        np.testing.assert_array_equal(a, b)
    if not ok:
        assert rp is None
        # the dedup wire takes the pass next; it starts by writing the
        # first-seen slot of every key of the pass
        for t in (t_new, t_old):
            t.bulk_assign_unique(np.asarray(keys, np.uint64),
                                 np.asarray(slots, np.int16))
        np.testing.assert_array_equal(t_new.slot_host, t_old.slot_host)
        return
    np.testing.assert_array_equal(t_new.slot_host, t_old.slot_host)
    assert rp is not None and rp.wire == "compact"
    np.testing.assert_array_equal(rp.gidx, locs)
    np.testing.assert_array_equal(rp.uniq, rows_g)
    np.testing.assert_array_equal(
        rp.meta, [(n, 999, 0, 0) for n in sizes])
    # the chunk map the device rebuilds global rows with
    cmap = np.asarray(rp.dev[1][0])
    cb = rp.chunk_bits
    real = rows_g <= CAP
    s_of = np.concatenate(
        [np.pad(s, (0, k_max - len(s))) for _, s, *_ in per_batch]
    ).reshape(rows_g.shape)
    rebuilt = (cmap[s_of, locs >> cb] << cb) | (locs & ((1 << cb) - 1))
    np.testing.assert_array_equal(rebuilt[real], rows_g[real])
    # the distinct rows the pass carries for mark_trained_rows
    np.testing.assert_array_equal(np.sort(rp.trained_rows),
                                  np.unique(rows_g[real]))
    assert len(rp.trained_rows) == len(np.unique(rows_g[real]))


def test_new_rows_are_claimed_in_stream_order_across_slots():
    """The case the oracle comparison rests on, spelled out: with chunks
    of 4 rows, slot 0's fifth new key claims the NEXT chunk after slot
    1's first, because that is when it is seen."""
    n_slots, bits, _, (keys, slots, sizes, k_max, segs), _ = \
        _stream("alternating_new")
    t = _table(n_slots, bits)
    rp = _compact(_batches(keys, slots, sizes, k_max, segs), t, False)
    chunk_slot, _ = t.index.arena_export()
    first_seen = []
    for k, s in zip(keys, slots):
        if (k, s) not in first_seen:
            first_seen.append((k, s))
    fill, want = {}, []
    for _, s in first_seen:   # a slot asks for a chunk every 4th new key
        if fill.get(s, 0) % 4 == 0:
            want.append(s)
        fill[s] = fill.get(s, 0) + 1
    assert list(chunk_slot) == want and len(set(want)) == 3
    assert rp is not None


@pytest.mark.parametrize("case", [
    "empty", "one", "all_same", "key_zero_slot_zero", "same_key_two_slots",
    "grows_past_its_first_table", "zipf_many_slots"])
def test_dedup_slotted_first_seen_matches_numpy(case):
    rng = np.random.default_rng(7)
    if case == "empty":
        keys, slots = np.empty(0, np.uint64), np.empty(0, np.uint16)
    elif case == "one":
        keys, slots = np.array([5], np.uint64), np.array([3], np.uint16)
    elif case == "all_same":
        keys, slots = np.full(100, 9, np.uint64), np.full(100, 2, np.uint16)
    elif case == "key_zero_slot_zero":   # an empty cell's own contents
        keys = np.array([3, 0, 0, 3, 0], np.uint64)
        slots = np.array([0, 0, 0, 0, 1], np.uint16)
    elif case == "same_key_two_slots":
        keys = np.array([4, 4, 4, 5, 4], np.uint64)
        slots = np.array([0, 1, 0, 1, 1], np.uint16)
    elif case == "grows_past_its_first_table":   # 4,096 cells to start
        keys = rng.integers(0, 1 << 62, size=20000).astype(np.uint64)
        keys = np.concatenate([keys, keys[::-1]])
        slots = (keys % 5).astype(np.uint16)
    else:
        slots = rng.integers(0, 26, size=50000).astype(np.uint16)
        keys = (_zipf(rng, 5000, 50000)).astype(np.uint64)  # shared ids
    got = dedup_slotted_first_seen(keys, slots)
    want = _dedup_slotted_first_seen_py(keys, slots)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
    uk, us, inv = got
    np.testing.assert_array_equal(uk[inv], keys)
    np.testing.assert_array_equal(us[inv], slots)
    assert len(set(zip(uk.tolist(), us.tolist()))) == len(uk)
    # first-seen: a pair's first position rises with its rank
    first = np.full(len(uk), len(keys), np.int64)
    np.minimum.at(first, inv, np.arange(len(keys)))
    assert (np.diff(first) > 0).all()
