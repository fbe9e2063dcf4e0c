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
from paddlebox_tpu.train.device_pass import _FloatHalf

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
    half = _FloatHalf()
    half.make(floats.shape[1], lambda: (floats, None))
    return ResidentPass._compact_tail(per_batch, half, trivial, nrec, table)


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


# ---- ISSUE 36: the chunk map's lookup without a gather ----

_BIG_CAP, _BIG_SLOT0 = 1 << 23, 1050 * 4096 + 17


@pytest.fixture(scope="module")
def wide_slot_table():
    """The build's view of a table whose slot 0 holds 1,051 chunks of
    4,096 rows (``arena_chunk_bits`` 12, cell 1's), slot 1 three and
    slot 2 one, registered slot by slot in turns so the slots' chunks
    interleave: the index, its lock and the rows' slots, and no device
    state (8.4M rows of it would serve nothing here)."""
    import threading
    import types
    from paddlebox_tpu.ps.kv import NativeKV, make_kv
    index = make_kv(_BIG_CAP)
    if not isinstance(index, NativeKV):
        pytest.skip("4.3M keys through the python index take minutes")
    index.arena_enable(12, 3)
    t = types.SimpleNamespace(
        capacity=_BIG_CAP, arena_slots=3, arena_chunk_bits=12, index=index,
        slot_host=np.zeros(_BIG_CAP + 1, np.int16),
        host_lock=threading.Lock())
    sizes, base = (_BIG_SLOT0, 9000, 40), (0, 1 << 40, 1 << 41)
    done = [0, 0, 0]
    while any(d < n for d, n in zip(done, sizes)):
        for s in range(3):           # a turn: up to 1.5M keys a slot
            n = min(1_500_000, sizes[s] - done[s])
            if n:
                keys = np.arange(done[s], done[s] + n, dtype=np.uint64)
                _register(t, keys + np.uint64(base[s]), np.full(n, s))
                done[s] += n
    return t, base, sizes


@pytest.mark.parametrize("path", ["select", "gather-ragged-K",
                                  "gather-segments"])
def test_decoded_rows_are_the_host_rows_on_both_sides_of_the_bound(
        path, wide_slot_table):
    """The device's decode of the compact wire gives the pass's host
    ``rows_g`` whether the chunk map is read by ``cmap_select`` (trivial
    segments and K a multiple of S: key p is slot p % S's) or by the
    gather (every other shape); the select side lowers without one."""
    import jax.numpy as jnp
    from paddlebox_tpu.train.device_pass import ResidentPassRunner
    table, base, sizes = wide_slot_table
    rng = np.random.default_rng(36)
    recs, s = 96, 3
    ids = np.stack([rng.integers(0, n, recs) for n in sizes], axis=1)
    ids[:8, 0] = sizes[0] - 1 - np.arange(8)     # the last chunk's rows
    ids[8:12] = ids[:4]                          # repeats
    keys = (ids + np.asarray(base)[None, :]).reshape(-1)
    slots = np.tile(np.arange(s), recs)
    trivial = path != "gather-segments"
    k_max = recs * s // 2 + (4 if path == "gather-ragged-K" else 0)
    per_batch = _batches(keys, slots, [recs * s // 2] * 2, k_max,
                         segs=not trivial)
    rp = _compact(per_batch, table, trivial)
    assert rp is not None and rp.chunk_bits == 12
    loc_t, (cmap,), floats, meta, segs_t, qm = rp.dev
    assert len(loc_t) == 2 and loc_t[1].dtype == jnp.uint8  # u16m, m = 8
    assert cmap.shape == (3, 2048)
    runner = ResidentPassRunner(None, _BIG_CAP, trivial, wire="compact",
                                num_slots=s, chunk_bits=12)

    def decode(loc, floats, meta, segs):
        v = runner._make_view(loc, (cmap,), floats, meta, segs, qm)
        return v.unique_rows, v.gather_idx, v.num_unique

    def args(i):
        return (tuple(a[i] for a in loc_t), floats[i], meta[i],
                tuple(a[i % a.shape[0]] for a in segs_t))

    text = jax.jit(decode).lower(*args(0)).as_text()
    assert ("gather" in text) == (path != "select")
    for i in range(2):
        uniq, gidx, n = jax.jit(decode)(*args(i))
        want = np.where(rp.uniq[i] <= _BIG_CAP, rp.uniq[i], _BIG_CAP)
        np.testing.assert_array_equal(np.asarray(uniq)[np.asarray(gidx)],
                                      want)
        assert int(n) == len(np.unique(want))
    assert (rp.gidx >> 12).max() == 1050        # slot 0's last chunk


@pytest.mark.parametrize("shape", [
    (1, 8, 64, 5), (26, 2048, 16, 8192), (3, 8193, 50, 8192),
    (5, 100, 33, 1 << 22), (2, 64, 7, 70000)],
    ids=["one-slot-8", "cell-1", "ragged-stride", "three-bytes-ragged",
         "two-and-a-bit-bytes"])
def test_cmap_select_is_the_gather(shape):
    import jax.numpy as jnp
    from paddlebox_tpu.ops.chunk_map import cmap_select
    s, stride, b, max_chunk = shape
    rng = np.random.default_rng(stride)
    cmap = rng.integers(0, max_chunk + 1, (s, stride)).astype(np.int32)
    cmap[:, -1] = max_chunk
    c = rng.integers(0, stride, (b, s)).astype(np.int32)
    c[0], c[-1] = 0, stride - 1
    got = jax.jit(cmap_select, static_argnums=2)(
        jnp.asarray(cmap), jnp.asarray(c), max_chunk)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got),
                                  cmap[np.arange(s)[None, :], c])
