"""The host kv is the one key→row index: every route that gives a pass's
keys their rows — ``EmbeddingTable.bulk_assign_unique`` (plain and
arena tables), ``ShardedEmbeddingTable.prepare_global`` /
``prepare_global_eval``, ``HostKV.lookup_unique`` — over adversarial key
streams, and after the lifecycle points that release or reassign rows
(shrink, load, merge_model; the tiered table's eviction ahead of a pass
and its rolled-back plan), checked against a walk of every key in
stream order: a plain ``dict`` on a fresh index, the same kv calls on a
twin table driven through the same lifecycle where free-list holes
decide the rows."""

import jax
import numpy as np
import pytest

from paddlebox_tpu.config import flags_scope
from paddlebox_tpu.data.batch import SlotBatch
from paddlebox_tpu.ps import (EmbeddingTable, SparseSGDConfig,
                              TieredShardedEmbeddingTable)
from paddlebox_tpu.ps.kv import PyKV, make_kv
from paddlebox_tpu.ps.sharded import ShardedEmbeddingTable
from paddlebox_tpu.ps.table import TableState

from test_native_kv import STREAMS  # noqa: E402

NAMES = sorted(STREAMS)
N_SLOTS = 7


class DictWalk:
    """The oracle for a fresh index: a new key takes the next row."""

    def __init__(self):
        self.rows = {}

    def assign(self, keys):
        return np.array([self.rows.setdefault(k, len(self.rows))
                         for k in keys.tolist()], np.int64)

    def lookup(self, keys, miss=-1):
        return np.array([self.rows.get(k, miss) for k in keys.tolist()],
                        np.int64)


def two_passes(keys):
    """The stream's first half, then a pass alternating the first half's
    keys (old) with the second half's (new)."""
    h = len(keys) // 2
    first, rest = keys[:h], keys[h:]
    m = min(h, len(rest))
    second = np.stack([first[::-1][:m], rest[:m]], axis=1).reshape(-1)
    return first, second


def slots_of(keys):
    return (keys % np.uint64(N_SLOTS)).astype(np.int64)


def first_seen(keys):
    return np.array(list(dict.fromkeys(keys.tolist())), np.uint64)


def items(index):
    ks, rs = index.items()
    order = np.argsort(ks)
    return ks[order], rs[order]


def assert_bulk_pass(table, keys, rows_of_key):
    """One bulk_assign_unique call against the rows a walk gave."""
    rows_u, inv = table.bulk_assign_unique(keys, slots_of(keys))
    uniq = first_seen(keys)
    pos = {k: i for i, k in enumerate(uniq.tolist())}
    np.testing.assert_array_equal(inv, [pos[k] for k in keys.tolist()])
    np.testing.assert_array_equal(rows_u[inv], rows_of_key)
    np.testing.assert_array_equal(table.index.lookup(uniq), rows_u)
    np.testing.assert_array_equal(table.slot_host[rows_u], slots_of(uniq))


# ---- EmbeddingTable.bulk_assign_unique ------------------------------------

@pytest.mark.parametrize("arena", [False, True], ids=["plain", "arena"])
@pytest.mark.parametrize("name", NAMES)
def test_bulk_assign_is_the_walk(name, arena):
    """Two passes, the second half old keys and half new: rows, inverse
    and slot_host. On an arena table a slot's k-th first-seen key takes
    its slot's local row k, placed through the arena's chunk map."""
    t = EmbeddingTable(mf_dim=4, capacity=1 << 12, unique_bucket_min=64,
                       arena_slots=N_SLOTS if arena else None,
                       arena_chunk_bits=4)
    walk = DictWalk()
    locals_of = [DictWalk() for _ in range(N_SLOTS)]
    for keys in two_passes(STREAMS[name]):
        if not arena:
            assert_bulk_pass(t, keys, walk.assign(keys))
            continue
        slot = slots_of(keys)
        loc = np.array([locals_of[s].assign(np.array([k], np.uint64))[0]
                        for k, s in zip(keys, slot)])
        # the walk places each new key before the chunk map is read
        rows_u, inv = t.bulk_assign_unique(keys, slot)
        cs_map, cr_map = t.index.arena_export()
        chunk = {(int(s), int(r)): c
                 for c, (s, r) in enumerate(zip(cs_map, cr_map))}
        want = np.array([(chunk[(s, l >> 4)] << 4) | (l & 15)
                         for s, l in zip(slot.tolist(), loc.tolist())])
        np.testing.assert_array_equal(rows_u[inv], want)
        uniq = first_seen(keys)
        np.testing.assert_array_equal(t.index.lookup(uniq), rows_u)
        np.testing.assert_array_equal(t.slot_host[rows_u], slots_of(uniq))
    assert len(t.index) == len(set(STREAMS[name].tolist()))


# ---- ShardedEmbeddingTable.prepare_global / prepare_global_eval ---------

def sharded_batches(keys, n=2, S=3):
    """The pass's keys split over ``n`` per-device batches."""
    out = []
    k_pad = (len(keys) + n - 1) // n
    bs = (k_pad + S - 1) // S
    for part in np.array_split(keys, n):
        kp = np.zeros(k_pad, np.uint64)
        kp[:len(part)] = part
        segs = np.full(k_pad, bs * S, np.int32)
        segs[:len(part)] = np.arange(len(part), dtype=np.int32)
        out.append(SlotBatch(
            keys=kp, segments=segs, num_keys=len(part),
            dense=np.zeros((bs, 1), np.float32),
            label=np.zeros(bs, np.float32), show=np.ones(bs, np.float32),
            clk=np.zeros(bs, np.float32), batch_size=bs, num_slots=S))
    return out


def plan_rows(plan, batches):
    """The row each key occurrence of each device reads through the
    plan: owner = gather position // A, then the owner's response."""
    A = plan.req_capacity
    out = []
    for d, b in enumerate(batches):
        g = plan.gather_idx[d, :b.num_keys]
        o, j = g // A, g % A
        out.append(plan.serve_rows[o, plan.resp_idx[o, d, j]])
    return out


def shard_walk(kvs, batches, n=2, miss=None):
    """Walk each device's sorted unique keys owner by owner, in the
    plan's order, through per-shard indexes ``kvs`` → rows per device."""
    out = []
    for b in batches:
        keys = b.keys[:b.num_keys]
        uniq = np.unique(keys)
        rows_u = np.empty(len(uniq), np.int64)
        owner = (uniq % np.uint64(n)).astype(np.int64)
        for s in range(n):
            sel = owner == s
            rows_u[sel] = (kvs[s].assign(uniq[sel]) if miss is None
                           else kvs[s].lookup(uniq[sel], miss))
        out.append(rows_u[np.searchsorted(uniq, keys)])
    return out


def _sharded():
    return ShardedEmbeddingTable(2, mf_dim=4, capacity_per_shard=512,
                                 req_bucket_min=8, serve_bucket_min=8)


@pytest.mark.parametrize("route", ["assign", "eval"])
@pytest.mark.parametrize("name", NAMES)
def test_sharded_prepare_is_the_walk(name, route):
    """Owner rows equal a per-shard walk; eval reads a miss as the
    sentinel C and allocates nothing."""
    t = _sharded()
    walk = [DictWalk(), DictWalk()]
    first, second = two_passes(STREAMS[name])
    b1 = sharded_batches(first)
    for got, want in zip(plan_rows(t.prepare_global(b1), b1),
                         shard_walk(walk, b1)):
        np.testing.assert_array_equal(got, want)
    b2 = sharded_batches(second)
    before = [items(t.indexes[s]) for s in range(2)]
    if route == "assign":
        plan, want = t.prepare_global(b2), shard_walk(walk, b2)
    else:
        plan = t.prepare_global_eval(b2)
        want = shard_walk(walk, b2, miss=t.capacity)
    for got, w in zip(plan_rows(plan, b2), want):
        np.testing.assert_array_equal(got, w)
    for s in range(2):
        ks, rs = items(t.indexes[s])
        np.testing.assert_array_equal(ks, sorted(walk[s].rows))
        np.testing.assert_array_equal(rs, walk[s].lookup(ks))
        if route == "eval":
            np.testing.assert_array_equal(ks, before[s][0])


# ---- HostKV.lookup_unique ---------------------------------------------------

@pytest.mark.parametrize("impl", ["host", "py"])
@pytest.mark.parametrize("name", NAMES)
def test_lookup_unique_is_the_walk(name, impl):
    """After half of the stream is assigned: rows, inverse, and every
    miss collapsed into one sentinel entry — on the table's HostKV and
    on the python fallback."""
    keys = STREAMS[name]
    C = 1 << 12
    kv, walk = (make_kv(C) if impl == "host" else PyKV(C)), DictWalk()
    first, _ = two_passes(keys)
    np.testing.assert_array_equal(kv.assign(first), walk.assign(first))
    n = len(kv)
    rows_u, inv = kv.lookup_unique(keys, C)
    np.testing.assert_array_equal(rows_u[inv], walk.lookup(keys, C))
    assert len(set(rows_u.tolist())) == len(rows_u)
    hits = set(k for k in keys.tolist() if k in walk.rows)
    assert len(rows_u) == len(hits) + (len(hits) < len(set(keys.tolist())))
    assert len(kv) == n


# ---- lifecycle points that release or reassign rows -------------------------

def _seed_show(table, keys, show):
    rows = table.index.lookup(keys)
    data = np.asarray(jax.device_get(table.state.data)).copy()
    data[rows, 0] = show
    table.state = TableState.from_logical(data, table.capacity,
                                          ext=table.opt_ext)


def _lifecycle(event, tmp_path, tag):
    """A table through pass 1, then ``event``: shrink drops every other
    key, load reads back a saved shrunk table, merge_model folds in
    another model's rows."""
    t = EmbeddingTable(mf_dim=4, capacity=1 << 11, unique_bucket_min=64)
    first, _ = two_passes(STREAMS["zipf"])
    t.bulk_assign_unique(first, slots_of(first))
    uniq = first_seen(first)
    _seed_show(t, uniq[::2], 20.0)
    if event in ("shrink", "load"):
        assert t.shrink(delete_threshold=1.0, decay=1.0) > 0
    if event == "load":
        path = str(tmp_path / f"base_{tag}.npz")
        t.save_base(path)
        t.load(path)
    if event == "merge_model":
        other = EmbeddingTable(mf_dim=4, capacity=1 << 11)
        extra = np.arange(5000, 5040, dtype=np.uint64)
        keys_o = np.concatenate([uniq[1::3], extra])
        other.bulk_assign_unique(keys_o, slots_of(keys_o))
        _seed_show(other, keys_o, 3.0)
        path = str(tmp_path / f"other_{tag}.npz")
        other.save_base(path)
        assert t.merge_model(path) == len(keys_o)
    return t


@pytest.mark.parametrize("event", ["shrink", "load", "merge_model"])
def test_bulk_assign_after_lifecycle_is_the_kv_walk(event, tmp_path):
    """The next pass's bulk assign equals a walk of every key through
    the kv the event left (its own items for old keys; its free list for
    new ones, as a twin table walked key by key shows)."""
    t = _lifecycle(event, tmp_path, "t")
    twin = _lifecycle(event, tmp_path, "twin")
    np.testing.assert_array_equal(items(t.index)[0], items(twin.index)[0])
    old = dict(zip(*(a.tolist() for a in t.index.items())))
    _, second = two_passes(STREAMS["zipf"])
    keys = np.concatenate([second, np.arange(9000, 9050, dtype=np.uint64)])
    want = twin.index.assign(keys)
    assert_bulk_pass(t, keys, want)
    for k, r in zip(keys.tolist(), want.tolist()):
        if k in old:
            assert r == old[k]
    assert len(t.index) == len(old) + len(set(keys.tolist()) - set(old))


# ---- TieredShardedEmbeddingTable: eviction ahead, rolled-back plan ----------

def _tiered(event):
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0)
    t = TieredShardedEmbeddingTable(2, mf_dim=2, capacity_per_shard=64,
                                    cfg=cfg, req_bucket_min=8,
                                    serve_bucket_min=8)
    k1 = np.arange(0, 128, dtype=np.uint64)          # 64 rows a shard
    if event == "evict_ahead":
        t.stage(k1, background=False)
        t.begin_pass(k1)
        t.end_pass()
        t.fence()                                     # k1 clean
        # the next queued pass needs 16 rows a shard more than are free:
        # the lane releases 16 clean k1 rows a shard
        t.stage(np.arange(128, 160, dtype=np.uint64), background=False,
                queue=True)
        assert t._evict_ahead() == 32
        return t, k1
    half = k1[:64]
    t.prepare_global(sharded_batches(half))
    with pytest.raises(RuntimeError, match="abandoned"):
        with t.plan_scope():     # a build whose pass never opens
            t.prepare_global(sharded_batches(
                np.arange(200, 264, dtype=np.uint64)))
            raise RuntimeError("abandoned")
    return t, np.concatenate([half, np.arange(232, 248, dtype=np.uint64),
                              np.arange(300, 316, dtype=np.uint64)])


@pytest.mark.parametrize("event", ["evict_ahead", "rollback_plan"])
def test_tiered_prepare_after_release_is_the_walk(event):
    """After rows were released behind the table's back, prepare_global
    equals the walk over the shards' kvs: old keys keep their rows, the
    released and the new ones take what a twin's kvs give them."""
    with flags_scope(warmup_pass_scatter=False):
        t, keys = _tiered(event)
        twin, _ = _tiered(event)
        old = [dict(zip(*(a.tolist() for a in t.indexes[s].items())))
               for s in range(2)]
        keys = np.random.default_rng(45).permutation(keys)
        b = sharded_batches(keys)
        want = shard_walk(twin.indexes, b)
        for got, w in zip(plan_rows(t.prepare_global(b), b), want):
            np.testing.assert_array_equal(got, w)
        for s in range(2):
            ks, rs = items(t.indexes[s])
            kt, rt = items(twin.indexes[s])
            np.testing.assert_array_equal(ks, kt)
            np.testing.assert_array_equal(rs, rt)
            live = dict(zip(ks.tolist(), rs.tolist()))
            assert all(live[k] == r for k, r in old[s].items())
        t.fence()
        twin.fence()
