"""Embedding store tests: pull/push/dedup/optimizer math vs numpy reference
(mirrors heter_ps/test_comm.cu's insert→pull→push→verify pattern)."""

import numpy as np
import jax.numpy as jnp
import pytest

from paddlebox_tpu.data.batch import SlotBatch
from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
from paddlebox_tpu.ps.table import HostKV


def mkbatch(keys, k_pad=16, B=2, S=2):
    keys = np.asarray(keys, np.uint64)
    kp = np.zeros(k_pad, np.uint64)
    kp[:len(keys)] = keys
    segs = np.full(k_pad, B * S, np.int32)
    segs[:len(keys)] = np.arange(len(keys)) % (B * S)
    return SlotBatch(keys=kp, segments=segs, num_keys=len(keys),
                     dense=np.zeros((B, 1), np.float32),
                     label=np.zeros(B, np.float32),
                     show=np.ones(B, np.float32), clk=np.zeros(B, np.float32),
                     batch_size=B, num_slots=S)


def test_hostkv_assign_reuse_release():
    kv = HostKV(capacity=4)
    r1 = kv.assign(np.array([10, 20, 30], np.uint64))
    assert len(set(r1.tolist())) == 3
    r2 = kv.assign(np.array([20, 10], np.uint64))
    np.testing.assert_array_equal(r2, [r1[1], r1[0]])
    kv.release(np.array([10], np.uint64))
    r3 = kv.assign(np.array([99], np.uint64))
    assert r3[0] == r1[0]  # row reused
    kv.assign(np.array([1], np.uint64))  # row 3: now 4/4 used
    with pytest.raises(RuntimeError):
        kv.assign(np.array([2], np.uint64))  # capacity 4 exhausted


def test_pull_new_keys_zero_and_dedup():
    t = EmbeddingTable(mf_dim=4, capacity=64, unique_bucket_min=8)
    b = mkbatch([5, 7, 5, 9])
    idx = t.prepare(b)
    assert idx.num_unique == 3
    vals = np.asarray(t.pull(idx))
    assert vals.shape == (16, 7)  # K_pad x (3 + mf_dim)
    np.testing.assert_array_equal(vals[:4], 0)  # fresh rows are zero
    # duplicate keys share a unique slot
    assert idx.gather_idx[0] == idx.gather_idx[2]
    # pad positions map to a slot whose row clamps to the zero sentinel
    # (pads hold distinct OOB rows > capacity — unique-scatter contract)
    assert np.all(idx.unique_rows[idx.gather_idx[4:]] >= t.capacity)
    np.testing.assert_array_equal(vals[4:], 0)  # padded keys pull zeros


def test_push_updates_counters_and_weights():
    cfg = SparseSGDConfig(mf_create_thresholds=1e9)  # no mf creation yet
    t = EmbeddingTable(mf_dim=2, capacity=32, cfg=cfg, unique_bucket_min=8)
    b = mkbatch([5, 7, 5], k_pad=8)
    idx = t.prepare(b)
    # grads: [g_show, g_clk, g_embed, g_embedx x2]
    kg = np.zeros((8, 5), np.float32)
    kg[0] = [1, 0, 0.5, 0.1, 0.1]
    kg[1] = [1, 1, 0.2, 0.2, 0.2]
    kg[2] = [1, 0, 0.3, 0.1, 0.1]
    t.push(idx, jnp.asarray(kg))
    st = t.state
    rows = t.index.lookup(np.array([5, 7], np.uint64))
    show = np.asarray(st.show)[rows]
    clk = np.asarray(st.clk)[rows]
    np.testing.assert_allclose(show, [2.0, 1.0])  # key 5 hit twice
    np.testing.assert_allclose(clk, [0.0, 1.0])
    # embed update (reference math): g=0.8 for key5, scale=g_show=2,
    # ratio = lr*sqrt(g0/(g0+0)) = 0.05; w = 0 + (0.8/2)*0.05
    w5 = np.asarray(st.embed_w)[rows[0]]
    np.testing.assert_allclose(w5, 0.4 * 0.05, rtol=1e-5)
    g2 = np.asarray(st.embed_g2sum)[rows[0]]
    np.testing.assert_allclose(g2, 0.4 ** 2, rtol=1e-5)
    # delta_score: nonclk*.1*(2-0)+1*0 = 0.2
    np.testing.assert_allclose(np.asarray(st.delta_score)[rows[0]], 0.2,
                               rtol=1e-5)
    # mf not created (threshold huge) → embedx still zero, mf_size 0
    assert np.all(np.asarray(st.mf_size)[rows] == 0)
    assert np.all(np.asarray(st.embedx_w)[rows] == 0)
    # sentinel row stays zero
    assert np.all(np.asarray(st.show)[t.capacity] == 0)


def test_lazy_mf_creation_threshold():
    cfg = SparseSGDConfig(mf_create_thresholds=0.5, mf_initial_range=0.01)
    t = EmbeddingTable(mf_dim=4, capacity=16, cfg=cfg, unique_bucket_min=8)
    b = mkbatch([3], k_pad=8)
    idx = t.prepare(b)
    kg = np.zeros((8, 7), np.float32)
    kg[0] = [1, 1, 0.1, 0, 0, 0, 0]  # score = .1*(1-1) + 1*1 = 1 >= 0.5
    t.push(idx, jnp.asarray(kg))
    row = t.index.lookup(np.array([3], np.uint64))[0]
    assert np.asarray(t.state.mf_size)[row] == 1
    mf = np.asarray(t.state.embedx_w)[row]
    assert np.all(mf >= 0) and np.all(mf <= 0.01) and mf.std() > 0
    # second push: now a normal adagrad step on embedx
    idx2 = t.prepare(b)
    kg2 = np.zeros((8, 7), np.float32)
    kg2[0] = [1, 0, 0.0, 0.4, 0.4, 0.4, 0.4]
    t.push(idx2, jnp.asarray(kg2))
    mf2 = np.asarray(t.state.embedx_w)[row]
    expect = np.clip(mf + (0.4 / 1.0) * 0.05 * np.sqrt(3.0 / 3.0), -10, 10)
    np.testing.assert_allclose(mf2, expect, rtol=1e-5)


def test_save_base_delta_load(tmp_path):
    t = EmbeddingTable(mf_dim=2, capacity=32, unique_bucket_min=8)
    b = mkbatch([11, 22], k_pad=8)
    idx = t.prepare(b)
    kg = np.zeros((8, 5), np.float32)
    kg[0] = [1, 0, 0.5, 0, 0]
    kg[1] = [1, 1, 0.1, 0, 0]
    t.push(idx, jnp.asarray(kg))
    base = str(tmp_path / "base.npz")
    assert t.save_base(base) == 2

    # touch only key 11 → delta has 1 row
    idx2 = t.prepare(mkbatch([11], k_pad=8))
    kg2 = np.zeros((8, 5), np.float32)
    kg2[0] = [1, 0, 0.2, 0, 0]
    t.push(idx2, jnp.asarray(kg2))
    delta = str(tmp_path / "delta.npz")
    assert t.save_delta(delta) == 1

    # fresh table: load base then apply delta → equals live table
    t2 = EmbeddingTable(mf_dim=2, capacity=32, unique_bucket_min=8)
    t2.load(base)
    t2.load(delta, merge=True)
    for k in (11, 22):
        r_live = t.index.lookup(np.array([k], np.uint64))[0]
        r_new = t2.index.lookup(np.array([k], np.uint64))[0]
        np.testing.assert_allclose(
            np.asarray(t2.state.embed_w)[r_new],
            np.asarray(t.state.embed_w)[r_live], rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(t2.state.show)[r_new],
            np.asarray(t.state.show)[r_live], rtol=1e-6)


def test_shrink_frees_low_score_rows():
    t = EmbeddingTable(mf_dim=2, capacity=16, unique_bucket_min=8)
    idx = t.prepare(mkbatch([1, 2], k_pad=8))
    kg = np.zeros((8, 5), np.float32)
    kg[0] = [20, 15, 0, 0, 0]   # high score: .1*5 + 15 = 15.5
    kg[1] = [1, 0, 0, 0, 0]     # low score: .1*1 = 0.1
    t.push(idx, jnp.asarray(kg))
    freed = t.shrink(delete_threshold=1.0, decay=1.0)
    assert freed == 1
    assert t.index.lookup(np.array([2], np.uint64))[0] == -1
    r1 = t.index.lookup(np.array([1], np.uint64))[0]
    assert r1 >= 0 and np.asarray(t.state.show)[r1] == 20.0
    # freed row is zeroed on device
    st = np.asarray(t.state.show)
    assert (st > 0).sum() == 1


def test_packed_gather_oob_pads_read_zero():
    """Regression: with capacity % rows_per_line == rpl-1 the first OOB
    pad id lands past the last storage line; a naive line-index clamp
    then aliases a REAL row. Pads must read the sentinel's zeros."""
    import jax.numpy as jnp
    from paddlebox_tpu.ps.table import (TableState, gather_full_rows,
                                        pack_geometry)
    cap = 999           # rpl=8 for F=16 → (cap+1) % 8 == 0, the bad case
    mf = 8
    rpl, fp, nl = pack_geometry(cap, 16)
    assert (cap + 1) % rpl == 0
    logical = np.zeros((cap + 1, 16), np.float32)
    logical[:cap, 4] = 7.0  # every real row has embed_w = 7
    st = TableState.from_logical(logical, cap)
    # sentinel (cap), first OOB pad (cap+1), far OOB pads
    rows = jnp.asarray(np.array([0, cap, cap + 1, cap + 8, cap + 4096],
                                np.int32))
    got = np.asarray(gather_full_rows(st, rows))
    assert got[0, 4] == 7.0            # real row reads its value
    np.testing.assert_array_equal(got[1:], 0.0)  # sentinel + pads → zeros


def test_slot_host_recorded_on_all_paths(tmp_path):
    """Saved slot metadata must be populated by every prepare/push path:
    EmbeddingTable.prepare, push(slot_of_key=...), and the
    ExtendedEmbeddingTable pair (regression: the extended path once
    saved slot=0 for every row)."""
    import jax.numpy as jnp

    # prepare path: keys 1..4 land in slots 0,1,0,1 (mkbatch: pos % S)
    t = EmbeddingTable(mf_dim=2, capacity=32, unique_bucket_min=8)
    idx = t.prepare(mkbatch([1, 2, 3, 4], k_pad=8))
    t.push(idx, jnp.zeros((8, 5)))
    p = str(tmp_path / "b.npz")
    t.save_base(p)
    blob = np.load(p)
    by_key = dict(zip(blob["keys"].tolist(), blob["slot"].tolist()))
    assert by_key == {1: 0.0, 2: 1.0, 3: 0.0, 4: 1.0}

    # eager push(slot_of_key) path on a fresh table (no prepare slots)
    t2 = EmbeddingTable(mf_dim=2, capacity=32, unique_bucket_min=8)
    b = mkbatch([7, 8], k_pad=8)
    with t2.host_lock:
        rows, inv = t2.index.assign_unique(b.keys[:2])
        t2._touched[rows] = True
    idx2 = t2._build_index(b, rows, inv)
    t2.push(idx2, jnp.zeros((8, 5)),
            slot_of_key=jnp.asarray(np.array([0, 1] + [0] * 6, np.float32)))
    assert t2.slot_host[t2.index.lookup(np.array([8], np.uint64))[0]] == 1

    # extended pair records slots for BOTH tables
    from paddlebox_tpu.ps.extended import ExtendedEmbeddingTable
    te = ExtendedEmbeddingTable(mf_dim=2, extend_mf_dim=2, capacity=32,
                                unique_bucket_min=8,
                                skip_extend_slots=(0,))
    te.prepare(mkbatch([11, 12], k_pad=8))
    rb = te.base.index.lookup(np.array([12], np.uint64))[0]
    assert te.base.slot_host[rb] == 1
    re_ = te.extend.index.lookup(np.array([12], np.uint64))[0]
    assert re_ >= 0 and te.extend.slot_host[re_] == 1


def test_merge_model_accumulates_stats(tmp_path):
    """merge_model (box_wrapper.h:801): overlapping keys accumulate
    show/clk/delta_score and keep live weights; new keys insert
    wholesale — unlike load(merge=True), which overwrites."""
    import jax
    from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0)
    a = EmbeddingTable(mf_dim=2, capacity=256, cfg=cfg)
    b = EmbeddingTable(mf_dim=2, capacity=256, cfg=cfg)

    def seed(table, keys, show, w):
        rows = table.index.assign(keys)
        data = np.asarray(jax.device_get(table.state.data)).copy()
        data[rows, 0] = show       # show
        data[rows, 1] = show / 2   # clk
        data[rows, 4] = w          # embed_w
        from paddlebox_tpu.ps.table import TableState
        table.state = TableState.from_logical(data, table.capacity)
        table.slot_host[rows] = 1

    k_a = np.array([1, 2, 3], np.uint64)
    k_b = np.array([2, 3, 4], np.uint64)
    seed(a, k_a, 10.0, 0.5)
    seed(b, k_b, 4.0, 0.9)
    path = str(tmp_path / "other.npz")
    b.save_base(path)
    merged = a.merge_model(path)
    assert merged == 3
    data = np.asarray(jax.device_get(a.state.data))
    rows = a.index.lookup(np.array([1, 2, 3, 4], np.uint64))
    assert (rows >= 0).all()          # key 4 inserted
    np.testing.assert_allclose(data[rows, 0], [10.0, 14.0, 14.0, 4.0])
    np.testing.assert_allclose(data[rows, 1], [5.0, 7.0, 7.0, 2.0])
    # overlapping keys KEEP live weights; the new key takes the file's
    np.testing.assert_allclose(data[rows, 4], [0.5, 0.5, 0.5, 0.9])
    assert a.slot_host[rows[3]] == 1
    # merged rows are flagged for the next delta save (key 1 was not in
    # the merge file, so it stays unflagged)
    assert a._touched[rows[1:]].all()
    assert not a._touched[rows[0]]


def test_zero1_rejects_non_elementwise_tx():
    import optax
    from paddlebox_tpu.train.sharded import _assert_elementwise_tx
    _assert_elementwise_tx(optax.adam(1e-3))       # fine
    _assert_elementwise_tx(optax.sgd(0.1))         # fine
    with pytest.raises(ValueError, match="ELEMENTWISE"):
        _assert_elementwise_tx(optax.chain(
            optax.clip_by_global_norm(1.0), optax.sgd(0.1)))


def test_merge_multi_models(tmp_path):
    """MergeMultiModels (box_wrapper.h:812): several files fold in order;
    update_type selects stat-merge vs delta-overwrite."""
    import jax
    from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
    from paddlebox_tpu.ps.table import TableState
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0)

    def seed(keys, show):
        t = EmbeddingTable(mf_dim=2, capacity=256, cfg=cfg)
        rows = t.index.assign(keys)
        d = np.asarray(jax.device_get(t.state.data)).copy()
        d[rows, 0] = show
        t.state = TableState.from_logical(d, t.capacity)
        return t

    a = seed(np.array([1, 2], np.uint64), 10.0)
    b = seed(np.array([2, 3], np.uint64), 4.0)
    c = seed(np.array([3, 4], np.uint64), 2.0)
    pb, pc = str(tmp_path / "b.npz"), str(tmp_path / "c.npz")
    b.save_base(pb)
    c.save_base(pc)
    assert a.merge_models([pb, pc]) == 4
    data = np.asarray(jax.device_get(a.state.data))
    rows = a.index.lookup(np.array([1, 2, 3, 4], np.uint64))
    # stats accumulate: key2 10+4, key3 4+2 (b inserted, c merged), key4 2
    np.testing.assert_allclose(data[rows, 0], [10.0, 14.0, 6.0, 2.0])
    with pytest.raises(ValueError):
        a.merge_models([pb], update_type="bogus")
    # overwrite mode applies files as deltas
    a2 = seed(np.array([2], np.uint64), 10.0)
    a2.merge_models([pb], update_type="overwrite")
    d2 = np.asarray(jax.device_get(a2.state.data))
    r2 = a2.index.lookup(np.array([2], np.uint64))
    np.testing.assert_allclose(d2[r2, 0], 4.0)  # overwritten, not summed


def test_nan_row_isolated_to_its_lane_span():
    """A diverging row's NaN must NOT bleed into healthy rows sharing
    its 128-lane storage line (the lane-packed gather/expand/push sites
    select with ``where``, not a 0*NaN multiply) — this is what lets
    telemetry localize a NaN to ONE key (ISSUE 1 satellite)."""
    import jax
    from paddlebox_tpu.ps.table import (TableState, expand_pull,
                                        gather_full_rows, merge_rows)
    cap, mf = 15, 8                      # feat 16 → 8 rows per line
    data = np.zeros((cap + 1, 16), np.float32)
    data[0, :] = np.nan                  # diverged row 0
    data[1, 4] = 3.25                    # healthy neighbor, same line
    ts = TableState.from_logical(data, cap)
    healthy = np.asarray(gather_full_rows(ts, jnp.array([1], jnp.int32)))
    assert np.isfinite(healthy).all()
    assert healthy[0, 4] == 3.25
    sick = np.asarray(gather_full_rows(ts, jnp.array([0], jnp.int32)))
    assert np.isnan(sick[0]).all()       # the NaN row still reads NaN

    # expand_pull fwd + transpose: u=16 uniques of D=8 (16 rows/line)
    vals = np.zeros((16, 8), np.float32)
    vals[3] = np.nan
    vals[4] = 7.0
    gi = jnp.array([4, 4, 3])
    out = np.asarray(expand_pull(jnp.asarray(vals), gi))
    assert np.isfinite(out[:2]).all() and np.isnan(out[2]).all()

    def loss(v):
        return expand_pull(v, gi)[:2].sum()   # healthy keys only

    g = np.asarray(jax.grad(loss)(jnp.asarray(
        np.where(np.isfinite(vals), vals, 0.0))))
    assert np.isfinite(g).all()
    assert g[4].sum() == 16.0            # 2 occurrences × 8 dims

    # merge_rows line form: a NaN contribution stays in its segment
    m = 4
    big = 1 << 18                        # above the line-form crossover
    mvals = np.ones((m, 8), np.float32)
    mvals[0] = np.nan
    idx = jnp.array([0, 1, 1, 2])        # rows 0..2 share a line
    merged = np.asarray(merge_rows(jnp.asarray(mvals), idx, big))
    assert np.isnan(merged[0]).all()
    np.testing.assert_allclose(merged[1], 2.0)
    np.testing.assert_allclose(merged[2], 1.0)


def test_push_with_nan_neighbor_keeps_healthy_rows_finite():
    """apply_push write-back: an untouched NaN row must not poison the
    touched rows' scatter deltas on the shared line."""
    from paddlebox_tpu.config import FLAGS
    from paddlebox_tpu.ps.table import (TableState, apply_push)
    from paddlebox_tpu.ps.sgd import SparseSGDConfig
    import jax
    cap, mf = 15, 8
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0)
    data = np.zeros((cap + 1, 16), np.float32)
    data[2, :] = np.nan                  # poisoned row on line 0
    ts = TableState.from_logical(data, cap)
    rows = jnp.array([1], jnp.int32)     # touch only the healthy row
    grads = jnp.ones((1, 3 + mf), jnp.float32)
    new = apply_push(ts, rows, grads, cfg, jax.random.PRNGKey(0))
    out = np.asarray(new.data)
    assert np.isfinite(out[1]).all(), "healthy touched row went NaN"
    assert np.isnan(out[2]).any(), "NaN row should persist until shrink"
    assert np.isfinite(out[0]).all() and np.isfinite(out[3:]).all()


# ---- the counted gather / push (ISSUE 27): a unique axis built on the
# device is as wide as the key axis; with its distinct count the two
# table ops stop at the rows the batch touched --------------------------

_COUNTED_CHUNK = 64


def _counted_case(name):
    """-> (capacity, per-key rows [K] with pad keys at ``capacity``)."""
    rng = np.random.default_rng(abs(hash(name)) % 1000 + len(name))
    cap = 1003      # (cap + 1) % 8 == 4: the sentinel shares its line
    k = 256         # four trips of 64
    if name == "sentinel-only":
        rows = np.full(k, cap)
    elif name == "inside-a-trip":           # 100 distinct + the sentinel
        rows = np.concatenate([rng.choice(cap, 100, replace=False),
                               np.full(k - 100, cap)])
    elif name == "exact-multiple":          # 127 distinct + the sentinel
        rows = np.concatenate([rng.choice(cap, 127, replace=False),
                               rng.choice(cap, 1), np.full(k - 128, cap)])
        rows[127] = rows[0]
    elif name == "all-distinct":            # num_unique == K, no pad key
        rows = rng.choice(cap, k, replace=False)
    elif name == "duplicates-no-pad-key":   # num_unique < K, no sentinel
        rows = rng.choice(cap, 90, replace=False)[rng.integers(0, 90, k)]
    elif name == "rows-share-lines":        # eight neighbours a line
        rows = np.concatenate([np.arange(400, 400 + 150),
                               np.full(k - 150, cap)])
    elif name == "ragged-K-all-distinct":   # 250 = 3 x 64 + 58
        rows = rng.choice(cap, 250, replace=False)
    elif name == "ragged-K-last-trip-overlaps":   # 200 + sentinel -> 4 trips
        rows = np.concatenate([rng.choice(cap, 200, replace=False),
                               np.full(50, cap)])
    elif name == "ragged-K-three-trips":    # stops before the overlap
        rows = np.concatenate([rng.choice(cap, 150, replace=False),
                               np.full(100, cap)])
    else:
        raise KeyError(name)
    return cap, rng.permutation(rows).astype(np.int32)


@pytest.mark.parametrize("name", [
    "sentinel-only", "inside-a-trip", "exact-multiple", "all-distinct",
    "duplicates-no-pad-key", "rows-share-lines", "ragged-K-all-distinct",
    "ragged-K-last-trip-overlaps", "ragged-K-three-trips"])
def test_counted_gather_and_push_are_bitwise_the_single_ops(name,
                                                            monkeypatch):
    import jax
    from paddlebox_tpu.ops.device_unique import dedup_rows
    from paddlebox_tpu.ps import table as tbl
    monkeypatch.setattr(tbl, "PUSH_CHUNK", _COUNTED_CHUNK)
    cap, key_rows = _counted_case(name)
    mf = 4                                   # feat 12 -> 8 rows a line
    rng = np.random.default_rng(7)
    logical = rng.normal(size=(cap + 1, 8 + mf)).astype(np.float32)
    logical[:, 0:2] = np.abs(logical[:, 0:2])            # show, clk
    logical[:, 5:7] = np.abs(logical[:, 5:7])            # g2sums
    logical[:, 7] = (rng.random(cap + 1) < 0.5) * mf     # mf_size
    logical[::7, 4] = -0.0                   # x + 0.0 must not be added
    logical[cap] = 0.0
    st = tbl.TableState.from_logical(logical, cap)
    uniq, gidx, n = dedup_rows(jnp.asarray(key_rows), cap)
    k = len(key_rows)
    assert int(n) == len(np.unique(key_rows))
    assert int(tbl.push_chunks(k, n)) == -(-int(n) // _COUNTED_CHUNK)
    # random draws reach the table (lazy mf creation): every row must
    # see the numbers its position drew before
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.5)
    grads = jnp.asarray(rng.normal(size=(k, 3 + mf)).astype(np.float32))
    key = jax.random.PRNGKey(5)

    @jax.jit
    def both(st, uniq, n):
        rows_1 = tbl.gather_full_rows(st, uniq)
        rows_n = tbl.gather_full_rows(st, uniq, n)
        new_1 = tbl.apply_push(st, uniq, grads, cfg, key, rows_full=rows_1)
        new_n = tbl.apply_push(st, uniq, grads, cfg, key, rows_full=rows_n,
                               num_unique=n)
        # and the push that gathers for itself
        new_g = tbl.apply_push(st, uniq, grads, cfg, key, num_unique=n)
        return rows_1, rows_n, new_1.packed, new_n.packed, new_g.packed

    rows_1, rows_n, p_1, p_n, p_g = map(np.asarray, both(st, uniq, n))
    as_bits = lambda a: a.view(np.uint32)    # noqa: E731
    np.testing.assert_array_equal(as_bits(rows_n), as_bits(rows_1))
    np.testing.assert_array_equal(as_bits(p_n), as_bits(p_1))
    np.testing.assert_array_equal(as_bits(p_g), as_bits(p_1))
    assert not np.array_equal(p_1, np.asarray(st.packed)) or int(n) == 1
    # the sentinel stays zero and real rows really moved
    assert not np.any(tbl.unpack_host(p_n, cap, 8 + mf)[cap])


@pytest.mark.parametrize("counted", [False, True],
                         ids=["no-count", "count"])
def test_push_without_a_count_lowers_to_the_single_scatter(counted):
    """A caller that passes no count compiles what it compiled before:
    one scatter, no loop. With a count the same one scatter sits in a
    ``while`` whose trip count is data."""
    import jax
    from paddlebox_tpu.ps import table as tbl
    cap, mf, u = 1003, 4, 256
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0)
    st = tbl.init_table_state(cap, mf)

    def push(st, uniq, grads, n):
        rows = tbl.gather_full_rows(st, uniq, n if counted else None)
        return tbl.apply_push(st, uniq, grads, cfg, jax.random.PRNGKey(0),
                              rows_full=rows,
                              num_unique=n if counted else None)

    def default_args(st, uniq, grads, n):
        rows = tbl.gather_full_rows(st, uniq)
        return tbl.apply_push(st, uniq, grads, cfg, jax.random.PRNGKey(0),
                              rows_full=rows)

    args = (st, jnp.zeros(u, jnp.int32), jnp.zeros((u, 3 + mf)),
            jnp.zeros((), jnp.int32))
    text = jax.jit(push).lower(*args).as_text()
    plain = jax.jit(default_args).lower(*args).as_text()
    ops = lambda t, op: t.count('"stablehlo.%s"(' % op)   # noqa: E731
    # the line scatter-add of the push; the sentinel's re-zero is the
    # other. (The text of the uncounted form was compared with the
    # parent commit's when this landed: equal byte for byte.)
    assert ops(text, "scatter") == ops(plain, "scatter") == 2
    assert ops(text, "gather") == ops(plain, "gather") == 1
    loops = text.count("stablehlo.while") - plain.count("stablehlo.while")
    assert loops == (2 if counted else 0)    # the gather's and the push's
    if not counted:
        assert text == plain.replace("default_args", "push")
