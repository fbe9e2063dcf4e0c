"""Sharded embedding PS + multi-chip train step on the 8-device CPU mesh —
the heter_ps/test_comm.cu analogue (single-process multi-device, no cluster)."""

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from paddlebox_tpu.config import flags_scope
from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
from paddlebox_tpu.data.criteo import generate_criteo_files
from paddlebox_tpu.models import DeepFM
from paddlebox_tpu.parallel import make_mesh
from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
from paddlebox_tpu.ps.sharded import ShardedEmbeddingTable
from paddlebox_tpu.train import Trainer
from paddlebox_tpu.train.sharded import (ShardedTrainer, make_global_batch)

N = 8


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= N, "conftest must provide 8 CPU devices"
    return make_mesh(N)


def make_batches(n, bs=8, S=3, k_pad=32, seed=0):
    """n local SlotBatch with random keys across a shared key space."""
    from paddlebox_tpu.data.batch import SlotBatch
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        nk = int(rng.integers(S, k_pad // 2))
        keys = rng.integers(1, 500, size=nk).astype(np.uint64)
        kp = np.zeros(k_pad, np.uint64)
        kp[:nk] = keys
        segs = np.full(k_pad, bs * S, np.int32)
        segs[:nk] = rng.integers(0, bs * S, size=nk).astype(np.int32)
        segs[:nk].sort()
        out.append(SlotBatch(
            keys=kp, segments=segs, num_keys=nk,
            dense=rng.normal(size=(bs, 4)).astype(np.float32),
            label=rng.integers(0, 2, bs).astype(np.float32),
            show=np.ones(bs, np.float32),
            clk=rng.integers(0, 2, bs).astype(np.float32),
            batch_size=bs, num_slots=S))
    return out


def test_prepare_global_routing():
    table = ShardedEmbeddingTable(N, mf_dim=4, capacity_per_shard=256,
                                  req_bucket_min=8, serve_bucket_min=8)
    batches = make_batches(N)
    idx = table.prepare_global(batches)
    A, A2 = idx.req_capacity, idx.serve_capacity
    assert idx.resp_idx.shape == (N, N, A)
    assert idx.serve_rows.shape == (N, A2)
    # every key's owner shard is key % N and its value row exists there
    for d, b in enumerate(batches):
        for k in b.keys[:b.num_keys]:
            s = int(k) % N
            assert table.indexes[s].lookup(
                np.array([k], np.uint64))[0] >= 0
    # serve rows are unique per owner (dedup across requesters)
    for s in range(N):
        valid = idx.serve_rows[s][idx.serve_valid[s] > 0]
        assert len(valid) == len(np.unique(valid))


def test_sharded_pull_matches_single_table(mesh):
    """Pull through the mesh == pull from one big table with same rows."""
    from paddlebox_tpu.train.sharded import ShardedTrainStep
    cfg = SparseSGDConfig(mf_create_thresholds=1e9)
    table = ShardedEmbeddingTable(N, mf_dim=4, capacity_per_shard=256,
                                  cfg=cfg, req_bucket_min=8,
                                  serve_bucket_min=8)
    batches = make_batches(N, seed=3)
    idx = table.prepare_global(batches)
    # plant distinctive embed_w = key value into each shard (AoS col 4)
    from paddlebox_tpu.ps.table import FIELD_COL
    data = np.asarray(jax.device_get(table.state.data)).copy()
    for s in range(N):
        keys, rows = table.indexes[s].items()
        data[s][rows, FIELD_COL["embed_w"]] = keys.astype(np.float32)
    table.state = type(table.state).from_logical(data, table.capacity)

    gb = make_global_batch(batches, idx)
    from jax.sharding import PartitionSpec as P
    from paddlebox_tpu.parallel.mesh import DATA_AXIS
    from paddlebox_tpu.ps.table import pull_rows, TableState

    def pull_blk(table_st, resp_idx, serve_rows, gather_idx):
        t = table_st.with_packed(table_st.packed[0])
        vals = pull_rows(t, serve_rows[0])
        resp = vals[resp_idx[0]]
        recv = jax.lax.all_to_all(resp, DATA_AXIS, 0, 0, tiled=True)
        flat = recv.reshape(-1, recv.shape[-1])
        return flat[gather_idx[0]][None]

    f = jax.jit(jax.shard_map(
        pull_blk, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS), check_vma=False))
    got = np.asarray(f(table.state, gb.resp_idx, gb.serve_rows,
                       gb.gather_idx))
    for d, b in enumerate(batches):
        np.testing.assert_allclose(
            got[d, :b.num_keys, 2], b.keys[:b.num_keys].astype(np.float32),
            rtol=1e-6, err_msg=f"device {d} pulled wrong embed_w")
        np.testing.assert_array_equal(got[d, b.num_keys:], 0)


def test_sharded_training_learns(mesh, tmp_path):
    files = generate_criteo_files(str(tmp_path), num_files=2,
                                  rows_per_file=1500, vocab_per_slot=40,
                                  seed=11)
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.load_into_memory()
    ds.local_shuffle(seed=1)
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=1e-3,
                          learning_rate=0.1, mf_learning_rate=0.1)
    table = ShardedEmbeddingTable(N, mf_dim=4, capacity_per_shard=4096,
                                  cfg=cfg, req_bucket_min=256,
                                  serve_bucket_min=256)
    with flags_scope(log_period_steps=10000):
        tr = ShardedTrainer(DeepFM(hidden=(32, 32)), table, desc, mesh,
                            tx=optax.adam(2e-3))
        r1 = tr.train_pass(ds)
        tr.reset_metrics()
        r2 = tr.train_pass(ds)
    assert np.isfinite(r2["last_loss"])
    assert r2["ins_num"] == 3000  # every record counted exactly once
    assert r2["auc"] > 0.58, f"sharded AUC too low: {r2['auc']}"
    assert table.feature_count() > 100


def test_sharded_save_load_roundtrip(mesh, tmp_path):
    cfg = SparseSGDConfig(mf_create_thresholds=1e9)
    table = ShardedEmbeddingTable(N, mf_dim=4, capacity_per_shard=128,
                                  cfg=cfg, req_bucket_min=8,
                                  serve_bucket_min=8)
    batches = make_batches(N, seed=5)
    table.prepare_global(batches)
    from paddlebox_tpu.ps.table import FIELD_COL
    data = np.asarray(jax.device_get(table.state.data)).copy()
    for s in range(N):
        keys, rows = table.indexes[s].items()
        data[s][rows, FIELD_COL["embed_w"]] = keys.astype(np.float32) * 2
    table.state = type(table.state).from_logical(data, table.capacity)
    path = str(tmp_path / "sharded.npz")
    n_saved = table.save_base(path)
    assert n_saved == table.feature_count() > 0

    t2 = ShardedEmbeddingTable(N, mf_dim=4, capacity_per_shard=128, cfg=cfg)
    assert t2.load(path) == n_saved
    for s in range(N):
        keys, rows = t2.indexes[s].items()
        np.testing.assert_allclose(
            np.asarray(t2.state.embed_w)[s][rows],
            keys.astype(np.float32) * 2)


def test_sharded_shrink_ages_features(mesh):
    """ShrinkTable on the stacked shards: decay + threshold drop, same
    accessor rules as EmbeddingTable.shrink (box_wrapper.h:638)."""
    from paddlebox_tpu.ps.table import FIELD_COL
    cfg = SparseSGDConfig(mf_create_thresholds=1e9)
    table = ShardedEmbeddingTable(N, mf_dim=2, capacity_per_shard=64,
                                  cfg=cfg, req_bucket_min=8,
                                  serve_bucket_min=8)
    batches = make_batches(N, seed=31)
    table.prepare_global(batches)
    before = table.feature_count()
    assert before > 0
    # plant heat on HALF the keys of shard 0; rest stay cold (show=0)
    data = np.asarray(jax.device_get(table.state.data)).copy()
    hot_per_shard = {}
    for s in range(N):
        keys, rows = table.indexes[s].items()
        half = rows[: len(rows) // 2]
        data[s][half, FIELD_COL["show"]] = 10.0
        data[s][half, FIELD_COL["clk"]] = 5.0
        hot_per_shard[s] = keys[: len(rows) // 2]
    table.state = type(table.state).from_logical(data, table.capacity)
    freed = table.shrink(delete_threshold=0.5, decay=0.9)
    assert freed == before - sum(len(v) for v in hot_per_shard.values())
    for s in range(N):
        keys, rows = table.indexes[s].items()
        assert set(keys.tolist()) == set(hot_per_shard[s].tolist())
        # decay applied to survivors
        np.testing.assert_allclose(
            np.asarray(table.state.data)[s][rows, FIELD_COL["show"]], 9.0)


def test_sharded_merge_model_and_merge_models(mesh, tmp_path):
    """merge_model accumulates stats for shared keys / inserts new ones;
    merge_models folds multiple files; single-table-format files split by
    key%N (box_wrapper.h:801-815)."""
    from paddlebox_tpu.ps.table import FIELD_COL
    cfg = SparseSGDConfig(mf_create_thresholds=1e9)

    def seeded_table(keys, w):
        t = ShardedEmbeddingTable(N, mf_dim=2, capacity_per_shard=64,
                                  cfg=cfg, req_bucket_min=8,
                                  serve_bucket_min=8)
        data = np.asarray(jax.device_get(t.state.data)).copy()
        owners = (keys % np.uint64(N)).astype(np.int64)
        for s in range(N):
            ks = keys[owners == s]
            rows = t.indexes[s].assign(ks)
            data[s][rows, FIELD_COL["embed_w"]] = w
            data[s][rows, FIELD_COL["show"]] = 3.0
            data[s][rows, FIELD_COL["clk"]] = 1.0
        t.state = type(t.state).from_logical(data, t.capacity)
        return t

    live = seeded_table(np.arange(1, 33, dtype=np.uint64), 1.0)
    other = seeded_table(np.arange(17, 49, dtype=np.uint64), -5.0)
    p1 = str(tmp_path / "other.npz")
    other.save_base(p1)

    assert live.merge_model(p1) == 32
    assert live.feature_count() == 48
    data = np.asarray(jax.device_get(live.state.data))
    # shared key 17: stats accumulate, live weight kept
    s17 = 17 % N
    r = live.indexes[s17].lookup(np.array([17], np.uint64))[0]
    assert data[s17][r, FIELD_COL["show"]] == 6.0
    assert data[s17][r, FIELD_COL["embed_w"]] == 1.0
    # new key 48: inserted wholesale
    s48 = 48 % N
    r = live.indexes[s48].lookup(np.array([48], np.uint64))[0]
    assert data[s48][r, FIELD_COL["embed_w"]] == -5.0

    # merge_models overwrite mode: later file wins on shared keys
    live2 = seeded_table(np.arange(1, 33, dtype=np.uint64), 1.0)
    assert live2.merge_models([p1], update_type="overwrite") == 32
    data2 = np.asarray(jax.device_get(live2.state.data))
    r = live2.indexes[s17].lookup(np.array([17], np.uint64))[0]
    assert data2[s17][r, FIELD_COL["embed_w"]] == -5.0

    # single-table-format file (no "n" block) splits by key%N
    st_keys = np.arange(100, 110, dtype=np.uint64)
    np.savez(str(tmp_path / "single.npz"), keys=st_keys,
             show=np.ones(10, np.float32), clk=np.zeros(10, np.float32),
             delta_score=np.zeros(10, np.float32),
             slot=np.zeros(10, np.float32),
             embed_w=np.full(10, 9.0, np.float32),
             embed_g2sum=np.zeros(10, np.float32),
             embedx_w=np.zeros((10, 2), np.float32),
             embedx_g2sum=np.zeros(10, np.float32),
             mf_size=np.zeros(10, np.float32))
    assert live.merge_model(str(tmp_path / "single.npz")) == 10
    s100 = 100 % N
    r = live.indexes[s100].lookup(np.array([100], np.uint64))[0]
    assert np.asarray(jax.device_get(
        live.state.data))[s100][r, FIELD_COL["embed_w"]] == 9.0


def test_sharded_opt_ext_survives_save_load(mesh, tmp_path):
    """SparseAdam per-row state (opt_ext block) persists through sharded
    save_base/load — the optimizer resumes, not restarts."""
    from paddlebox_tpu.ps.sgd import SparseAdamConfig
    cfg = SparseAdamConfig(mf_create_thresholds=1e9)
    table = ShardedEmbeddingTable(N, mf_dim=2, capacity_per_shard=64,
                                  cfg=cfg, req_bucket_min=8,
                                  serve_bucket_min=8)
    assert table.opt_ext > 0
    batches = make_batches(N, seed=41)
    table.prepare_global(batches)
    from paddlebox_tpu.ps.table import NUM_FIXED
    mf_end = NUM_FIXED + table.mf_dim
    data = np.asarray(jax.device_get(table.state.data)).copy()
    for s in range(N):
        _, rows = table.indexes[s].items()
        data[s][rows, mf_end:] = 0.25 * (s + 1)
    table.state = type(table.state).from_logical(data, table.capacity,
                                                 ext=table.opt_ext)
    path = str(tmp_path / "adam.npz")
    n = table.save_base(path)
    t2 = ShardedEmbeddingTable(N, mf_dim=2, capacity_per_shard=64,
                               cfg=cfg, req_bucket_min=8,
                               serve_bucket_min=8)
    assert t2.load(path) == n
    d2 = np.asarray(jax.device_get(t2.state.data))
    for s in range(N):
        _, rows = t2.indexes[s].items()
        if len(rows):
            np.testing.assert_allclose(d2[s][rows, mf_end:],
                                       0.25 * (s + 1))


def test_sharded_save_delta_and_reset_load(mesh, tmp_path):
    """load(merge=False) must reset device rows not covered by the dump;
    save_delta only dumps touched-since-last-save rows."""
    cfg = SparseSGDConfig(mf_create_thresholds=1e9)
    table = ShardedEmbeddingTable(N, mf_dim=2, capacity_per_shard=64,
                                  cfg=cfg, req_bucket_min=8,
                                  serve_bucket_min=8)
    b1 = make_batches(N, seed=21)
    table.prepare_global(b1)
    base = str(tmp_path / "b.npz")
    n1 = table.save_base(base)
    # new keys after the base save → delta contains only those shards' rows
    b2 = make_batches(N, seed=22)
    table.prepare_global(b2)
    delta = str(tmp_path / "d.npz")
    nd = table.save_delta(delta)
    assert 0 < nd <= table.feature_count()
    # plant junk in a row, then reset-load the base: junk must be gone
    from paddlebox_tpu.ps.table import FIELD_COL
    data = np.asarray(jax.device_get(table.state.data)).copy()
    data[0][:, FIELD_COL["embed_w"]] = 99.0
    table.state = type(table.state).from_logical(data, table.capacity)
    got = table.load(base)  # merge=False resets everything first
    assert got == n1
    w0 = np.asarray(table.state.embed_w)[0]
    keys0, rows0 = table.indexes[0].items()
    mask = np.ones(len(w0), bool)
    mask[rows0] = False
    assert np.all(w0[mask] == 0.0), "stale device rows survived reset load"


@pytest.mark.slow  # heavy on the virtual-CPU mesh —
# out of the tier-1 wall budget, runs in the slow tier (zero1 parity
# is also pinned by the lr_map zero1 variant there)
def test_zero1_matches_replicated_dense_update(mesh):
    """ZeRO-1 (opt-state sharded over flat param chunks, reference
    boxps_worker.cc:601 sharding stage) must produce the same params as
    the replicated optimizer path."""
    cfg = SparseSGDConfig(mf_create_thresholds=1e9, learning_rate=0.05)
    batches = make_batches(N, seed=7)

    results = []
    for zero1 in (False, True):
        table = ShardedEmbeddingTable(N, mf_dim=4, capacity_per_shard=256,
                                      cfg=cfg, req_bucket_min=8,
                                      serve_bucket_min=8)
        desc = type("D", (), {"batch_size": 8, "sparse_slots": [0, 1, 2],
                              "dense_dim": 4})()
        tr = ShardedTrainer(DeepFM(hidden=(8, 8)), table, desc, mesh,
                            tx=optax.adam(1e-2), zero1=zero1)
        state = tr.state
        idx = table.prepare_global(batches)
        gb = make_global_batch(batches, idx)
        for i in range(3):
            state, stats = tr.step_fn(state, gb, jax.random.PRNGKey(i))
        results.append(jax.device_get(state.params))

    flat_a = jax.tree_util.tree_leaves(results[0])
    flat_b = jax.tree_util.tree_leaves(results[1])
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)


@pytest.mark.slow  # same budget rationale — the resident mesh path
# stays covered in tier-1 by test_sharded_resident_matches_streaming
def test_sharded_resident_non_trivial_segments(mesh):
    """Mesh resident pass with MULTI-KEY slots (non-trivial segments —
    the wire ships a segment stream instead of deriving from meta):
    must match the streaming mesh pass exactly."""
    from paddlebox_tpu.data import DataFeedDesc, InMemoryDataset, SlotDef
    from paddlebox_tpu.data.record import SlotRecord
    slots = [SlotDef("label", "float", 1), SlotDef("d", "float", 3)]
    slots += [SlotDef(f"S{i}", "uint64") for i in range(4)]
    desc = DataFeedDesc(slots=slots, label_slot="label", batch_size=16,
                        key_bucket_min=128)
    rng = np.random.default_rng(61)
    recs = []
    for i in range(N * 16 * 4):
        counts = rng.integers(0, 3, size=4)
        counts[rng.integers(0, 4)] += 1
        offs = np.zeros(5, np.int32)
        np.cumsum(counts, out=offs[1:])
        keys = np.concatenate([
            rng.integers(s * 1000, (s + 1) * 1000, size=counts[s])
            for s in range(4)]).astype(np.uint64)
        recs.append(SlotRecord(
            keys=keys, slot_offsets=offs,
            dense=rng.normal(size=3).astype(np.float32),
            label=float(i % 2), show=1.0, clk=float(i % 2)))

    def mk():
        ds = InMemoryDataset(desc)
        ds.records = list(recs)
        ds.columnarize()
        cfg = SparseSGDConfig(mf_create_thresholds=0.0,
                              mf_initial_range=0.0,
                              learning_rate=0.05, mf_learning_rate=0.05)
        table = ShardedEmbeddingTable(N, mf_dim=4, capacity_per_shard=512,
                                      cfg=cfg, req_bucket_min=32,
                                      serve_bucket_min=32)
        with flags_scope(log_period_steps=10000):
            tr = ShardedTrainer(DeepFM(hidden=(8, 8)), table, desc, mesh,
                                tx=optax.adam(1e-2), seed=5)
        return tr, ds

    tr_a, ds_a = mk()
    tr_b, ds_b = mk()
    for _ in range(2):
        ra = tr_a.train_pass(ds_a)
        rb = tr_b.train_pass_resident(ds_b)
    assert rb["ins_num"] == ra["ins_num"]
    assert np.isclose(rb["auc"], ra["auc"], atol=1e-6), (ra["auc"],
                                                         rb["auc"])
    for a, b in zip(jax.tree.leaves(tr_a.state.params),
                    jax.tree.leaves(tr_b.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_repad_plan_equals_reroute():
    """_repad_plan (host-side array surgery) must produce exactly the
    plan prepare_global would build with the same forced capacities —
    both shrink (fine < pow2) and growth (tail group) directions."""
    from paddlebox_tpu.train.sharded import ShardedResidentPass
    cfg = SparseSGDConfig(mf_create_thresholds=1e9)
    for forced_a, forced_a2 in ((24, 40), (96, 104)):
        table = ShardedEmbeddingTable(N, mf_dim=4, capacity_per_shard=256,
                                      cfg=cfg, req_bucket_min=64,
                                      serve_bucket_min=64)
        batches = make_batches(N, seed=51)
        p1 = table.prepare_global(batches)
        if forced_a < p1.req_need or forced_a2 < p1.serve_need:
            forced_a = max(forced_a, p1.req_need)
            forced_a2 = max(forced_a2, p1.serve_need)
        got = ShardedResidentPass._repad_plan(
            p1, forced_a, forced_a2, N, table.capacity)
        assert got is not None
        want = table.prepare_global(batches, req_capacity=forced_a,
                                    serve_capacity=forced_a2)
        np.testing.assert_array_equal(got.resp_idx, want.resp_idx)
        np.testing.assert_array_equal(got.serve_rows, want.serve_rows)
        np.testing.assert_array_equal(got.serve_valid, want.serve_valid)
        np.testing.assert_array_equal(got.serve_slot, want.serve_slot)
        np.testing.assert_array_equal(got.gather_idx, want.gather_idx)
        assert got.req_capacity == want.req_capacity == forced_a
        assert got.serve_capacity == want.serve_capacity == forced_a2

    # the ambiguous-full-bucket guard: when the OLD request bucket is
    # exactly full (req_need == req_capacity), the gather pad sentinel
    # aliases a real position — _repad_plan must refuse (build() then
    # re-routes via prepare_global)
    from paddlebox_tpu.train.sharded import ShardedResidentPass as SRP
    p_full = p1._replace(req_need=p1.req_capacity)
    assert SRP._repad_plan(p_full, p1.req_capacity + 512,
                           p1.serve_capacity, N, table.capacity) is None


def test_sharded_resident_matches_streaming(mesh, tmp_path):
    """Device-resident mesh pass == streaming mesh pass (same data, same
    init; mf_initial_range=0 so rng paths don't diverge)."""
    files = generate_criteo_files(str(tmp_path), num_files=2,
                                  rows_per_file=1200, vocab_per_slot=40,
                                  seed=13)
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.load_into_memory()

    def mk():
        cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0,
                              learning_rate=0.1, mf_learning_rate=0.1)
        table = ShardedEmbeddingTable(N, mf_dim=4, capacity_per_shard=4096,
                                      cfg=cfg, req_bucket_min=256,
                                      serve_bucket_min=256)
        with flags_scope(log_period_steps=10000):
            return ShardedTrainer(DeepFM(hidden=(32, 32)), table, desc, mesh,
                                  tx=optax.adam(2e-3)), table

    tr_a, _ = mk()
    ra = tr_a.train_pass(ds)
    tr_b, table_b = mk()
    rb = tr_b.train_pass_resident(ds)
    assert rb["batches"] == ra["batches"]
    assert rb["ins_num"] == ra["ins_num"]
    assert np.isclose(rb["auc"], ra["auc"], atol=2e-3), (rb["auc"], ra["auc"])
    for x, y in zip(jax.tree.leaves(tr_a.state.params),
                    jax.tree.leaves(tr_b.state.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-2, atol=2e-3)
    # second resident pass continues training
    tr_b.reset_metrics()
    rb2 = tr_b.train_pass_resident(ds)
    assert rb2["auc"] > rb["auc"] - 0.02


def test_sharded_pass_preloader(mesh, tmp_path):
    """PassPreloader double-buffers mesh resident passes via build_fn."""
    from paddlebox_tpu.train import PassPreloader
    files = generate_criteo_files(str(tmp_path), num_files=1,
                                  rows_per_file=600, vocab_per_slot=30,
                                  seed=17)
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.load_into_memory()
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0)
    table = ShardedEmbeddingTable(N, mf_dim=2, capacity_per_shard=2048,
                                  cfg=cfg, req_bucket_min=128,
                                  serve_bucket_min=128)
    with flags_scope(log_period_steps=10000):
        tr = ShardedTrainer(DeepFM(hidden=(16,)), table, desc, mesh,
                            tx=optax.adam(1e-3))
        pre = PassPreloader(iter([ds, ds]),
                            build_fn=tr.build_resident_pass)
        pre.start_next()
        results = []
        while True:
            rp = pre.wait()
            if rp is None:
                break
            more = pre.start_next()
            results.append(tr.train_pass_resident(rp))
            if not more:
                break
    assert len(results) == 2
    assert all(np.isfinite(r["auc"]) for r in results)


@pytest.mark.slow  # same budget rationale as above
def test_sharded_eval_pass_and_checkpoint(mesh, tmp_path):
    """Forward-only mesh eval + CheckpointManager save/restore round trip
    on the sharded trainer."""
    from paddlebox_tpu.train import CheckpointManager
    files = generate_criteo_files(str(tmp_path / "d"), num_files=1,
                                  rows_per_file=1200, vocab_per_slot=40,
                                  seed=23)
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.load_into_memory()

    def mk():
        cfg = SparseSGDConfig(mf_create_thresholds=0.0,
                              mf_initial_range=0.0, learning_rate=0.1,
                              mf_learning_rate=0.1)
        table = ShardedEmbeddingTable(N, mf_dim=4, capacity_per_shard=4096,
                                      cfg=cfg, req_bucket_min=256,
                                      serve_bucket_min=256)
        with flags_scope(log_period_steps=10000):
            return ShardedTrainer(DeepFM(hidden=(32, 32)), table, desc,
                                  mesh, tx=optax.adam(2e-3))

    tr = mk()
    tr.train_pass(ds)
    tr.train_pass(ds)
    ev = tr.eval_pass(ds)
    assert ev["ins_num"] == 1200
    assert ev["auc"] > 0.6, ev["auc"]

    cm = CheckpointManager(str(tmp_path / "ck"))
    cm.save(tr)
    tr2 = mk()
    assert cm.restore(tr2) == tr.global_step
    ev2 = tr2.eval_pass(ds)   # restored state predicts identically
    assert np.isclose(ev2["auc"], ev["auc"], atol=1e-6)
    # restored trainer keeps training
    r = tr2.train_pass(ds)
    assert np.isfinite(r["last_loss"])


@pytest.mark.slow
def test_sharded_resident_scale(mesh, tmp_path):
    """Scale validation: realistic routing-bucket
    growth — wide key space (little cross-shard dedup), per-device batch
    128, multiple preloaded passes — streaming == resident parity holds
    at sizes where A/A2/K buckets actually grow across passes, and the
    routing plans keep every key."""
    from paddlebox_tpu.train import PassPreloader
    files = generate_criteo_files(str(tmp_path), num_files=4,
                                  rows_per_file=2500,
                                  vocab_per_slot=3000, seed=21)
    desc = DataFeedDesc.criteo(batch_size=128)
    desc.key_bucket_min = 4096
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.load_into_memory()
    assert ds.columnar.num_records == 10_000

    def mk():
        cfg = SparseSGDConfig(mf_create_thresholds=0.0,
                              mf_initial_range=0.0,
                              learning_rate=0.05, mf_learning_rate=0.05)
        table = ShardedEmbeddingTable(N, mf_dim=4,
                                      capacity_per_shard=1 << 15,
                                      cfg=cfg, req_bucket_min=1024,
                                      serve_bucket_min=1024)
        with flags_scope(log_period_steps=10 ** 6):
            return ShardedTrainer(DeepFM(hidden=(32, 16)), table, desc,
                                  mesh, tx=optax.adam(2e-3)), table

    tr_a, _ = mk()
    ra = tr_a.train_pass(ds)
    tr_b, table_b = mk()
    pre = PassPreloader(iter([ds, ds, ds]), table=None,
                        build_fn=tr_b.build_resident_pass)
    pre.start_next()
    results = []
    while True:
        rp = pre.wait()
        if rp is None:
            break
        pre.start_next()
        results.append(tr_b.train_pass_resident(rp))
    assert len(results) == 3
    rb = results[0]
    # pass 1 parity vs streaming (same init, same data, same order)
    assert rb["batches"] == ra["batches"]
    assert rb["ins_num"] == ra["ins_num"]
    assert np.isclose(rb["auc"], ra["auc"], atol=2e-3), (rb["auc"],
                                                        ra["auc"])
    # the wide key space really landed across all shards
    counts = [len(ix) for ix in table_b.indexes]
    assert min(counts) > 0 and sum(counts) > 20_000, counts
    # continued passes keep learning with finite metrics
    assert all(np.isfinite(r["auc"]) for r in results)
    assert results[-1]["auc"] > 0.55


@pytest.mark.slow  # same budget rationale as above
def test_sharded_resident_q8_wire_learns(mesh, tmp_path):
    """The sharded q8 float wire (dense int8 affine + u8 lsc, decoded in
    _decode_wire_step) trains and tracks the f32 wire's AUC."""
    files = generate_criteo_files(str(tmp_path), num_files=2,
                                  rows_per_file=1200, vocab_per_slot=40,
                                  seed=5)
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.load_into_memory()

    def mk(wire):
        cfg = SparseSGDConfig(mf_create_thresholds=0.0,
                              mf_initial_range=0.0,
                              learning_rate=0.1, mf_learning_rate=0.1)
        table = ShardedEmbeddingTable(N, mf_dim=4,
                                      capacity_per_shard=4096, cfg=cfg,
                                      req_bucket_min=256,
                                      serve_bucket_min=256)
        with flags_scope(log_period_steps=10 ** 6):
            return ShardedTrainer(DeepFM(hidden=(32, 32)), table, desc,
                                  mesh, tx=optax.adam(2e-3),
                                  float_wire=wire)

    tr_a = mk("f32")
    tr_b = mk("q8")
    for _ in range(3):
        ra = tr_a.train_pass_resident(ds)
        rb = tr_b.train_pass_resident(ds)
    assert rb["batches"] == ra["batches"]
    assert np.isclose(rb["auc"], ra["auc"], atol=5e-3), (rb["auc"],
                                                         ra["auc"])
    assert rb["auc"] > 0.55


# ---- fused computation-collective sharded step (ISSUE 11) --------------
def _model_digest(tr):
    """Raw-bytes identity (params + packed table + AUC) — the shared
    chunk-parity digest (scripts/scaling_check.py uses the same one)."""
    from paddlebox_tpu.train.checkpoint import sharded_state_digest
    return sharded_state_digest(tr)


@pytest.fixture(scope="module")
def chunk_parity_ds(tmp_path_factory):
    d = tmp_path_factory.mktemp("chunkds")
    files = generate_criteo_files(str(d), num_files=1, rows_per_file=500,
                                  vocab_per_slot=40, seed=29)
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.load_into_memory()
    return ds, desc


def _chunk_trainer(mesh, desc, chunks, zero1=False):
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0,
                          learning_rate=0.1, mf_learning_rate=0.1)
    table = ShardedEmbeddingTable(N, mf_dim=4, capacity_per_shard=4096,
                                  cfg=cfg, req_bucket_min=256,
                                  serve_bucket_min=256)
    with flags_scope(log_period_steps=10 ** 6, a2a_chunks=chunks):
        return ShardedTrainer(DeepFM(hidden=(16, 16)), table, desc, mesh,
                              tx=optax.adam(2e-3), zero1=zero1)


def test_a2a_chunked_digest_parity(mesh, chunk_parity_ds):
    """a2a_chunks ∈ {2, 4} reproduce the monolithic (=1) model digest
    BIT-FOR-BIT through train_pass, deterministically across 2 seeded
    runs. chunks=4 over criteo's 26 slots is the uneven-group case
    (26 % 4 != 0: groups of 7/7/6/6)."""
    ds, desc = chunk_parity_ds

    def run(chunks):
        tr = _chunk_trainer(mesh, desc, chunks)
        tr.train_pass(ds)
        return _model_digest(tr)

    want = run(1)
    assert run(1) == want, "monolithic digest not deterministic"
    for chunks in (2, 4):
        got = run(chunks)
        assert got == want, \
            f"a2a_chunks={chunks} diverged from the monolithic schedule"


def test_a2a_chunked_resident_digest_parity(mesh, chunk_parity_ds):
    """The chunked RESIDENT pass (uniform forced sections, grouped wire
    encode, per-schedule fori_loop runner) matches the monolithic
    resident digest bit-for-bit."""
    ds, desc = chunk_parity_ds

    def run(chunks):
        tr = _chunk_trainer(mesh, desc, chunks)
        rp = tr.build_resident_pass(ds)
        if chunks > 1:
            assert rp.sections, "chunked build lost its sections"
        tr.train_pass_resident(rp)
        return _model_digest(tr)

    assert run(2) == run(1)


def test_a2a_chunked_zero1_digest_parity(mesh, chunk_parity_ds):
    """ZeRO-1 variant: the chunked schedule interleaves the push
    exchange with the reduce-scatter/update/all-gather — still
    bit-identical to the monolithic order."""
    ds, desc = chunk_parity_ds

    def run(chunks):
        tr = _chunk_trainer(mesh, desc, chunks, zero1=True)
        tr.train_pass(ds)
        return _model_digest(tr)

    assert run(2) == run(1)


def test_a2a_chunked_fallback_non_qualified_keys(mesh):
    """make_batches keys are NOT slot-qualified (random ids across
    slots): the grouped plan builder must detect it before mutating the
    index and fall back to the monolithic layout — same plan bytes as
    groups=1."""
    cfg = SparseSGDConfig(mf_create_thresholds=1e9)
    t1 = ShardedEmbeddingTable(N, mf_dim=4, capacity_per_shard=256,
                               cfg=cfg, req_bucket_min=8,
                               serve_bucket_min=8)
    t2 = ShardedEmbeddingTable(N, mf_dim=4, capacity_per_shard=256,
                               cfg=cfg, req_bucket_min=8,
                               serve_bucket_min=8)
    batches = make_batches(N, seed=71)
    p1 = t1.prepare_global(batches)
    p2 = t2.prepare_global(batches, groups=2)
    assert p2.a2a_sections == () and p2.key_segments is None
    np.testing.assert_array_equal(p1.resp_idx, p2.resp_idx)
    np.testing.assert_array_equal(p1.gather_idx, p2.gather_idx)
    np.testing.assert_array_equal(p1.serve_rows, p2.serve_rows)


def test_a2a_grouped_plan_layout():
    """Grouped plan invariants on slot-qualified batches: sections sum
    to the A/K axes, every key's gather position lands inside its
    group's section, and each section keeps the pad slack."""
    from paddlebox_tpu.data.batch import SlotBatch
    from paddlebox_tpu.ops.seqpool_cvm import slot_group_bounds
    rng = np.random.default_rng(3)
    bs, S, k_pad = 8, 5, 40
    batches = []
    for _ in range(N):
        nk = int(rng.integers(S, k_pad // 2))
        slots = rng.integers(0, S, size=nk)
        keys = (slots * 1000 + rng.integers(1, 200, size=nk)).astype(
            np.uint64)
        segs = np.full(k_pad, bs * S, np.int32)
        ins = np.sort(rng.integers(0, bs, size=nk))
        segs[:nk] = (ins * S + slots).astype(np.int32)
        kp = np.zeros(k_pad, np.uint64)
        kp[:nk] = keys
        batches.append(SlotBatch(
            keys=kp, segments=segs, num_keys=nk,
            dense=rng.normal(size=(bs, 4)).astype(np.float32),
            label=rng.integers(0, 2, bs).astype(np.float32),
            show=np.ones(bs, np.float32),
            clk=np.zeros(bs, np.float32),
            batch_size=bs, num_slots=S))
    table = ShardedEmbeddingTable(N, mf_dim=4, capacity_per_shard=256,
                                  req_bucket_min=8, serve_bucket_min=8)
    c = 2
    p = table.prepare_global(batches, groups=c)
    assert len(p.a2a_sections) == c
    assert sum(p.a2a_sections) == p.req_capacity
    assert sum(p.key_sections) == p.gather_idx.shape[1]
    assert p.slot_sections == tuple(hi - lo for lo, hi
                                    in slot_group_bounds(S, c))
    assert p.key_segments is not None \
        and p.key_segments.shape == p.gather_idx.shape
    a_lo = np.concatenate([[0], np.cumsum(p.a2a_sections)])
    k_lo = np.concatenate([[0], np.cumsum(p.key_sections)])
    s_lo = np.concatenate([[0], np.cumsum(p.slot_sections)])
    for g in range(c):
        sec_gi = p.gather_idx[:, k_lo[g]:k_lo[g + 1]]
        j = sec_gi % p.req_capacity
        assert (j >= a_lo[g]).all() and (j < a_lo[g + 1]).all(), \
            f"group {g} gathers outside its A section"
        sec_seg = p.key_segments[:, k_lo[g]:k_lo[g + 1]]
        real = sec_seg < bs * S
        slots = sec_seg[real] % S
        assert (slots >= s_lo[g]).all() and (slots < s_lo[g + 1]).all()
        # pad slack: the last j of each pair's section serves the
        # sentinel (resp pad), so in-section pad keys read zeros
        assert (p.resp_idx[:, :, a_lo[g + 1] - 1]
                == p.serve_capacity - 1).all()
