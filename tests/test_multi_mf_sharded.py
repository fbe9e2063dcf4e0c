"""Multi-mf × sharded: per-slot embedding dims on the 8-device CPU mesh
(feature_value.h:42-185 — the dy-mf accessor as the sharded PS layout;
ps_gpu_wrapper.cc multi-mf BuildGPUTask)."""

import numpy as np
import jax
import optax
import pytest

from paddlebox_tpu.config import flags_scope
from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
from paddlebox_tpu.data.criteo import generate_criteo_files
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.parallel import make_mesh
from paddlebox_tpu.ps import MultiMfEmbeddingTable, SparseSGDConfig
from paddlebox_tpu.ps.multi_mf_sharded import MultiMfShardedTable
from paddlebox_tpu.train import MultiMfTrainer
from paddlebox_tpu.train.multi_mf_sharded import MultiMfShardedTrainer

N = 8


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= N
    return make_mesh(N)


def _dims():
    return [2] * 10 + [4] * 10 + [8] * 6   # three dim classes


@pytest.fixture(scope="module")
def criteo_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("criteo_mmfs")
    return generate_criteo_files(str(d), num_files=2, rows_per_file=1500,
                                 vocab_per_slot=40, seed=19)


def _ds(files, bs=32):
    desc = DataFeedDesc.criteo(batch_size=bs)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.load_into_memory()
    return ds, desc


def _cfg():
    return SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0,
                           learning_rate=0.05, mf_learning_rate=0.05)


def test_mmf_sharded_routing_and_slot_field(mesh, criteo_files):
    """Keys route to their slot's class table and, inside it, to their
    key%N owner shard; serve_slot carries GLOBAL slot ids."""
    ds, desc = _ds(criteo_files)
    table = MultiMfShardedTable(N, _dims(), capacity_per_shard=2048,
                                cfg=_cfg(), req_bucket_min=64,
                                serve_bucket_min=64)
    group = []
    for b in ds.batches():
        group.append(b)
        if len(group) == N:
            break
    plans = table.prepare_global(group)
    assert len(plans) == 3
    dims = np.asarray(_dims())
    for d, b in enumerate(group):
        segs = b.segments[:b.num_keys]
        slots = segs % b.num_slots
        for k, sl in zip(b.keys[:b.num_keys], slots):
            c = table.class_of_slot[sl]
            s = int(k) % N
            assert table.tables[c].indexes[s].lookup(
                np.array([k], np.uint64))[0] >= 0
    # serve_slot values are valid GLOBAL slot ids of the right class
    for c, p in enumerate(plans):
        valid = p.serve_slot[p.serve_valid > 0].astype(int)
        assert np.isin(valid, table.class_slots[c]).all()


def test_mmf_sharded_e2e_learns_and_matches_single_chip(
        mesh, criteo_files):
    """8-dev mesh multi-mf training with 3 dim classes learns the same
    planted signal as the single-chip MultiMfTrainer on the same data,
    and per-key pulled values keep per-slot widths."""
    ds, desc = _ds(criteo_files)
    with flags_scope(log_period_steps=10000):
        sh_table = MultiMfShardedTable(N, _dims(), capacity_per_shard=2048,
                                       cfg=_cfg(), req_bucket_min=256,
                                       serve_bucket_min=256)
        tr_m = MultiMfShardedTrainer(CtrDnn(hidden=(16, 8)), sh_table,
                                     desc, mesh, tx=optax.adam(1e-2),
                                     seed=3)
        sc_table = MultiMfEmbeddingTable(_dims(), capacity=1 << 12,
                                         cfg=_cfg(),
                                         unique_bucket_min=1024)
        tr_s = MultiMfTrainer(CtrDnn(hidden=(16, 8)), sc_table, desc,
                              tx=optax.adam(1e-2), seed=3)
    rm = rs = None
    for _ in range(4):
        rs = tr_s.train_pass(ds)
    # the mesh takes N-batch global steps (12/pass vs 94/pass single
    # chip) — give it more passes to reach the same optimizer-step count
    for _ in range(8):
        rm = tr_m.train_pass(ds)
    assert np.isfinite(rm["last_loss"])
    # both learn the planted signal; mesh quality tracks single-chip
    assert rs["auc"] > 0.60, rs["auc"]
    assert rm["auc"] > 0.60, rm["auc"]
    # one-sided: the mesh must not trail the single chip by much (it may
    # LEAD it — 8 passes of N-batch global steps see more data-epochs)
    assert rm["auc"] > rs["auc"] - 0.08, (rm["auc"], rs["auc"])
    # every class table holds features on the mesh
    assert all(t.feature_count() > 0 for t in sh_table.tables)
    # per-slot width contract on the mesh pull
    col = ds.columnar
    keys = col.keys[:100].astype(np.uint64)
    slots = col.key_slot[:100]
    vals = sh_table.pull(keys, slots)
    assert vals.shape == (100, 3 + 8)
    dims = np.asarray(_dims())
    for i in range(100):
        np.testing.assert_allclose(vals[i, 3 + dims[slots[i]]:], 0.0)
    assert (vals[:, 0] > 0).all()  # show counters accumulated


@pytest.mark.slow  # heavy on the virtual-CPU mesh —
# out of the tier-1 wall budget, runs in the slow tier
def test_mmf_sharded_save_load_roundtrip(mesh, criteo_files, tmp_path):
    ds, desc = _ds(criteo_files)
    with flags_scope(log_period_steps=10000):
        table = MultiMfShardedTable(N, _dims(), capacity_per_shard=2048,
                                    cfg=_cfg(), req_bucket_min=256,
                                    serve_bucket_min=256)
        tr = MultiMfShardedTrainer(CtrDnn(hidden=(16, 8)), table, desc,
                                   mesh, tx=optax.adam(1e-2))
        tr.train_pass(ds)
    path = str(tmp_path / "mmf_sharded")
    n = table.save_base(path)
    assert n == table.feature_count() > 0
    t2 = MultiMfShardedTable(N, _dims(), capacity_per_shard=2048,
                             cfg=_cfg())
    assert t2.load(path) == n
    col = ds.columnar
    keys = col.keys[:50].astype(np.uint64)
    slots = col.key_slot[:50]
    np.testing.assert_allclose(t2.pull(keys, slots),
                               table.pull(keys, slots), rtol=1e-6)


def _write_offset_pass_mmf(tmp_path, pass_id, vocab=40, rows=600):
    """Criteo files with per-pass disjoint value ranges (fresh features
    each pass — the day-k workload for the tiered window tests)."""
    import os
    rng = np.random.default_rng(300 + pass_id)
    d = tmp_path / f"mmfoff{pass_id}"
    os.makedirs(str(d), exist_ok=True)
    path = str(d / "part.txt")
    base = pass_id * vocab
    with open(path, "w") as fh:
        for _ in range(rows):
            dense = rng.integers(0, 100, size=13)
            cats = base + rng.integers(0, vocab, size=26)
            label = int(rng.random() < 0.5)
            fh.write(f"{label}\t" + "\t".join(str(int(v)) for v in dense)
                     + "\t" + "\t".join(format(int(c), "x") for c in cats)
                     + "\n")
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist([path])
    ds.load_into_memory()
    return ds, desc


@pytest.mark.slow  # same budget rationale; the tiered fence/epilogue
# surface stays covered in tier-1 by test_mmf_tiered_matches_untired
# and test_mmf_tiered_overlap_stage_and_delta
def test_mmf_tiered_full_cross_product(mesh, tmp_path):
    """Per-slot dims x beyond-HBM tiering x mesh sharding: 3 dim classes,
    3 disjoint day-passes, per-class capacity_per_shard far below the
    union — the host tiers carry the full model across pass windows, and
    save/load round-trips the whole thing."""
    from paddlebox_tpu.ps import BoxPSHelper
    from paddlebox_tpu.ps.multi_mf_sharded import MultiMfTieredShardedTable
    built = [_write_offset_pass_mmf(tmp_path, p) for p in range(3)]
    desc = built[0][1]
    table = MultiMfTieredShardedTable(
        N, _dims(), capacity_per_shard=128, cfg=_cfg(),
        req_bucket_min=64, serve_bucket_min=64)
    with flags_scope(log_period_steps=10000):
        tr = MultiMfShardedTrainer(CtrDnn(hidden=(16, 8)), table, desc,
                                   mesh, tx=optax.adam(1e-2))
    helper = BoxPSHelper(table, trainer=tr)
    for ds, _ in built:
        helper.begin_pass(ds)
        r = tr.train_pass(ds)
        assert np.isfinite(r["last_loss"])
        helper.end_pass(ds)
    total = table.feature_count()
    # union exceeds any single class's HBM window by construction:
    # 3 passes x 26 slots x 40 vocab of mostly-disjoint keys
    assert total > 2000, total
    for t in table.tables:
        for s in range(N):
            assert len(t.indexes[s]) <= t.capacity
    # host-tier pull serves per-slot widths for keys from EVERY pass
    ds0 = built[0][0]
    col = ds0.columnar
    keys = col.keys[:60].astype(np.uint64)
    slots = col.key_slot[:60]
    vals = table.pull(keys, slots)
    dims = np.asarray(_dims())
    assert (vals[:, 0] > 0).all()  # show counters from pass 0 persisted
    for i in range(60):
        np.testing.assert_allclose(vals[i, 3 + dims[slots[i]]:], 0.0)
    # full save/load round-trip through the tiers
    path = str(tmp_path / "mmf_tiered")
    n = table.save_base(path)
    assert n == total
    t2 = MultiMfTieredShardedTable(
        N, _dims(), capacity_per_shard=128, cfg=_cfg())
    assert t2.load(path) == n
    np.testing.assert_allclose(t2.pull(keys, slots),
                               table.pull(keys, slots), rtol=1e-6)


def test_mmf_tiered_overlap_stage_and_delta(mesh, tmp_path):
    """Overlapped staging × multi-mf: stage_pass during an OPEN pass
    fans out per dim class (keys route by their slot's class), and the
    next begin_pass consumes a pure per-class delta when working sets
    repeat — the round-4 persistent-window contract composed with the
    dim-class routing."""
    from paddlebox_tpu.ps import BoxPSHelper
    from paddlebox_tpu.ps.multi_mf_sharded import MultiMfTieredShardedTable
    ds, desc = _ds(generate_criteo_files(
        str(tmp_path / "ovl"), num_files=1, rows_per_file=800,
        vocab_per_slot=40, seed=77))
    table = MultiMfTieredShardedTable(
        N, _dims(), capacity_per_shard=2048, cfg=_cfg(),
        req_bucket_min=64, serve_bucket_min=64)
    with flags_scope(log_period_steps=10000):
        tr = MultiMfShardedTrainer(CtrDnn(hidden=(16, 8)), table, desc,
                                   mesh, tx=optax.adam(1e-2))
    helper = BoxPSHelper(table, trainer=tr)
    helper.begin_pass(ds)
    assert sum(t.last_pass_stats["staged"] for t in table.tables) > 0
    helper.stage_pass(ds)  # overlap: stage the SAME keys mid-pass
    r1 = tr.train_pass(ds)
    helper.end_pass(ds)
    helper.begin_pass(ds)  # consumes the overlapped per-class stages
    for t in table.tables:
        st = t.last_pass_stats
        assert st["staged"] == 0, st       # pure delta: all resident
        assert st["resident"] > 0, st
    r2 = tr.train_pass(ds)
    helper.end_pass(ds)
    assert np.isfinite(r1["last_loss"]) and np.isfinite(r2["last_loss"])


def test_mmf_tiered_matches_untired(mesh, tmp_path):
    """Tiering stays TRANSPARENT under multi-mf: when everything fits,
    the tiered cross-product equals the plain multi-mf sharded table
    trained straight through."""
    from paddlebox_tpu.ps import BoxPSHelper
    from paddlebox_tpu.ps.multi_mf_sharded import MultiMfTieredShardedTable
    ds, desc = _ds(generate_criteo_files(
        str(tmp_path / "flat"), num_files=1, rows_per_file=800,
        vocab_per_slot=30, seed=23))
    with flags_scope(log_period_steps=10000):
        plain = MultiMfShardedTable(N, _dims(), capacity_per_shard=2048,
                                    cfg=_cfg(), req_bucket_min=128,
                                    serve_bucket_min=128)
        tr_a = MultiMfShardedTrainer(CtrDnn(hidden=(16, 8)), plain, desc,
                                     mesh, tx=optax.adam(1e-2))
        tiered = MultiMfTieredShardedTable(
            N, _dims(), capacity_per_shard=2048, cfg=_cfg(),
            req_bucket_min=128, serve_bucket_min=128)
        tr_b = MultiMfShardedTrainer(CtrDnn(hidden=(16, 8)), tiered, desc,
                                     mesh, tx=optax.adam(1e-2))
    helper = BoxPSHelper(tiered, trainer=tr_b)
    ra = rb = None
    for _ in range(2):
        ra = tr_a.train_pass(ds)
        helper.begin_pass(ds)
        rb = tr_b.train_pass(ds)
        helper.end_pass(ds)
    assert np.isclose(rb["auc"], ra["auc"], atol=1e-6), (rb["auc"], ra["auc"])
    for x, y in zip(jax.tree.leaves(tr_a.state.params),
                    jax.tree.leaves(tr_b.state.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-7)
    col = ds.columnar
    keys = col.keys[:80].astype(np.uint64)
    slots = col.key_slot[:80]
    np.testing.assert_allclose(tiered.pull(keys, slots),
                               plain.pull(keys, slots),
                               rtol=1e-5, atol=1e-7)
