"""Tiered sharded PS (ps/tiered.py): HostStore-backed pass windows per
HBM shard on the 8-device CPU mesh — capacity beyond HBM composed with
the mesh trainer (BuildPull/BuildGPUTask/EndPass, ps_gpu_wrapper.cc:337,
684,983; LoadSSD2Mem, box_wrapper.cc:1415)."""

import time

import numpy as np
import jax
import optax
import pytest

from paddlebox_tpu.config import flags_scope
from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
from paddlebox_tpu.data.criteo import generate_criteo_files
from paddlebox_tpu.models import DeepFM
from paddlebox_tpu.parallel import make_mesh
from paddlebox_tpu.ps import (BoxPSHelper, SparseSGDConfig,
                              TieredShardedEmbeddingTable)
from paddlebox_tpu.ps.sharded import ShardedEmbeddingTable
from paddlebox_tpu.train.sharded import ShardedTrainer

N = 8


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= N
    return make_mesh(N)


def _cfg(**kw):
    kw.setdefault("mf_create_thresholds", 0.0)
    kw.setdefault("mf_initial_range", 0.0)
    kw.setdefault("learning_rate", 0.1)
    kw.setdefault("mf_learning_rate", 0.1)
    return SparseSGDConfig(**kw)


def _make_ds(tmp_path, seed, vocab=40, rows=1200, name="p"):
    files = generate_criteo_files(str(tmp_path / f"{name}{seed}"),
                                  num_files=2, rows_per_file=rows,
                                  vocab_per_slot=vocab, seed=seed)
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.load_into_memory()
    return ds, desc


def _write_offset_pass(tmp_path, pass_id, vocab=60, rows=800):
    """Criteo-format files whose categorical values live in a PER-PASS
    disjoint range [pass_id*vocab, (pass_id+1)*vocab) — models day-k data
    with fresh features, so pass windows are disjoint key sets."""
    import os
    rng = np.random.default_rng(100 + pass_id)
    d = tmp_path / f"off{pass_id}"
    os.makedirs(str(d), exist_ok=True)
    path = str(d / "part.txt")
    base = pass_id * vocab
    with open(path, "w") as fh:
        for _ in range(rows):
            dense = rng.integers(0, 100, size=13)
            cats = base + rng.integers(0, vocab, size=26)
            label = int(rng.random() < 0.5)
            dense_s = "\t".join(str(int(v)) for v in dense)
            cat_s = "\t".join(format(int(c), "x") for c in cats)
            fh.write(f"{label}\t{dense_s}\t{cat_s}\n")
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist([path])
    ds.load_into_memory()
    return ds, desc


def test_tiered_window_smaller_than_model(mesh, tmp_path):
    """Train 3 passes over DIFFERENT datasets with capacity_per_shard far
    below the total feature count: each pass window fits, the union does
    not — the host tier must carry the full model across windows."""
    built = [_write_offset_pass(tmp_path, p) for p in range(3)]
    datasets = [b[0] for b in built]
    desc = built[0][1]
    # each pass touches ≤ 26*60 = 1560 uniques (≈195/shard);
    # capacity_per_shard=256 cannot hold the 3-pass union (disjoint
    # per-pass value ranges)
    table = TieredShardedEmbeddingTable(
        N, mf_dim=4, capacity_per_shard=256, cfg=_cfg(),
        req_bucket_min=256, serve_bucket_min=256)
    with flags_scope(log_period_steps=10000):
        tr = ShardedTrainer(DeepFM(hidden=(16, 16)), table, desc, mesh,
                            tx=optax.adam(2e-3))
    helper = BoxPSHelper(table, trainer=tr)
    for ds in datasets:
        helper.begin_pass(ds)
        tr.train_pass(ds)
        helper.end_pass(ds)
    total = table.feature_count()
    assert total > N * table.capacity, (
        f"host tier must exceed HBM window: {total} <= {N * table.capacity}")
    # a pass window only ever held its own working set
    for s in range(N):
        assert len(table.indexes[s]) <= table.capacity


def test_tiered_matches_untired_sharded(mesh, tmp_path):
    """Tiering must be TRANSPARENT: when everything happens to fit, a
    tiered table trained over 2 pass windows equals a plain
    ShardedEmbeddingTable trained straight through — same AUC, same dense
    params, same per-key embeddings."""
    ds, desc = _make_ds(tmp_path, 13)

    with flags_scope(log_period_steps=10000):
        plain = ShardedEmbeddingTable(N, mf_dim=4, capacity_per_shard=4096,
                                      cfg=_cfg(), req_bucket_min=256,
                                      serve_bucket_min=256)
        tr_a = ShardedTrainer(DeepFM(hidden=(32, 32)), plain, desc, mesh,
                              tx=optax.adam(2e-3))
        tiered = TieredShardedEmbeddingTable(
            N, mf_dim=4, capacity_per_shard=4096, cfg=_cfg(),
            req_bucket_min=256, serve_bucket_min=256)
        tr_b = ShardedTrainer(DeepFM(hidden=(32, 32)), tiered, desc, mesh,
                              tx=optax.adam(2e-3))
    helper = BoxPSHelper(tiered, trainer=tr_b)
    ra = rb = None
    for _ in range(2):
        ra = tr_a.train_pass(ds)
        helper.begin_pass(ds)
        rb = tr_b.train_pass(ds)
        helper.end_pass(ds)
    assert rb["ins_num"] == ra["ins_num"]
    assert np.isclose(rb["auc"], ra["auc"], atol=1e-6), (rb["auc"], ra["auc"])
    for x, y in zip(jax.tree.leaves(tr_a.state.params),
                    jax.tree.leaves(tr_b.state.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-7)
    # per-key embed_w parity: read via host tier vs plain device rows
    for s in range(N):
        keys, rows = plain.indexes[s].items()
        w_plain = np.asarray(plain.state.embed_w)[s][rows]
        got = tiered.hosts[s].fetch(keys)["embed_w"]
        np.testing.assert_allclose(got, w_plain, rtol=1e-5, atol=1e-7)


def test_tiered_resident_matches_streaming(mesh, tmp_path):
    """Resident mesh passes inside tiered windows == streaming passes."""
    ds, desc = _make_ds(tmp_path, 17)

    def mk():
        t = TieredShardedEmbeddingTable(
            N, mf_dim=4, capacity_per_shard=4096, cfg=_cfg(),
            req_bucket_min=256, serve_bucket_min=256)
        with flags_scope(log_period_steps=10000):
            tr = ShardedTrainer(DeepFM(hidden=(32, 32)), t, desc, mesh,
                                tx=optax.adam(2e-3))
        return t, tr, BoxPSHelper(t, trainer=tr)

    ta, tr_a, ha = mk()
    tb, tr_b, hb = mk()
    ra = rb = None
    for _ in range(2):
        ha.begin_pass(ds)
        ra = tr_a.train_pass(ds)
        ha.end_pass(ds)
        hb.begin_pass(ds)
        rb = tr_b.train_pass_resident(ds)
        hb.end_pass(ds)
    assert rb["ins_num"] == ra["ins_num"]
    assert np.isclose(rb["auc"], ra["auc"], atol=2e-3), (rb["auc"], ra["auc"])
    for x, y in zip(jax.tree.leaves(tr_a.state.params),
                    jax.tree.leaves(tr_b.state.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-2, atol=2e-3)


def test_tiered_save_load_roundtrips_through_tiers(mesh, tmp_path):
    """save_base after a spill to the disk tier still exports the
    COMPLETE model; a fresh tiered table restores it and continues."""
    ds, desc = _make_ds(tmp_path, 23, vocab=30, rows=600)
    table = TieredShardedEmbeddingTable(
        N, mf_dim=4, capacity_per_shard=1024, cfg=_cfg(),
        req_bucket_min=256, serve_bucket_min=256)
    with flags_scope(log_period_steps=10000):
        tr = ShardedTrainer(DeepFM(hidden=(16, 16)), table, desc, mesh,
                            tx=optax.adam(2e-3))
    helper = BoxPSHelper(table, trainer=tr)
    helper.begin_pass(ds)
    tr.train_pass(ds)
    helper.end_pass(ds)
    n_feat = table.feature_count()

    delta = str(tmp_path / "delta.npz")
    nd = table.save_delta(delta)
    assert nd == n_feat  # everything written back this window

    # spill EVERYTHING cold (threshold high), then save_base: the export
    # must still carry the full model (spilled rows merge in)
    spilled = table.spill_cold(str(tmp_path / "spill"), threshold=1e9)
    assert spilled > 0
    base = str(tmp_path / "base.npz")
    assert table.save_base(base) == n_feat

    t2 = TieredShardedEmbeddingTable(
        N, mf_dim=4, capacity_per_shard=1024, cfg=_cfg(),
        req_bucket_min=256, serve_bucket_min=256)
    assert t2.load(base) == n_feat
    for s in range(N):
        keys, _ = table.hosts[s].index.items()
        if len(keys) == 0:
            continue
        a = table.hosts[s].fetch(keys)
        b = t2.hosts[s].fetch(keys)
        np.testing.assert_allclose(b["embed_w"], a["embed_w"],
                                   rtol=1e-6, atol=1e-8)
        np.testing.assert_allclose(b["show"], a["show"], rtol=1e-6)
    # restored table trains another window
    with flags_scope(log_period_steps=10000):
        tr2 = ShardedTrainer(DeepFM(hidden=(16, 16)), t2, desc, mesh,
                             tx=optax.adam(2e-3))
    h2 = BoxPSHelper(t2, trainer=tr2)
    h2.begin_pass(ds)
    r = tr2.train_pass(ds)
    h2.end_pass(ds)
    assert np.isfinite(r["last_loss"])


def test_tiered_spilled_rows_promote_on_stage(mesh, tmp_path):
    """A key whose row lives only in a disk-tier spill file must come
    back with its trained value when a later pass stages it
    (LoadSSD2Mem, box_wrapper.cc:1415)."""
    ds, desc = _make_ds(tmp_path, 29, vocab=20, rows=400)
    table = TieredShardedEmbeddingTable(
        N, mf_dim=4, capacity_per_shard=1024, cfg=_cfg(),
        req_bucket_min=256, serve_bucket_min=256)
    with flags_scope(log_period_steps=10000):
        tr = ShardedTrainer(DeepFM(hidden=(16, 16)), table, desc, mesh,
                            tx=optax.adam(2e-3))
    helper = BoxPSHelper(table, trainer=tr)
    helper.begin_pass(ds)
    tr.train_pass(ds)
    helper.end_pass(ds)
    # snapshot one trained key's value, spill everything, re-stage
    s0 = next(s for s in range(N) if len(table.hosts[s]) > 0)
    keys0, _ = table.hosts[s0].index.items()
    probe = keys0[:5]
    before = table.hosts[s0].fetch(probe)["embed_w"].copy()
    assert np.any(before != 0)
    table.save_base(str(tmp_path / "b.npz"))  # spill requires saved rows
    assert table.spill_cold(str(tmp_path / "sp"), threshold=1e9) > 0
    assert len(table.hosts[s0]) == 0  # gone from RAM
    # drop HBM residency so the next stage MUST go through the disk
    # tier (with the persistent window the keys would otherwise still
    # serve from HBM and never exercise promotion)
    table.drop_window()
    helper.begin_pass(ds)  # stage promotes from the disk tier
    rows = table.indexes[s0].lookup(probe)
    assert (rows >= 0).all()
    w = np.asarray(jax.device_get(table.state.embed_w))[s0][rows]
    np.testing.assert_allclose(w, before, rtol=1e-6)
    helper.end_pass(ds)


def test_tiered_lifecycle_shrink_and_merge(mesh, tmp_path):
    """shrink ages the host tier; merge_model folds a single-table-format
    save (split by key%N) with stat accumulation."""
    table = TieredShardedEmbeddingTable(
        N, mf_dim=2, capacity_per_shard=64, cfg=_cfg())
    # seed host rows directly through a pass-less write-back
    keys = np.arange(1, 41, dtype=np.uint64)
    per = table._split_by_owner(keys)
    for s in range(N):
        ks = per[s]
        f = {"show": np.full(len(ks), 4.0, np.float32),
             "clk": np.full(len(ks), 2.0, np.float32),
             "delta_score": np.zeros(len(ks), np.float32),
             "slot": np.zeros(len(ks), np.float32),
             "embed_w": ks.astype(np.float32),
             "embed_g2sum": np.zeros(len(ks), np.float32),
             "embedx_w": np.zeros((len(ks), 2), np.float32),
             "embedx_g2sum": np.zeros(len(ks), np.float32),
             "mf_size": np.zeros(len(ks), np.float32)}
        table.hosts[s].update(ks, f)
    assert table.feature_count() == 40

    # merge a single-table-format file: 20 overlapping keys (stats
    # accumulate, embed_w keeps live), 10 new (insert wholesale)
    mkeys = np.arange(21, 51, dtype=np.uint64)
    np.savez(str(tmp_path / "m.npz"), keys=mkeys,
             show=np.full(30, 10.0, np.float32),
             clk=np.full(30, 5.0, np.float32),
             delta_score=np.zeros(30, np.float32),
             slot=np.zeros(30, np.float32),
             embed_w=np.full(30, -7.0, np.float32),
             embed_g2sum=np.zeros(30, np.float32),
             embedx_w=np.zeros((30, 2), np.float32),
             embedx_g2sum=np.zeros(30, np.float32),
             mf_size=np.zeros(30, np.float32))
    assert table.merge_model(str(tmp_path / "m.npz")) == 30
    assert table.feature_count() == 50
    s21 = int(21) % N
    got = table.hosts[s21].fetch(np.array([21], np.uint64))
    assert got["show"][0] == 14.0          # 4 + 10 accumulated
    assert got["embed_w"][0] == 21.0       # live weight kept
    s50 = int(50) % N
    got = table.hosts[s50].fetch(np.array([50], np.uint64))
    assert got["embed_w"][0] == -7.0       # new key inserted wholesale

    # shrink: decay 0.5 → score of old-only keys (show 4→2) drops below
    # threshold while merged keys survive
    freed = table.shrink(delete_threshold=3.0, decay=0.5)
    assert freed > 0
    assert table.feature_count() < 50
    assert table.hosts[s21].index.lookup(
        np.array([21], np.uint64))[0] >= 0  # hot key survives


def test_tiered_adam_opt_ext_roundtrips(mesh):
    """SparseAdam per-row state (opt_ext block) survives the pass window:
    begin_pass → device mutation → end_pass → host store → next window
    (the reviewer-found embedx/opt_ext slicing hazard)."""
    from paddlebox_tpu.ps.sgd import SparseAdamConfig
    from paddlebox_tpu.ps.table import NUM_FIXED
    cfg = SparseAdamConfig(mf_create_thresholds=0.0, mf_initial_range=0.0)
    table = TieredShardedEmbeddingTable(N, mf_dim=2, capacity_per_shard=32,
                                        cfg=cfg)
    assert table.opt_ext > 0
    keys = np.arange(1, 25, dtype=np.uint64)
    table.begin_pass(keys)
    # simulate a jit update: plant distinct embedx and opt_ext values,
    # and mark the rows touched as the trainer's prepare/mark_trained
    # paths do (end_pass writes back only touched rows)
    mf_end = NUM_FIXED + table.mf_dim
    data = np.asarray(jax.device_get(table.state.data)).copy()
    for s in range(N):
        _, rows = table.indexes[s].items()
        data[s][rows, NUM_FIXED:mf_end] = 2.0
        data[s][rows, mf_end:] = 0.5
        table._touched[s][rows] = True
    table.state = type(table.state).from_logical(data, table.capacity,
                                                 ext=table.opt_ext)
    table.end_pass()
    # embedx stayed mf_dim-wide and opt_ext persisted separately
    for s in range(N):
        ks, _ = table.hosts[s].index.items()
        if not len(ks):
            continue
        got = table.hosts[s].fetch(ks)
        assert got["embedx_w"].shape[1] == 2
        np.testing.assert_allclose(got["embedx_w"], 2.0)
        np.testing.assert_allclose(got["opt_ext"], 0.5)
    # next window sees both back
    table.begin_pass(keys)
    d2 = np.asarray(jax.device_get(table.state.data))
    for s in range(N):
        _, rows = table.indexes[s].items()
        np.testing.assert_allclose(d2[s][rows, NUM_FIXED:mf_end], 2.0)
        np.testing.assert_allclose(d2[s][rows, mf_end:], 0.5)
    table.end_pass()


def _write_overlap_pass(tmp_path, pass_id, vocab=100, step=10, rows=600):
    """Criteo-format files whose categorical values live in a SLIDING
    range [pass_id*step, pass_id*step + vocab) — consecutive passes
    share ~(vocab-step)/vocab of their key range (the CTR workload:
    day k+1 mostly re-touches day k's features)."""
    import os
    rng = np.random.default_rng(500 + pass_id)
    d = tmp_path / f"ovl{pass_id}"
    os.makedirs(str(d), exist_ok=True)
    path = str(d / "part.txt")
    base = pass_id * step
    with open(path, "w") as fh:
        for _ in range(rows):
            dense = rng.integers(0, 100, size=13)
            cats = base + rng.integers(0, vocab, size=26)
            label = int(rng.random() < 0.5)
            dense_s = "\t".join(str(int(v)) for v in dense)
            cat_s = "\t".join(format(int(c), "x") for c in cats)
            fh.write(f"{label}\t{dense_s}\t{cat_s}\n")
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist([path])
    ds.load_into_memory()
    return ds, desc


def test_delta_staging_equals_full_staging(mesh, tmp_path):
    """THE delta-staging contract (box_wrapper.cc:129-186): with ~90%
    overlapping pass working sets, a table reusing its resident window
    (delta staging, the default) must match a table that re-stages the
    full working set every pass (drop_window between passes) — same AUC,
    same dense params, bit-identical host-tier values. And the staged
    row count per pass must equal the working-set DELTA, not its size."""
    built = [_write_overlap_pass(tmp_path, p) for p in range(4)]
    datasets = [b[0] for b in built]
    desc = built[0][1]

    def mk():
        t = TieredShardedEmbeddingTable(
            N, mf_dim=4, capacity_per_shard=2048, cfg=_cfg(),
            req_bucket_min=256, serve_bucket_min=256)
        with flags_scope(log_period_steps=10000):
            tr = ShardedTrainer(DeepFM(hidden=(16, 16)), t, desc, mesh,
                                tx=optax.adam(2e-3))
        return t, tr, BoxPSHelper(t, trainer=tr)

    ta, tr_a, ha = mk()   # delta (default)
    tb, tr_b, hb = mk()   # forced full re-staging
    resident: set = set()
    for p, ds in enumerate(datasets):
        want = set(ds.pass_keys().tolist())
        ha.begin_pass(ds)
        st = ta.last_pass_stats
        # staged == |want \ resident|: wire ∝ working-set delta
        assert st["staged"] == len(want - resident), (p, st)
        assert st["resident"] == len(want & resident), (p, st)
        assert st["evicted"] == 0
        resident |= want
        ra = tr_a.train_pass(ds)
        ha.end_pass(ds)

        tb.drop_window()  # forces full staging: everything re-fetched
        hb.begin_pass(ds)
        assert tb.last_pass_stats["staged"] == len(want)
        rb = tr_b.train_pass(ds)
        hb.end_pass(ds)
        assert np.isclose(ra["auc"], rb["auc"], atol=1e-9)
    # pass 2+ staged a small fraction of the working set
    assert st["staged"] < 0.25 * (st["staged"] + st["resident"])
    for x, y in zip(jax.tree.leaves(tr_a.state.params),
                    jax.tree.leaves(tr_b.state.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for s in range(N):
        keys, _ = ta.hosts[s].index.items()
        keys = np.sort(keys)
        kb, _ = tb.hosts[s].index.items()
        np.testing.assert_array_equal(keys, np.sort(kb))
        a = ta.hosts[s].fetch(keys)
        b = tb.hosts[s].fetch(keys)
        for f in ta.hosts[s].fields:
            np.testing.assert_array_equal(a[f], b[f], err_msg=f"s{s} {f}")


def test_async_epilogue_parity_bit_identical(mesh, tmp_path):
    """ISSUE 4 parity suite: overlapped end_pass/begin_pass (async
    epilogue ON, the default) over 4 passes with ~90% key overlap must
    be BIT-IDENTICAL to the synchronous path — same dense params, same
    host-tier values, same staged-delta accounting — and the async run
    must actually run background write-back jobs."""
    built = [_write_overlap_pass(tmp_path, p, vocab=100, step=10)
             for p in range(4)]
    datasets = [b[0] for b in built]
    desc = built[0][1]

    def run(async_mode):
        with flags_scope(async_end_pass=async_mode):
            t = TieredShardedEmbeddingTable(
                N, mf_dim=4, capacity_per_shard=2048, cfg=_cfg(),
                req_bucket_min=256, serve_bucket_min=256)
            with flags_scope(log_period_steps=10000):
                tr = ShardedTrainer(DeepFM(hidden=(16, 16)), t, desc,
                                    mesh, tx=optax.adam(2e-3))
            h = BoxPSHelper(t, trainer=tr)
            staged = []
            for i, ds in enumerate(datasets):
                h.begin_pass(ds)
                staged.append(t.last_pass_stats["staged"])
                if i + 1 < len(datasets):
                    h.stage_pass(datasets[i + 1])  # overlapped fetch
                tr.train_pass(ds)
                h.end_pass(ds)  # async: returns before write-back lands
            t.fence()
            return t, tr, staged

    ta, tr_a, staged_a = run(False)   # synchronous oracle
    tb, tr_b, staged_b = run(True)    # async epilogue (default)
    assert staged_b == staged_a, (staged_b, staged_a)
    assert tb.endpass_stats()["jobs_run"] >= len(datasets)
    assert ta.endpass_stats()["jobs_run"] == 0  # sync ran inline
    for x, y in zip(jax.tree.leaves(tr_a.state.params),
                    jax.tree.leaves(tr_b.state.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for s in range(N):
        ka, fa = ta.hosts[s].export_rows()
        kb, fb = tb.hosts[s].export_rows()
        oa, ob = np.argsort(ka), np.argsort(kb)
        np.testing.assert_array_equal(ka[oa], kb[ob])
        assert np.abs(fa["embed_w"]).sum() > 0  # actually trained
        for f in ta.hosts[s].fields:
            np.testing.assert_array_equal(fa[f][oa], fb[f][ob],
                                          err_msg=f"s{s} {f}")


def test_async_writeback_failure_surfaces_at_fence(mesh):
    """A mid-write-back failure (endpass.writeback seam) must surface
    LOUDLY at the fence — through an explicit fence(), AND through the
    implicit read barrier on any host-tier access — never as silent
    row loss; once surfaced, the error is consumed."""
    from paddlebox_tpu.ps.epilogue import EndPassWritebackError
    from paddlebox_tpu.resilience.faults import FaultPlan, installed

    def check(surface):
        """One failing end_pass; ``surface(table)`` must raise the held
        error. The plan stays installed until the background job ran
        (the surface call fences)."""
        table = TieredShardedEmbeddingTable(
            N, mf_dim=2, capacity_per_shard=64, cfg=_cfg())
        keys = np.arange(1, 33, dtype=np.uint64)
        table.begin_pass(keys)
        from paddlebox_tpu.ps.table import FIELD_COL
        data = np.asarray(jax.device_get(table.state.data)).copy()
        with table.host_lock:
            for s in range(N):
                _, rows = table.indexes[s].items()
                data[s][rows, FIELD_COL["embed_w"]] = 3.0
                table._touched[s][rows] = True
        data[:, table.capacity, :] = 0.0
        table.state = type(table.state).from_logical(
            data, table.capacity, ext=table.opt_ext)
        with installed(FaultPlan.parse(
                "endpass.writeback:fail:nth=1,exc=crash")):
            table.end_pass()       # submit succeeds; the JOB fails
            with pytest.raises(EndPassWritebackError):
                surface(table)
        return table

    t1 = check(lambda t: t.fence())          # explicit fence
    t1.fence()                               # surfaced once — consumed
    check(lambda t: t.feature_count())       # implicit read barrier
    check(lambda t: t.save_delta("/tmp/never_epilogue.npz"))  # capture


def test_overlap_stage_reconciles_mid_pass_assign(mesh):
    """The overlap race, resolved by the begin_pass reconcile: key K is
    staged for pass 2 while pass 1 is open (host value fetched), then
    pass 1's streaming training assigns K mid-pass (outside its staged
    set) and trains it. The stale fetched value must be DROPPED — the
    resident row (written back at end_pass 1) wins."""
    from paddlebox_tpu.ps.table import FIELD_COL, FIELDS
    table = TieredShardedEmbeddingTable(N, mf_dim=2, capacity_per_shard=64,
                                        cfg=_cfg())
    K = np.uint64(200)
    s = int(K) % N
    # host tier knows K with embed_w = -5
    f0 = {f: np.zeros((1, 2), np.float32) if f == "embedx_w"
          else np.zeros(1, np.float32) for f in FIELDS}
    f0["embed_w"] = np.array([-5.0], np.float32)
    table.hosts[s].update(np.array([K]), f0)

    k1 = np.arange(1, 17, dtype=np.uint64)
    table.begin_pass(k1)
    # overlap: stage pass 2 (includes K, missing from the window → its
    # host value -5 is fetched) while pass 1 is open
    k2 = np.concatenate([np.arange(9, 17, dtype=np.uint64), [K]])
    table.stage(k2, background=False)
    assert np.any(np.concatenate(table._stage.new_keys) == K)
    # pass 1's streaming step assigns K mid-pass and trains it to 7
    with table.host_lock:
        row = int(table.indexes[s].assign(np.array([K]))[0])
        table._touched[s][row] = True
    data = np.asarray(jax.device_get(table.state.data)).copy()
    data[s][row, FIELD_COL["embed_w"]] = 7.0
    table.state = type(table.state).from_logical(data, table.capacity,
                                                 ext=table.opt_ext)
    table.end_pass()
    assert table.hosts[s].fetch(np.array([K]))["embed_w"][0] == 7.0
    table.begin_pass(k2)
    st = table.last_pass_stats
    # K was reconciled away: resident, not staged
    row2 = int(table.indexes[s].lookup(np.array([K]))[0])
    w = float(np.asarray(jax.device_get(
        table.state.data[s][row2, FIELD_COL["embed_w"]])))
    assert w == 7.0, f"stale staged value overwrote the trained row: {w}"
    table.end_pass()


def test_eviction_writes_back_touched_rows(mesh):
    """Capacity-pressure eviction: clean rows evict silently (host tier
    already has their values), rows touched since the last write-back
    are written back before release."""
    from paddlebox_tpu.ps.table import FIELD_COL
    cap = 16
    table = TieredShardedEmbeddingTable(N, mf_dim=2,
                                        capacity_per_shard=cap, cfg=_cfg())
    k1 = np.arange(0, N * cap, dtype=np.uint64)       # fills every shard
    table.begin_pass(k1)
    # train every row, write back, window stays full and clean
    for s in range(N):
        _, rows = table.indexes[s].items()
        table._touched[s][rows] = True
    data = np.asarray(jax.device_get(table.state.data)).copy()
    data[:, :, FIELD_COL["embed_w"]] = 3.0
    data[:, table.capacity, :] = 0.0  # keep the sentinel row zero
    table.state = type(table.state).from_logical(data, table.capacity,
                                                 ext=table.opt_ext)
    table.end_pass()
    # between passes, one row is dirtied again (streaming use outside
    # the pass protocol): its eviction must write back
    s0 = 0
    keys0, rows0 = table.indexes[s0].items()
    probe_key, probe_row = keys0[0], rows0[0]
    data = np.asarray(jax.device_get(table.state.data)).copy()
    data[s0][probe_row, FIELD_COL["embed_w"]] = 9.0
    table.state = type(table.state).from_logical(data, table.capacity,
                                                 ext=table.opt_ext)
    table._touched[s0][probe_row] = True
    # pass 2: disjoint working set, full capacity → evicts everything
    k2 = np.arange(N * cap, 2 * N * cap, dtype=np.uint64)
    table.begin_pass(k2)
    st = table.last_pass_stats
    assert st["evicted"] > 0
    assert st["evicted_writeback"] == 1  # only the dirtied row
    got = table.hosts[s0].fetch(np.array([probe_key]))["embed_w"][0]
    assert got == 9.0, "touched evicted row lost its update"
    # clean evicted rows kept their pass-1 write-back values
    other = keys0[1]
    assert table.hosts[s0].fetch(
        np.array([other]))["embed_w"][0] == 3.0
    table.end_pass()


def test_drop_window_discards_pending_stage(mesh):
    """drop_window (auto-run by load/merge_model/shrink) must discard a
    pending stage — its fetched values and resident/missing split
    predate the host-tier mutation — and zero the device rows so
    released rows read as fresh zero rows."""
    from paddlebox_tpu.ps.table import FIELDS
    table = TieredShardedEmbeddingTable(N, mf_dim=2, capacity_per_shard=32,
                                        cfg=_cfg())
    k1 = np.arange(1, 17, dtype=np.uint64)
    # seed host values and make keys resident once
    table.begin_pass(k1)
    for s in range(N):
        _, rows = table.indexes[s].items()
        table._touched[s][rows] = True
    from paddlebox_tpu.ps.table import FIELD_COL
    data = np.asarray(jax.device_get(table.state.data)).copy()
    data[:, :, FIELD_COL["show"]] = 5.0
    data[:, table.capacity, :] = 0.0
    table.state = type(table.state).from_logical(data, table.capacity,
                                                 ext=table.opt_ext)
    table.end_pass()
    # stage k2 (all resident → nothing fetched), then mutate the host
    # tier: the stale stage must not survive
    table.stage(k1, background=False)
    assert table._stage is not None
    table.shrink(delete_threshold=0.0, decay=0.5)  # decays show 5→2.5
    assert table._stage is None, "drop_window kept a stale stage"
    assert not np.any(np.asarray(jax.device_get(table.state.packed))), (
        "drop_window left stale values in released device rows")
    # next pass re-fetches everything, with post-shrink values
    table.begin_pass(k1)
    assert table.last_pass_stats["staged"] == len(k1)
    assert table.last_pass_stats["resident"] == 0
    for s in range(N):
        keys, rows = table.indexes[s].items()
        if not len(keys):
            continue
        show = np.asarray(jax.device_get(table.state.data))[s][
            rows, FIELD_COL["show"]]
        np.testing.assert_allclose(show, 2.5)
    table.end_pass()


def test_tiered_guards(mesh):
    table = TieredShardedEmbeddingTable(N, mf_dim=2, capacity_per_shard=16)
    with pytest.raises(RuntimeError):
        table.end_pass()
    table.begin_pass(np.arange(8, dtype=np.uint64))
    with pytest.raises(RuntimeError):
        table.begin_pass(np.arange(8, dtype=np.uint64))
    with pytest.raises(RuntimeError):
        table.save_base("/tmp/never.npz")
    with pytest.raises(RuntimeError):
        table.drop_window()
    # staging DURING an open pass is the overlap contract — legal; but a
    # second concurrent stage is not
    table.stage(np.arange(8, 16, dtype=np.uint64), background=False)
    with pytest.raises(RuntimeError):
        table.stage(np.arange(8, dtype=np.uint64))
    table.end_pass()
    table.begin_pass(np.arange(8, 16, dtype=np.uint64))  # consumes stage
    table.end_pass()
    # per-shard capacity guard
    with pytest.raises(ValueError):
        table.stage(np.arange(N * 64, dtype=np.uint64), background=False)


def test_tiered_preloader_overlapped_plan_build(mesh, tmp_path):
    """PassPreloader(build_fn=trainer.build_resident_pass) over a tiered
    table (preload_into_memory box_wrapper.h:1142):
    pass k+1's ROUTING PLAN builds during pass k (plan_scope pending
    rows), its host values stage overlapped, and begin_pass scatters the
    staged values into the plan-baked rows instead of keeping zeros —
    the model matches the build-after-begin oracle."""
    from paddlebox_tpu.train.device_pass import PassPreloader

    ds_a, desc = _make_ds(tmp_path, 31)
    # ds_b draws from an OFFSET value range → a real key delta vs ds_a
    files_b = generate_criteo_files(str(tmp_path / "q32"), num_files=2,
                                    rows_per_file=1200, vocab_per_slot=40,
                                    seed=32, value_base=1000)
    ds_b = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds_b.set_filelist(files_b)
    ds_b.load_into_memory()
    datasets = [ds_a, ds_b, ds_a, ds_b]

    def mk():
        t = TieredShardedEmbeddingTable(
            N, mf_dim=4, capacity_per_shard=4096, cfg=_cfg(),
            req_bucket_min=256, serve_bucket_min=256)
        with flags_scope(log_period_steps=10000):
            tr = ShardedTrainer(DeepFM(hidden=(16, 16)), t, desc, mesh,
                                tx=optax.adam(2e-3))
        return t, tr, BoxPSHelper(t, trainer=tr)

    # oracle: the sequential order (begin_pass, THEN build+train)
    ta, tr_a, ha = mk()
    staged_a = []
    for ds in datasets:
        ha.begin_pass(ds)
        staged_a.append(ta.last_pass_stats["staged"])
        tr_a.train_pass_resident(ds)
        ha.end_pass(ds)

    # overlapped: the preloader builds pass k+1's plan while k trains
    tb, tr_b, hb = mk()
    pre = PassPreloader(iter(datasets), build_fn=tr_b.build_resident_pass)
    pre.start_next()
    staged_b = []
    pending_seen = 0
    for i, ds in enumerate(datasets):
        rp = pre.wait()
        assert rp is not None
        hb.begin_pass(ds)     # staged values win over plan zero rows
        staged_b.append(tb.last_pass_stats["staged"])
        if pre.start_next() and i + 1 < len(datasets):
            hb.stage_pass(datasets[i + 1])   # host fetch overlaps too
        tr_b.train_pass_resident(rp)         # the PREBUILT pass
        with tb.host_lock:  # consolidated view (plan assigns append
            pending_seen = max(  # O(1) chunks; _pending_of merges them)
                pending_seen,
                sum(len(tb._pending_of(s)) for s in range(tb.n)))
        hb.end_pass(ds)
    # the mechanism actually engaged: some future-pass keys were
    # plan-assigned as pending before their begin_pass
    assert pending_seen > 0
    # begin_pass staged the same deltas as the sequential oracle
    assert staged_b == staged_a, (staged_b, staged_a)
    assert staged_b[1] > 0          # ds_b's keys were a real delta
    # model parity: dense params and per-key host-tier values (row ids
    # differ — plan-order vs promote-order assignment — so reductions
    # reorder; values agree to float-drift tolerance)
    for x, y in zip(jax.tree.leaves(tr_a.state.params),
                    jax.tree.leaves(tr_b.state.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-2, atol=2e-3)
    for s in range(N):
        ka, fa = ta.hosts[s].export_rows()
        kb, fb = tb.hosts[s].export_rows()
        oa, ob = np.argsort(ka), np.argsort(kb)
        np.testing.assert_array_equal(ka[oa], kb[ob])
        assert np.abs(fa["embed_w"][oa]).sum() > 0  # actually trained
        np.testing.assert_allclose(fa["embed_w"][oa], fb["embed_w"][ob],
                                   rtol=2e-2, atol=2e-3)


# ---- SSD third tier (ps/ssd.py, ISSUE 7): spill × async-epilogue ----


def test_ssd_demote_fences_inflight_endpass(tmp_path):
    """Demotion racing an in-flight end_pass write-back must FENCE
    first: the write-back lands (marking its rows touched) before the
    demote selects victims, so a pass's freshly written rows never
    spill while colder candidates exist."""
    from paddlebox_tpu.ps.host_store import HostStore
    from paddlebox_tpu.ps.table import FIELDS

    def mk_fields(n, v):
        return {f: (np.full((n, 2), v, np.float32) if f == "embedx_w"
                    else np.full(n, v, np.float32)) for f in FIELDS}

    hs = HostStore(mf_dim=2, capacity=64,
                   ssd_dir=str(tmp_path / "tier"))
    cold = np.arange(1, 41, dtype=np.uint64)
    hs.update(cold, mk_fields(40, 1.0))
    hs.export_rows()            # clear touched: cold rows are spillable
    hot = np.arange(101, 111, dtype=np.uint64)

    barrier_calls = []

    def inflight_writeback():
        # stands in for PassEpilogue.fence draining an end_pass job:
        # the job lands the hot rows (update marks them touched)
        if not barrier_calls:
            hs.update(hot, mk_fields(10, 9.0))
        barrier_calls.append(1)

    hs.read_barrier = inflight_writeback
    with flags_scope(host_demote_watermark=0.5, host_demote_target=0.25):
        n = hs.demote_to_watermark(barrier=True)
    assert barrier_calls, "demote never fenced the epilogue"
    assert n > 0
    # every hot (just-written-back, touched) key stayed in RAM …
    assert (hs.index.lookup(hot) >= 0).all()
    assert not hs.ssd.contains(hot).any()
    # … and the spilled set is cold keys only
    assert hs.ssd.contains(cold).sum() == n


def test_ssd_promote_under_plan_rollback_releases_rows(tmp_path):
    """A promote landing under a plan_scope that ROLLS BACK releases
    its plan-assigned window rows (no leaked pending pins), while the
    promoted host rows keep their trained values — the next real pass
    stages them normally."""
    import sys
    sys.path.insert(0, "scripts")
    from pipeline_check import _train_mutate

    with flags_scope(warmup_pass_scatter=False):
        table = TieredShardedEmbeddingTable(
            2, mf_dim=2, capacity_per_shard=256, cfg=_cfg(),
            host_capacity=1 << 12, ssd_dir=str(tmp_path / "tier"))
        keys = np.arange(1, 65, dtype=np.uint64)
        table.stage(keys, background=False)
        table.begin_pass(keys)
        _train_mutate(table, 0)           # embed_w = key*0.001 + 1
        table.end_pass()
        table.fence()
        table.drop_window()
        # force the whole trained set to the SSD tier
        for h in table.hosts:
            h.demote_cold()
        assert table.has_spilled_rows()
        assert sum(len(h) for h in table.hosts) == 0

        with pytest.raises(RuntimeError, match="boom"):
            with table.plan_scope():
                # a preloader build: plan-assign the keys as pending …
                for s, ks in enumerate(table._split_by_owner(keys)):
                    with table.host_lock:
                        table.indexes[s].assign(ks)
                        table._note_plan_assigned(s, ks)
                # … promote their spilled values host-ward …
                assert table.prefetch_promote(keys) == len(keys)
                raise RuntimeError("boom")   # … and the build dies

        # rollback released the plan's window rows and pending pins
        assert table.obs_stats()["pending"] == 0
        for s, ks in enumerate(table._split_by_owner(keys)):
            assert (table.indexes[s].lookup(ks) == -1).all()
        # the promote itself is NOT rolled back: rows live in host RAM
        # with their trained values (RAM is authoritative; the tier
        # copy was consumed exactly once)
        assert not table.has_spilled_rows()
        for s, ks in enumerate(table._split_by_owner(keys)):
            got = table.hosts[s].fetch(ks)["embed_w"]
            np.testing.assert_allclose(
                got, ks.astype(np.float64) * 0.001 + 1, rtol=1e-6)
        # and a real pass over the same keys stages cleanly
        table.stage(keys, background=False)
        assert table.begin_pass(keys) == len(keys)
        table.end_pass()
        table.fence()


def test_ssd_segment_compaction(tmp_path):
    """Compaction rewrites a sealed segment whose live fraction fell
    below the threshold: live rows re-append bit-identically, the dead
    file unlinks, and ONLY the compaction accounting books the rewrite
    — the real demote/promote counters (and the promote-wait
    critical-path attribution) stay untouched."""
    import os

    from paddlebox_tpu.ps.ssd import SsdTier
    tier = SsdTier(str(tmp_path / "t"), width=4, segment_rows=8,
                   compact_live_frac=0.9)
    keys = np.arange(1, 9, dtype=np.uint64)
    rows = np.arange(32, dtype=np.float32).reshape(8, 4)
    tier.append(keys, rows)                    # fills + seals segment 0
    path0 = tier.segment_paths()[0]
    assert tier.discard(keys[:6]) == 6         # live 2/8 < 0.9
    moved = tier.maybe_compact()
    assert moved == 2
    st = tier.stats()
    assert st["compacted_rows"] == 2
    assert st["demoted_rows"] == 8 and st["promoted_rows"] == 0, st
    assert st["promote_sec"] == 0.0 and st["promote_wait_sec"] == 0.0
    assert not os.path.exists(path0)           # dead segment unlinked
    fk, frows, _ = tier.take(keys[6:])
    np.testing.assert_array_equal(np.sort(fk), keys[6:])
    order = np.argsort(fk)
    np.testing.assert_array_equal(frows[order], rows[6:])
    assert len(tier) == 0


def test_ssd_tier_sweeps_leftover_segments(tmp_path):
    """A restarted process reusing the same tier directory must NOT
    append into the dead process's segment files (offsets would address
    the old content — silent wrong rows); leftovers are swept at init
    (the tier is a capacity cache; checkpoints are self-contained)."""
    import os

    from paddlebox_tpu.ps.ssd import SsdTier
    root = str(tmp_path / "t")
    t1 = SsdTier(root, width=4, segment_rows=8)
    keys = np.arange(1, 5, dtype=np.uint64)
    t1.append(keys, np.full((4, 4), 7.0, np.float32))
    old = t1.segment_paths()
    assert old and all(os.path.exists(p) for p in old)
    t2 = SsdTier(root, width=4, segment_rows=8)   # "restart"
    assert len(t2) == 0
    assert not any(os.path.exists(p) for p in old)  # swept
    t2.append(keys, np.full((4, 4), 42.0, np.float32))
    fk, rows, _ = t2.take(keys)
    assert len(fk) == 4
    np.testing.assert_array_equal(rows, np.full((4, 4), 42.0, np.float32))


def test_ssd_take_deduplicates_keys(tmp_path):
    """A key duplicated in one take() promotes (and leaves the index)
    exactly once — no KeyError, no double-counted row."""
    from paddlebox_tpu.ps.ssd import SsdTier
    tier = SsdTier(str(tmp_path / "t"), width=4)
    keys = np.arange(1, 4, dtype=np.uint64)
    tier.append(keys, np.tile(keys.astype(np.float32)[:, None], (1, 4)))
    dup = np.array([2, 2, 1, 2], np.uint64)
    fk, rows, _ = tier.take(dup)
    np.testing.assert_array_equal(np.sort(fk), [1, 2])
    assert len(tier) == 1
    assert tier.stats()["promoted_rows"] == 2


def test_ssd_touched_bit_preserves_delta(tmp_path):
    """A row demoted with an un-exported update carries its touched bit
    through the tier: save_delta/export_rows(delta=True) still emit it
    exactly once — demotion never loses a pending delta row."""
    from paddlebox_tpu.ps.host_store import HostStore
    from paddlebox_tpu.ps.table import FIELDS

    hs = HostStore(mf_dim=2, capacity=1 << 10,
                   ssd_dir=str(tmp_path / "tier"))
    keys = np.arange(1, 11, dtype=np.uint64)
    data = {f: (np.full((10, 2), 5.0, np.float32) if f == "embedx_w"
                else np.arange(10, dtype=np.float32)) for f in FIELDS}
    hs.update(keys, data)                      # touched
    assert hs.demote_cold(include_touched=True) == 10
    assert len(hs) == 0 and len(hs.ssd) == 10
    dk, dfields = hs.export_rows(delta=True)   # tier-touched rows merge
    order = np.argsort(dk)
    np.testing.assert_array_equal(dk[order], keys)
    np.testing.assert_allclose(dfields["embed_w"][order],
                               data["embed_w"])
    dk2, _ = hs.export_rows(delta=True)        # … exactly once
    assert len(dk2) == 0
    # the full export still carries the (now clean) tier rows
    fk, _ = hs.export_rows()
    assert len(fk) == 10


# ---- unified pass pipeline (ISSUE 9): queued stages × async eviction ----


def _plant_window_values(table, value: float) -> None:
    """Write ``value`` into every resident row's embed_w and mark the
    rows touched (a deterministic stand-in for a trained pass)."""
    from paddlebox_tpu.ps.table import FIELD_COL
    data = np.asarray(jax.device_get(table.state.data)).copy()
    with table.host_lock:
        for s in range(table.n):
            _, rows = table.indexes[s].items()
            if not len(rows):
                continue
            data[s][rows, FIELD_COL["embed_w"]] = value
            table._touched[s][rows] = True
        data[:, table.capacity, :] = 0.0
        table.state = type(table.state).from_logical(
            data, table.capacity, ext=table.opt_ext)


def test_async_evict_orders_behind_writeback():
    """Async capacity eviction vs the in-flight end_pass write-back:
    the lane's _evict_ahead runs in the SAME epilogue job strictly
    after the write-back lands, so a freshly-written row is never
    evicted ahead of its write-back — after the fence, every evicted
    key's host value carries the pass's update, and the next begin_pass
    finds its eviction already done (no inline emergency)."""
    from paddlebox_tpu.config import flags_scope
    cap = 16
    with flags_scope(warmup_pass_scatter=False):
        table = TieredShardedEmbeddingTable(
            2, mf_dim=2, capacity_per_shard=cap, cfg=_cfg())
        k1 = np.arange(0, 2 * cap, dtype=np.uint64)   # fills both shards
        table.stage(k1, background=False)
        table.begin_pass(k1)
        _plant_window_values(table, 5.0)
        # the NEXT pass's stage is queued (disjoint keys → full
        # pressure) BEFORE end_pass, the pipeline shape
        k2 = np.arange(2 * cap, 4 * cap, dtype=np.uint64)
        table.stage(k2, background=False, queue=True)
        table.end_pass()      # lane: write-back k1 → evict ahead for k2
        table.fence()
        # every k1 value landed in the host tier BEFORE its eviction
        for s, ks in enumerate(table._split_by_owner(k1)):
            got = table.hosts[s].fetch(ks)["embed_w"]
            np.testing.assert_allclose(got, 5.0)
        # the lane actually freed the window for k2
        with table.host_lock:
            for s in range(2):
                assert len(table.indexes[s]) == 0
        table.begin_pass(k2)
        st = table.last_pass_stats
        assert st["evict_async_rows"] == 2 * cap
        assert st["evicted"] == 0, (
            f"begin_pass still evicted inline: {st}")
        assert st["staged"] == 2 * cap
        table.end_pass()
        table.fence()


def test_async_evict_skips_dirty_rows():
    """The clean-only rule: a row dirtied AFTER the end_pass snapshot
    (its write-back hasn't landed) is never evicted by the lane — it
    survives _evict_ahead and falls to the emergency inline path at
    begin_pass, which writes it back before release."""
    from paddlebox_tpu.config import flags_scope
    from paddlebox_tpu.ps.table import FIELD_COL
    cap = 16
    with flags_scope(warmup_pass_scatter=False):
        table = TieredShardedEmbeddingTable(
            2, mf_dim=2, capacity_per_shard=cap, cfg=_cfg())
        k1 = np.arange(0, 2 * cap, dtype=np.uint64)
        table.stage(k1, background=False)
        table.begin_pass(k1)
        _plant_window_values(table, 5.0)
        table.end_pass()
        table.fence()          # k1 clean, host has 5.0
        k2 = np.arange(2 * cap, 4 * cap, dtype=np.uint64)
        table.stage(k2, background=False, queue=True)
        # dirty ONE row after the snapshot: its newest value (9.0) is
        # only on device — the lane must not evict it
        s0 = 0
        keys0, rows0 = table.indexes[s0].items()
        probe_key, probe_row = keys0[0], rows0[0]
        data = np.asarray(jax.device_get(table.state.data)).copy()
        data[s0][probe_row, FIELD_COL["embed_w"]] = 9.0
        table.state = type(table.state).from_logical(
            data, table.capacity, ext=table.opt_ext)
        table._touched[s0][probe_row] = True
        freed = table._evict_ahead()   # what the lane would run
        assert freed == 2 * cap - 1, freed
        with table.host_lock:          # the dirty row survived the lane
            assert int(table.indexes[s0].lookup(
                np.array([probe_key]))[0]) == probe_row
        # host still has the OLD value — the lane wrote nothing
        assert table.hosts[s0].fetch(
            np.array([probe_key]))["embed_w"][0] == 5.0
        # begin_pass: the emergency inline path evicts it WITH its
        # write-back (the fence + dirty-evictee discipline)
        table.begin_pass(k2)
        st = table.last_pass_stats
        assert st["evicted"] == 1 and st["evicted_writeback"] == 1, st
        assert st["evict_emergency_sec"] > 0.0
        assert table.hosts[s0].fetch(
            np.array([probe_key]))["embed_w"][0] == 9.0, (
            "dirty evictee lost its update")
        table.end_pass()
        table.fence()


def test_async_evict_never_unpins_queued_promote(tmp_path):
    """Eviction vs prefetch_promote: a row plan-assigned (pending) for
    a QUEUED pass — its value just promoted SSD→host by the preloader —
    cannot be evicted out from under its pin, even when the overflow
    wants more rows than the unpinned candidates can supply; its
    promoted value survives to its own begin_pass."""
    from paddlebox_tpu.config import flags_scope
    with flags_scope(warmup_pass_scatter=False):
        cap = 12
        table = TieredShardedEmbeddingTable(
            2, mf_dim=2, capacity_per_shard=cap, cfg=_cfg(),
            ssd_dir=str(tmp_path / "tier"))
        # pass 1: 8 rows/shard, trained to 5.0, written back, clean
        k1 = np.arange(0, 16, dtype=np.uint64)
        table.stage(k1, background=False)
        table.begin_pass(k1)
        _plant_window_values(table, 5.0)
        table.end_pass()
        table.fence()
        # pass 2's keys: 4/shard whose values live ONLY on SSD + 8/shard
        # genuinely new
        pend = np.arange(100, 108, dtype=np.uint64)
        new = np.arange(200, 216, dtype=np.uint64)
        k2 = np.concatenate([pend, new])
        from paddlebox_tpu.ps.table import FIELDS
        for s, ks in enumerate(table._split_by_owner(pend)):
            f = {f_: (np.full((len(ks), 2), 7.0, np.float32)
                      if f_ == "embedx_w"
                      else np.full(len(ks), 7.0, np.float32))
                 for f_ in FIELDS}
            table.hosts[s].update(ks, f)
        table.fence()
        for h in table.hosts:
            h.demote_cold()
        assert table.has_spilled_rows()
        # the preloader build: plan-assign k2's pending subset + promote
        # their spilled values, then queue the stage (PassPipeline shape)
        with table.plan_scope():
            for s, ks in enumerate(table._split_by_owner(pend)):
                with table.host_lock:
                    pre = table.indexes[s].lookup(ks)
                    table.indexes[s].assign(ks)
                    table._note_plan_assigned(s, ks[pre < 0])
            assert table.prefetch_promote(pend) == len(pend)
            table.stage(k2, background=False, queue=True)
        # pressure: index 12/shard (8 k1 + 4 pending) + 8 new > cap 12;
        # overflow (8) equals the ONLY unpinned candidates (k1) — the
        # pinned pending rows must all survive
        freed = table._evict_ahead()
        assert freed == 16, freed       # all of k1, both shards
        with table.host_lock:
            for s, ks in enumerate(table._split_by_owner(pend)):
                assert (table.indexes[s].lookup(ks) >= 0).all(), (
                    "a pinned pending row was evicted from under its "
                    "promote")
        table.begin_pass(k2)
        st = table.last_pass_stats
        assert st["evicted"] == 0, st
        # the promoted values reached the window through the reconcile
        for s, ks in enumerate(table._split_by_owner(pend)):
            rows = table.indexes[s].lookup(ks)
            from paddlebox_tpu.ps.table import FIELD_COL
            w = np.asarray(jax.device_get(
                table.state.data))[s][rows, FIELD_COL["embed_w"]]
            np.testing.assert_allclose(w, 7.0)
        table.end_pass()
        table.fence()


def test_pipeline_plan_rollback_on_abort():
    """Preloader-staged tiered pass rollback under plan_scope abort: a
    build that dies AFTER plan-assigning its keys (the abort-between-
    stages poll) rolls its pending rows back — nothing stays pinned, no
    stage is queued, and the table runs a normal pass afterwards."""
    from paddlebox_tpu.config import flags_scope
    from paddlebox_tpu.train.device_pass import (PassPipeline,
                                                 PreloadBuildAborted)
    with flags_scope(warmup_pass_scatter=False):
        table = TieredShardedEmbeddingTable(
            2, mf_dim=2, capacity_per_shard=256, cfg=_cfg())
        k1 = np.arange(0, 32, dtype=np.uint64)
        k2 = np.arange(100, 132, dtype=np.uint64)
        built = []

        class _Tok:
            def upload(self, materialize=False):
                pass

            def nbytes(self):
                return 0

        def build(ks):
            for s, sub in enumerate(table._split_by_owner(ks)):
                with table.host_lock:
                    pre = table.indexes[s].lookup(sub)
                    table.indexes[s].assign(sub)
                    table._note_plan_assigned(s, sub[pre < 0])
            built.append(ks[0])
            if len(built) == 2:
                # the second build observes a stop between stages
                raise PreloadBuildAborted("stop between build stages")
            return _Tok()

        pipe = PassPipeline(iter([k1, k2]), build_fn=build,
                            window_table=table, keys_of=lambda k: k)
        pipe.start_next()
        rp = pipe.wait()
        assert rp is not None
        pipe.begin_pass()
        pipe.end_pass()
        assert pipe.wait() is None       # the aborted build never lands
        pipe.drain()
        table.fence()
        # k2's plan rows rolled back: no pins, no rows, no queued stage
        assert table.obs_stats()["pending"] == 0
        for s, sub in enumerate(table._split_by_owner(k2)):
            assert (table.indexes[s].lookup(sub) == -1).all()
        assert len(table._stage_q) == 0
        # and the table still runs a normal pass over those keys
        table.stage(k2, background=False)
        assert table.begin_pass(k2) == len(k2)
        table.end_pass()
        table.fence()


def test_pipeline_drain_discards_queued_stages():
    """PassPipeline.drain() with built-but-never-begun passes: queued
    stages are discarded and their plan-pending pins released
    (discard_queued_stages) — abandoned stages never pin window
    capacity; keys shared with the open window stay resident."""
    from paddlebox_tpu.config import flags_scope
    from paddlebox_tpu.train.device_pass import PassPipeline
    with flags_scope(warmup_pass_scatter=False):
        table = TieredShardedEmbeddingTable(
            2, mf_dim=2, capacity_per_shard=256, cfg=_cfg())
        k1 = np.arange(0, 32, dtype=np.uint64)
        k2 = np.arange(100, 132, dtype=np.uint64)    # disjoint from k1
        k3 = np.arange(116, 148, dtype=np.uint64)    # overlaps k2

        class _Tok:
            def upload(self, materialize=False):
                pass

            def nbytes(self):
                return 0

        def build(ks):
            for s, sub in enumerate(table._split_by_owner(ks)):
                with table.host_lock:
                    pre = table.indexes[s].lookup(sub)
                    table.indexes[s].assign(sub)
                    table._note_plan_assigned(s, sub[pre < 0])
            return _Tok()

        pipe = PassPipeline(iter([k1, k2, k3]), build_fn=build,
                            window_table=table, depth=3,
                            keys_of=lambda k: k)
        pipe.start_next()
        rp = pipe.wait()
        pipe.begin_pass()                 # consume k1 only
        # let the worker finish building+staging k2 and k3
        for _ in range(200):
            with table.host_lock:
                q = len(table._stage_q)
            if q == 2:
                break
            time.sleep(0.01)
        assert q == 2
        pipe.end_pass()
        pipe.drain()                      # k2/k3 will never begin
        table.fence()
        assert table.obs_stats()["pending"] == 0
        assert len(table._stage_q) == 0
        with table.host_lock:
            for s, sub in enumerate(table._split_by_owner(
                    np.setdiff1d(np.concatenate([k2, k3]), k1))):
                assert (table.indexes[s].lookup(sub) == -1).all(), (
                    "an abandoned stage left plan rows pinning the "
                    "window")
            # the open pass's rows are untouched by the discard
            for s, sub in enumerate(table._split_by_owner(k1)):
                assert (table.indexes[s].lookup(sub) >= 0).all()


def test_async_evict_pins_inflight_stage():
    """The in-flight stage pin (review finding): a queued stage's
    missing-split is computed BEFORE its lock-free host fetch, so the
    whole working set must be pinned from that moment — _evict_ahead
    firing mid-fetch must not evict a key the stage classified as
    resident (it would never be re-inserted at that pass's begin)."""
    from paddlebox_tpu.config import flags_scope
    cap = 16
    with flags_scope(warmup_pass_scatter=False):
        table = TieredShardedEmbeddingTable(
            2, mf_dim=2, capacity_per_shard=cap, cfg=_cfg())
        k1 = np.arange(0, 2 * cap, dtype=np.uint64)
        table.stage(k1, background=False)
        table.begin_pass(k1)
        _plant_window_values(table, 5.0)
        table.end_pass()
        table.fence()                      # k1 resident, clean
        # head queued stage: disjoint keys → full capacity pressure
        kb = np.arange(100, 100 + 2 * cap, dtype=np.uint64)
        table.stage(kb, background=False, queue=True)
        # next stage re-uses k1 (classified resident at split time);
        # the lane fires _evict_ahead DURING its host fetch
        fired = []
        orig = table._fetch_stage_values

        def hook(s, new_keys, table=table):
            if not fired:
                fired.append(table._evict_ahead())
            return orig(s, new_keys)

        table._fetch_stage_values = hook
        try:
            table.stage(k1, background=False, queue=True)
        finally:
            table._fetch_stage_values = orig
        assert fired, "the mid-fetch eviction never ran"
        # the in-flight stage's resident keys survived the lane
        assert fired[0] == 0, (
            f"_evict_ahead evicted {fired[0]} rows out from under the "
            "in-flight stage's missing-split")
        with table.host_lock:
            for s, ks in enumerate(table._split_by_owner(k1)):
                assert (table.indexes[s].lookup(ks) >= 0).all(), (
                    "an in-flight stage's resident key was evicted "
                    "mid-fetch")
            assert table._staging_keys is None   # pin released
        table.discard_queued_stages()
        table.fence()


def test_begin_failure_restores_queued_stage():
    """A begin_pass that fails AFTER consuming a queued stage (e.g.
    window overflow with every candidate pinned) restores the stage to
    the queue head and drops the open-pass pin — the pipeline's queues
    stay aligned and drain/discard still release every pin."""
    from paddlebox_tpu.config import flags_scope
    cap = 8
    with flags_scope(warmup_pass_scatter=False):
        table = TieredShardedEmbeddingTable(
            2, mf_dim=2, capacity_per_shard=cap, cfg=_cfg())
        k1 = np.arange(0, 2 * cap, dtype=np.uint64)
        table.stage(k1, background=False)
        table.begin_pass(k1)
        _plant_window_values(table, 5.0)
        table.end_pass()
        table.fence()                       # window full of clean k1
        kb = np.arange(100, 100 + 2 * cap, dtype=np.uint64)
        table.stage(kb, background=False, queue=True)
        # the NEXT queued stage re-stages k1 — pinning it, so kb's
        # begin has zero evictable candidates and must overflow
        table.stage(k1, background=False, queue=True)
        with pytest.raises(Exception):
            table.begin_pass(kb)
        assert not table.in_pass
        with table.host_lock:
            # the failed pass's stage is back at the queue head …
            assert len(table._stage_q) == 2
            assert np.array_equal(
                np.concatenate(table._stage_q[0].keys),
                np.concatenate(table._split_by_owner(kb)))
            # … and nothing stays pinned as "open"
            assert all(len(a) == 0 for a in table._open_keys)
        assert table.discard_queued_stages() == 2
        table.fence()
        # the table still runs a normal (evicting) pass afterwards
        table.stage(kb, background=False)
        assert table.begin_pass(kb) == len(kb)
        table.end_pass()
        table.fence()


def test_pin_working_set_covers_plan_build():
    """The pre-build pin (review finding): a plan build bakes row ids
    for RESIDENT keys too, so the pass's working set must be pinned
    from the first row lookup — _evict_ahead firing between plan build
    and stage() must not evict a resident key the plan already
    addresses."""
    from paddlebox_tpu.config import flags_scope
    cap = 16
    with flags_scope(warmup_pass_scatter=False):
        table = TieredShardedEmbeddingTable(
            2, mf_dim=2, capacity_per_shard=cap, cfg=_cfg())
        k1 = np.arange(0, 2 * cap, dtype=np.uint64)
        table.stage(k1, background=False)
        table.begin_pass(k1)
        _plant_window_values(table, 5.0)
        table.end_pass()
        table.fence()                      # k1 resident, clean
        kb = np.arange(100, 100 + 2 * cap, dtype=np.uint64)
        table.stage(kb, background=False, queue=True)   # pressure head
        # the PassPipeline order: pin → plan build (bakes k1's rows) →
        # lane eviction fires → stage. The pin must hold throughout.
        table.pin_working_set(k1)
        rows_baked = [table.indexes[s].lookup(ks) for s, ks in
                      enumerate(table._split_by_owner(k1))]
        freed = table._evict_ahead()       # the lane firing mid-build
        assert freed == 0, (
            f"_evict_ahead evicted {freed} rows the in-build plan "
            "already baked")
        table.stage(k1, background=False, queue=True)   # same-keys pin ok
        with table.host_lock:
            assert table._staging_keys is None          # handed over
            for s, ks in enumerate(table._split_by_owner(k1)):
                np.testing.assert_array_equal(
                    table.indexes[s].lookup(ks), rows_baked[s])
        table.discard_queued_stages()
        table.fence()


def test_discard_rejects_straddling_fetch():
    """discard_queued_stages racing an in-flight queued fetch: the
    fetch that straddled the discard must NOT append a zombie stage
    afterwards (its plan pins would leak forever) — it raises, and the
    queue stays empty."""
    from paddlebox_tpu.config import flags_scope
    with flags_scope(warmup_pass_scatter=False):
        table = TieredShardedEmbeddingTable(
            2, mf_dim=2, capacity_per_shard=64, cfg=_cfg())
        k1 = np.arange(0, 32, dtype=np.uint64)
        orig = table._fetch_stage_values
        fired = []

        def hook(s, new_keys):
            if not fired:     # the discard lands mid-fetch
                fired.append(table.discard_queued_stages())
            return orig(s, new_keys)

        table._fetch_stage_values = hook
        try:
            with pytest.raises(RuntimeError, match="discarded"):
                table.stage(k1, background=False, queue=True)
        finally:
            table._fetch_stage_values = orig
        with table.host_lock:
            assert len(table._stage_q) == 0
            assert table._staging_keys is None
        # the table still stages and begins normally afterwards
        table.stage(k1, background=False, queue=True)
        assert table.begin_pass(k1) == len(k1)
        table.end_pass()
        table.fence()
