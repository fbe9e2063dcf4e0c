"""Metric variant semantics vs sklearn-style numpy references
(fleet/metrics.h:198-567 behaviors)."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu.metrics import MetricRegistry
from paddlebox_tpu.metrics_ext import (
    CmatchRankAucMetric, CmatchRankMaskAucMetric, ContinueValueMetric,
    MaskAucMetric, MultiTaskAucMetric, NanInfMetric, WuAucMetric,
    _tie_averaged_user_auc, parse_cmatch_rank_group,
)


def ref_auc(label, pred):
    """Exact Mann-Whitney AUC (tie-averaged)."""
    order = np.argsort(pred, kind="stable")
    p, l = pred[order], label[order]
    ranks = np.empty(len(p))
    i = 0
    while i < len(p):
        j = i
        while j < len(p) and p[j] == p[i]:
            j += 1
        ranks[i:j] = (i + j + 1) / 2.0
        i = j
    n_pos, n_neg = l.sum(), (1 - l).sum()
    return (ranks[l > 0].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)


def test_default_auc_bitmatches_f64_reference_calculator():
    """FLAGS.auc_device_reduce defaults to False: the default AUC path is
    the exact f64 host finalize — BasicAucCalculator::compute semantics
    (metrics.cc:288-304). Assert bit-equality against an independent numpy
    transcription of the bucket scan."""
    from paddlebox_tpu.config import FLAGS
    from paddlebox_tpu.metrics import (auc_add_batch, auc_compute,
                                       init_auc_state)
    assert FLAGS.auc_device_reduce is False  # parity by default
    rng = np.random.default_rng(7)
    nb = 4096
    st = init_auc_state(nb)
    for _ in range(3):
        pred = rng.random(512).astype(np.float32)
        label = (rng.random(512) < pred).astype(np.float32)
        st = auc_add_batch(st, jnp.asarray(pred), jnp.asarray(label),
                           jnp.ones(512, jnp.float32))
    got = auc_compute(st).auc
    # independent f64 bucket scan (metrics.cc BasicAucCalculator::compute)
    pos = np.asarray(st.pos, np.float64)
    neg = np.asarray(st.neg, np.float64)
    area = 0.0
    cum_neg = 0.0
    for i in range(nb):
        area += pos[i] * (cum_neg + 0.5 * neg[i])
        cum_neg += neg[i]
    want = area / (pos.sum() * neg.sum())
    assert got == want  # bit-exact, not approx


def test_parse_cmatch_rank_group():
    assert parse_cmatch_rank_group("401:0,402:1") == [(401, 0), (402, 1)]
    assert parse_cmatch_rank_group("7, 8") == [(7, 0), (8, 0)]


def test_cmatch_rank_filter():
    rng = np.random.default_rng(0)
    n = 2000
    pred = rng.random(n).astype(np.float32)
    label = (rng.random(n) < pred).astype(np.float32)
    cmatch = rng.choice([401, 402, 403], size=n).astype(np.int32)
    rank = rng.integers(0, 3, size=n).astype(np.int32)

    m = CmatchRankAucMetric("m", "401:0,402:1", nbins=100_000)
    m.add(jnp.asarray(pred), jnp.asarray(label),
          cmatch=jnp.asarray(cmatch), rank=jnp.asarray(rank))
    sel = ((cmatch == 401) & (rank == 0)) | ((cmatch == 402) & (rank == 1))
    got = m.compute()
    assert got["ins_num"] == sel.sum()
    assert abs(got["auc"] - ref_auc(label[sel], pred[sel])) < 2e-3

    m2 = CmatchRankAucMetric("m2", "401", ignore_rank=True, nbins=100_000)
    m2.add(jnp.asarray(pred), jnp.asarray(label),
           cmatch=jnp.asarray(cmatch), rank=jnp.asarray(rank))
    assert m2.compute()["ins_num"] == (cmatch == 401).sum()


def test_mask_and_combined_filter():
    rng = np.random.default_rng(1)
    n = 1000
    pred = rng.random(n).astype(np.float32)
    label = (rng.random(n) < pred).astype(np.float32)
    mask = rng.integers(0, 2, size=n).astype(np.int32)
    cmatch = rng.choice([7, 9], size=n).astype(np.int32)

    m = MaskAucMetric("m", nbins=100_000)
    m.add(jnp.asarray(pred), jnp.asarray(label), mask=jnp.asarray(mask))
    assert m.compute()["ins_num"] == mask.sum()

    mc = CmatchRankMaskAucMetric("mc", "7", ignore_rank=True, nbins=100_000)
    mc.add(jnp.asarray(pred), jnp.asarray(label),
           cmatch=jnp.asarray(cmatch), mask=jnp.asarray(mask))
    sel = (cmatch == 7) & (mask == 1)
    got = mc.compute()
    assert got["ins_num"] == sel.sum()
    assert abs(got["auc"] - ref_auc(label[sel], pred[sel])) < 4e-3


def test_multi_task_selects_head_by_cmatch():
    rng = np.random.default_rng(2)
    n, t = 1500, 3
    preds = rng.random((n, t)).astype(np.float32)
    cmatch = rng.choice([11, 12, 13, 99], size=n).astype(np.int32)
    task = np.select([cmatch == 11, cmatch == 12, cmatch == 13],
                     [0, 1, 2], default=-1)
    sel = task >= 0
    chosen = preds[np.arange(n), np.maximum(task, 0)]
    label = (rng.random(n) < chosen).astype(np.float32)

    m = MultiTaskAucMetric("mt", "11:0,12:1,13:2", nbins=100_000)
    m.add(jnp.asarray(preds), jnp.asarray(label), cmatch=jnp.asarray(cmatch))
    got = m.compute()
    assert got["ins_num"] == sel.sum()
    assert abs(got["auc"] - ref_auc(label[sel], chosen[sel])) < 2e-3


def test_continue_value():
    m = ContinueValueMetric("cv")
    pred = jnp.asarray([1.0, 2.0, 3.0])
    label = jnp.asarray([1.5, 2.0, 1.0])
    m.add(pred, label)
    got = m.compute()
    np.testing.assert_allclose(got["mae"], (0.5 + 0 + 2.0) / 3)
    np.testing.assert_allclose(got["rmse"], np.sqrt((0.25 + 4.0) / 3))


def test_nan_inf_counter():
    m = NanInfMetric("ni")
    m.add(jnp.asarray([0.1, np.nan, np.inf, -np.inf, 0.5]))
    got = m.compute()
    assert got["nan"] == 1 and got["inf"] == 2 and got["ins_num"] == 5


def test_wuauc_matches_per_user_reference():
    rng = np.random.default_rng(3)
    n = 3000
    uid = rng.integers(0, 40, size=n).astype(np.int64)
    pred = np.round(rng.random(n).astype(np.float64), 2)  # force ties
    label = (rng.random(n) < pred).astype(np.float64)

    wuauc, uauc, users = _tie_averaged_user_auc(uid, pred, label)
    # python reference: loop users
    aucs, weights = [], []
    for u in np.unique(uid):
        m = uid == u
        l, p = label[m], pred[m]
        if l.sum() in (0, len(l)):
            continue
        aucs.append(ref_auc(l, p))
        weights.append(m.sum())
    want_w = float(np.sum(np.array(aucs) * np.array(weights)) / np.sum(weights))
    assert users == len(aucs)
    np.testing.assert_allclose(wuauc, want_w, rtol=1e-10)
    np.testing.assert_allclose(uauc, np.mean(aucs), rtol=1e-10)


def test_wuauc_metric_batches():
    m = WuAucMetric("wu")
    m.add(np.array([0.9, 0.1]), np.array([1.0, 0.0]), uid=np.array([1, 1]))
    m.add(np.array([0.2, 0.8]), np.array([1.0, 0.0]), uid=np.array([2, 2]))
    got = m.compute()
    assert got["user_count"] == 2
    np.testing.assert_allclose(got["wuauc"], 0.5)  # user1 perfect, user2 inverted


def test_registry_dispatch_and_phase():
    reg = MetricRegistry()
    reg.init_metric("join_auc", method="auc", phase=1, nbins=1000)
    reg.init_metric("upd_auc", method="auc", phase=0, nbins=1000)
    reg.init_metric("wu", method="wuauc")
    assert set(reg.active()) == {"join_auc", "wu"}
    reg.flip_phase()
    assert set(reg.active()) == {"upd_auc", "wu"}
    with pytest.raises(ValueError):
        reg.init_metric("x", method="nope")
    msg = reg.get_metric_msg("wu")
    assert msg["ins_num"] == 0.0


def test_registry_auto_feed_through_trainer():
    """Registered metric variants accumulate automatically during
    train_pass (AddAucMonitor semantics) with batch side channels."""
    import optax
    from paddlebox_tpu.data import DataFeedDesc, InMemoryDataset, SlotDef
    from paddlebox_tpu.data.record import SlotRecord
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
    from paddlebox_tpu.train import Trainer

    rng = np.random.default_rng(0)
    S = 3
    recs = []
    for i in range(512):
        keys = (rng.integers(0, 40, S) + np.arange(S) * 40).astype(np.uint64)
        lbl = float(rng.random() < 0.3)
        recs.append(SlotRecord(
            keys=keys, slot_offsets=np.arange(S + 1, dtype=np.int32),
            dense=rng.normal(size=2).astype(np.float32), label=lbl,
            show=1.0, clk=lbl, uid=int(i % 17),
            rank=int(rng.integers(1, 4)),
            cmatch=int(rng.choice([222, 223, 0]))))
    slots = [SlotDef("label", "float", 1), SlotDef("dense", "float", 2)]
    slots += [SlotDef(f"C{i}", "uint64") for i in range(S)]
    desc = DataFeedDesc(slots=slots, batch_size=64, label_slot="label")
    ds = InMemoryDataset(desc)
    ds.records = recs
    ds.columnarize()

    t = EmbeddingTable(mf_dim=2, capacity=1 << 12,
                       cfg=SparseSGDConfig(mf_create_thresholds=0.0))
    tr = Trainer(CtrDnn(hidden=(8,)), t, desc, tx=optax.adam(1e-2))
    tr.metrics.init_metric("all", method="auc")
    tr.metrics.init_metric("cm222", method="cmatch_rank_auc",
                           cmatch_rank_group="222:1,222:2,222:3")
    tr.metrics.init_metric("wu", method="wuauc")
    tr.train_pass(ds)

    msg_all = tr.metrics.get_metric_msg("all")
    msg_cm = tr.metrics.get_metric_msg("cm222")
    msg_wu = tr.metrics.get_metric_msg("wu")
    assert msg_all["ins_num"] == 512
    # cmatch 222 subset only
    n222 = sum(1 for r in recs if r.cmatch == 222)
    assert msg_cm["ins_num"] == n222 > 0
    assert np.isfinite(msg_wu["wuauc"])
    assert msg_wu["user_count"] == 17


def test_registry_skips_metric_missing_side_channel():
    """A registered metric whose REQUIRED side channel is absent from the
    feed is skipped with a warning, not a crash."""
    from paddlebox_tpu.metrics import MetricRegistry
    reg = MetricRegistry()
    reg.init_metric("m", method="mask_auc")      # needs mask — never fed
    reg.init_metric("a", method="auc")
    pred = jnp.asarray(np.array([0.2, 0.8], np.float32))
    label = np.array([0.0, 1.0], np.float32)
    reg.add_batch(pred, label, np.ones(2, np.float32))  # must not raise
    assert reg.get_metric_msg("a")["ins_num"] == 2
    assert reg.get_metric_msg("m")["ins_num"] == 0


@pytest.mark.slow  # heavy on the virtual-CPU mesh —
# out of the tier-1 wall budget, runs in the slow tier
def test_registry_on_sharded_trainer():
    """Metric variants accumulate on the MESH trainer: the per-device-row
    AddAucMonitor feed matches the single-chip trainer's registry on the
    same data (pod-scale init_metric/get_metric_msg)."""
    import jax
    import optax
    from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
    from paddlebox_tpu.data.criteo import generate_criteo_files
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
    from paddlebox_tpu.ps.sharded import ShardedEmbeddingTable
    from paddlebox_tpu.train import Trainer
    from paddlebox_tpu.train.sharded import ShardedTrainer
    import tempfile
    assert len(jax.devices()) >= 8
    tmp = tempfile.mkdtemp()
    files = generate_criteo_files(tmp, num_files=1, rows_per_file=1024,
                                  vocab_per_slot=40, seed=31)
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.load_into_memory()
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0,
                          learning_rate=0.05, mf_learning_rate=0.05)
    sh = ShardedEmbeddingTable(8, mf_dim=4, capacity_per_shard=2048,
                               cfg=cfg, req_bucket_min=128,
                               serve_bucket_min=128)
    tr_m = ShardedTrainer(DeepFM(hidden=(16, 8)), sh, desc, make_mesh(8),
                          tx=optax.adam(1e-2), seed=3)
    sc = EmbeddingTable(mf_dim=4, capacity=1 << 13, cfg=cfg,
                        unique_bucket_min=1024)
    tr_s = Trainer(DeepFM(hidden=(16, 8)), sc, desc, tx=optax.adam(1e-2),
                   seed=3)
    for tr in (tr_m, tr_s):
        tr.metrics.init_metric("auc2", method="auc")
        tr.metrics.init_metric("wu", method="wuauc")
    tr_m.train_pass(ds)
    tr_s.train_pass(ds)
    mm = tr_m.metrics.get_metric_msg("auc2")
    ms = tr_s.metrics.get_metric_msg("auc2")
    # same data, same seeds — but mesh updates come per GLOBAL batch, so
    # predictions differ slightly; the registry wiring must agree closely
    assert abs(mm["auc"] - ms["auc"]) < 0.05, (mm, ms)
    assert mm["ins_num"] == ms["ins_num"] == 1024
    wm = tr_m.metrics.get_metric_msg("wu")
    ws = tr_s.metrics.get_metric_msg("wu")
    assert abs(wm["wuauc"] - ws["wuauc"]) < 0.08, (wm, ws)


@pytest.mark.slow  # same budget rationale as the sharded-trainer
# registry test above
def test_registry_on_mesh_resident_pass():
    """Metric variants accumulate in the MESH RESIDENT pass: predictions
    are collected inside the fori_loop (device-sharded [nb, N, B]) and
    replayed through the registry post-pass — the outputs must match the
    mesh STREAMING pass on identical data/seeds (boxps_worker.cc:1267,
    1337 accumulates monitors in every worker mode unconditionally)."""
    import jax
    import optax
    from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
    from paddlebox_tpu.data.criteo import generate_criteo_files
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.ps import SparseSGDConfig
    from paddlebox_tpu.ps.sharded import ShardedEmbeddingTable
    from paddlebox_tpu.train.sharded import ShardedTrainer
    import tempfile
    assert len(jax.devices()) >= 8
    tmp = tempfile.mkdtemp()
    files = generate_criteo_files(tmp, num_files=1, rows_per_file=1024,
                                  vocab_per_slot=40, seed=37)
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.load_into_memory()
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0,
                          learning_rate=0.05, mf_learning_rate=0.05)

    def mk():
        sh = ShardedEmbeddingTable(8, mf_dim=4, capacity_per_shard=2048,
                                   cfg=cfg, req_bucket_min=128,
                                   serve_bucket_min=128)
        tr = ShardedTrainer(DeepFM(hidden=(16, 8)), sh, desc,
                            make_mesh(8), tx=optax.adam(1e-2), seed=3)
        tr.metrics.init_metric("auc2", method="auc")
        tr.metrics.init_metric("wu", method="wuauc")
        return tr

    tr_s = mk()   # streaming
    tr_r = mk()   # resident
    rs = tr_s.train_pass(ds)
    rr = tr_r.train_pass_resident(ds)
    assert rr["ins_num"] == rs["ins_num"]
    ms, mr = (t.metrics.get_metric_msg("auc2") for t in (tr_s, tr_r))
    assert mr["ins_num"] == ms["ins_num"] == 1024
    assert abs(mr["auc"] - ms["auc"]) < 1e-5, (mr, ms)
    ws, wr = (t.metrics.get_metric_msg("wu") for t in (tr_s, tr_r))
    assert abs(wr["wuauc"] - ws["wuauc"]) < 1e-5, (wr, ws)
    assert wr["user_count"] == ws["user_count"]
