"""Per-param dense learning rates (lr_map).

Reference: ``InitializeGPUAndLoadModel`` carries a param-name→lr map
(box_wrapper.cc:1303-1335) consumed per parameter by the async dense
table (boxps_worker.cc:199-204). Ours: per-leaf update multipliers
(dense_modes.build_lr_scales / lr_map_transform), native in
AsyncDenseTable, Trainer, and ShardedTrainer (psum + zero1 chunks).
"""

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from paddlebox_tpu.train.dense_modes import (AsyncDenseTable,
                                             build_lr_scales,
                                             lr_map_transform)


def _leaf_path(params, idx=0):
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    return paths[idx]


@pytest.fixture(scope="module")
def ctr_dataset(tmp_path_factory):
    from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
    from paddlebox_tpu.data.criteo import generate_criteo_files
    tmp = str(tmp_path_factory.mktemp("lrmap"))
    files = generate_criteo_files(tmp, num_files=1, rows_per_file=512,
                                  vocab_per_slot=40, seed=41)
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.load_into_memory()
    return ds, desc


def test_lr_pattern_segment_boundaries():
    """'Dense_1' must not match 'Dense_10' (bare substring over-match):
    the rule requires non-identifier boundaries, shared by
    build_lr_scales and AsyncDenseTable."""
    from paddlebox_tpu.train.dense_modes import lr_pattern_matches
    assert lr_pattern_matches("Dense_1", "['params']['Dense_1']['kernel']")
    assert not lr_pattern_matches("Dense_1",
                                  "['params']['Dense_10']['kernel']")
    assert lr_pattern_matches("['Dense_1']['kernel']",
                              "['params']['Dense_1']['kernel']")
    params = {"Dense_1": jnp.ones(2), "Dense_10": jnp.ones(2)}
    scales = build_lr_scales(params, {"Dense_1": 0.0}, 1.0)
    assert scales["Dense_1"] == 0.0 and scales["Dense_10"] == 1.0
    # AsyncDenseTable goes through the same matcher
    t = AsyncDenseTable({"Dense_1": np.ones(2, np.float32),
                         "Dense_10": np.ones(2, np.float32)},
                        lr=1e-3, lr_map={"Dense_1": 0.0})
    t.start()
    t.push({"Dense_1": np.ones(2, np.float32),
            "Dense_10": np.ones(2, np.float32)})
    t.drain()
    t.stop()
    out = t.pull()
    np.testing.assert_array_equal(out["Dense_1"], 1.0)   # frozen
    assert (out["Dense_10"] != 1.0).all()                # trains


def test_lr_map_transform_scales_updates_exactly():
    params = {"w_0": jnp.ones(4), "b_0": jnp.ones(2), "other": jnp.ones(3)}
    base = 0.1
    scales = build_lr_scales(params, {"w_0": 0.0, "b_0": 1.0}, base)
    assert scales["w_0"] == 0.0 and scales["b_0"] == 10.0
    assert scales["other"] == 1.0
    tx = optax.chain(optax.sgd(base), lr_map_transform(scales))
    st = tx.init(params)
    g = jax.tree.map(jnp.ones_like, params)
    upd, _ = tx.update(g, st, params)
    np.testing.assert_allclose(np.asarray(upd["w_0"]), 0.0)
    np.testing.assert_allclose(np.asarray(upd["b_0"]), -1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(upd["other"]), -0.1, rtol=1e-6)


def test_async_dense_table_lr_map():
    """Frozen param holds exactly; boosted param moves ~10x the default
    (Adam step magnitude ≈ lr on the first update)."""
    params = {"w_0": np.ones(4, np.float32), "b_0": np.ones(2, np.float32),
              "fc": np.ones(3, np.float32)}
    t = AsyncDenseTable(params, lr=1e-3,
                        lr_map={"w_0": 0.0, "b_0": 1e-2})
    t.start()
    g = {"w_0": np.full(4, 0.5, np.float32),
         "b_0": np.full(2, 0.5, np.float32),
         "fc": np.full(3, 0.5, np.float32)}
    t.push(g)
    t.drain()
    t.stop()
    out = t.pull()
    np.testing.assert_array_equal(out["w_0"], 1.0)          # frozen
    d_b = 1.0 - out["b_0"][0]
    d_fc = 1.0 - out["fc"][0]
    assert d_fc > 0
    np.testing.assert_allclose(d_b / d_fc, 10.0, rtol=1e-4)  # boosted 10x


def test_trainer_lr_map_freezes_param(ctr_dataset):
    """Single-chip Trainer: a frozen-lr param stays at init through a
    full pass while the rest train."""
    ds, desc = ctr_dataset
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
    from paddlebox_tpu.train import Trainer
    cfg = SparseSGDConfig(mf_create_thresholds=0.0)

    probe = Trainer(CtrDnn(hidden=(8,)),
                    EmbeddingTable(mf_dim=4, capacity=1 << 12, cfg=cfg),
                    desc, tx=optax.adam(1e-2))
    frozen = _leaf_path(probe.state.params)
    tr = Trainer(CtrDnn(hidden=(8,)),
                 EmbeddingTable(mf_dim=4, capacity=1 << 12, cfg=cfg),
                 desc, tx=optax.adam(1e-2),
                 lr_map={frozen: 0.0}, lr_map_base=1e-2)
    init = jax.tree_util.tree_leaves_with_path(
        jax.tree.map(np.asarray, tr.state.params))
    tr.train_pass(ds)
    moved = 0
    for (path, before) in init:
        after = np.asarray(dict(jax.tree_util.tree_leaves_with_path(
            tr.state.params))[path])
        if jax.tree_util.keystr(path) == frozen:
            np.testing.assert_array_equal(after, before)
        elif not np.array_equal(after, before):
            moved += 1
    assert moved > 0


@pytest.mark.slow  # heavy on the virtual-CPU mesh —
# out of the tier-1 wall budget, runs in the slow tier
@pytest.mark.parametrize("zero1", [False, True])
def test_sharded_trainer_lr_map(ctr_dataset, zero1):
    """Mesh trainer (psum and zero1 flat chunks): frozen param holds at
    init; a boosted param moves farther than under the global lr."""
    ds, desc = ctr_dataset
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.ps import SparseSGDConfig
    from paddlebox_tpu.ps.sharded import ShardedEmbeddingTable
    from paddlebox_tpu.train.sharded import ShardedTrainer
    assert len(jax.devices()) >= 8
    cfg = SparseSGDConfig(mf_create_thresholds=0.0)

    def mk(lr_map=None):
        t = ShardedEmbeddingTable(8, mf_dim=4, capacity_per_shard=2048,
                                  cfg=cfg, req_bucket_min=128,
                                  serve_bucket_min=128)
        return ShardedTrainer(CtrDnn(hidden=(8,)), t, desc, make_mesh(8),
                              tx=optax.adam(1e-2), seed=3, zero1=zero1,
                              lr_map=lr_map, lr_map_base=1e-2)

    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(mk().state.params)]
    frozen, boosted = paths[0], paths[-1]
    assert frozen != boosted
    tr = mk({frozen: 0.0, boosted: 5e-2})
    tr_plain = mk()
    init = {jax.tree_util.keystr(p): np.asarray(l) for p, l in
            jax.tree_util.tree_leaves_with_path(tr.state.params)}
    tr.train_pass(ds)
    tr_plain.train_pass(ds)
    after = {jax.tree_util.keystr(p): np.asarray(l) for p, l in
             jax.tree_util.tree_leaves_with_path(tr.state.params)}
    after_plain = {jax.tree_util.keystr(p): np.asarray(l) for p, l in
                   jax.tree_util.tree_leaves_with_path(
                       tr_plain.state.params)}
    np.testing.assert_array_equal(after[frozen], init[frozen])
    assert not np.array_equal(after_plain[frozen], init[frozen])
    d_boost = np.abs(after[boosted] - init[boosted]).mean()
    d_plain = np.abs(after_plain[boosted] - init[boosted]).mean()
    assert d_boost > 2.0 * d_plain, (d_boost, d_plain)
