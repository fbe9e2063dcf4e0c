"""AucRunner slot-replacement eval (box_wrapper.h:908-1009 semantics)."""

import numpy as np
import optax
import pytest

from paddlebox_tpu.auc_runner import AucRunner, RecordCandidateList
from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
from paddlebox_tpu.data.criteo import generate_criteo_files
from paddlebox_tpu.data.record import SlotRecord
from paddlebox_tpu.models import CtrDnn
from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
from paddlebox_tpu.train import Trainer


def make_records(n, num_slots=4, seed=0):
    rng = np.random.default_rng(seed)
    recs = []
    for i in range(n):
        keys = rng.integers(0, 50, size=num_slots).astype(np.uint64)
        keys += np.arange(num_slots, dtype=np.uint64) * 100
        recs.append(SlotRecord(
            keys=keys, slot_offsets=np.arange(num_slots + 1, dtype=np.int32),
            dense=np.zeros(2, np.float32), label=float(i % 2)))
    return recs


def test_candidate_reservoir():
    rng = np.random.default_rng(0)
    cl = RecordCandidateList(capacity=10, slots=[0, 2])
    cl.add_all(make_records(100), rng)
    assert cl.size == 10
    v = cl.sample(0, rng)
    assert v.dtype == np.uint64 and 0 <= int(v[0]) < 100


def test_record_replace_and_back():
    recs = make_records(20, seed=1)
    runner = AucRunner(slots_to_replace=[1], pool_size=50, seed=2)
    runner.init_pass(recs)
    replaced = runner.record_replace(recs)
    assert runner.phase == 0
    # untouched slots identical; replaced slot drawn from other records
    diff = 0
    for a, b in zip(recs, replaced):
        np.testing.assert_array_equal(a.slot_keys(0), b.slot_keys(0))
        np.testing.assert_array_equal(a.slot_keys(2), b.slot_keys(2))
        np.testing.assert_array_equal(a.slot_keys(3), b.slot_keys(3))
        assert 100 <= int(b.slot_keys(1)[0]) < 200  # still slot-1 vocab
        diff += int(a.slot_keys(1)[0] != b.slot_keys(1)[0])
    assert diff > 5  # replacement actually shuffled most records
    back = runner.record_replace_back()
    assert back is not replaced and back[0] is recs[0]
    assert runner.phase == 1
    with pytest.raises(RuntimeError):
        runner.record_replace_back()


def _informative_setup(batch_size):
    """Slot 0 determines the label; slot 3 is pure noise — shared by the
    single-chip and mesh slot-importance tests."""
    from paddlebox_tpu.data import SlotDef
    rng = np.random.default_rng(5)
    n, num_slots = 4000, 4
    recs = []
    for i in range(n):
        k0 = int(rng.integers(0, 20))
        keys = np.array(
            [k0,
             100 + int(rng.integers(0, 20)),
             200 + int(rng.integers(0, 20)),
             300 + int(rng.integers(0, 20))], np.uint64)
        recs.append(SlotRecord(
            keys=keys, slot_offsets=np.arange(num_slots + 1, dtype=np.int32),
            dense=np.zeros(1, np.float32), label=float(k0 < 10),
            clk=float(k0 < 10)))
    desc = DataFeedDesc(
        slots=[SlotDef(name=f"s{i}") for i in range(num_slots)]
        + [SlotDef(name="d0", type="float", dim=1)],
        batch_size=batch_size)
    desc.key_bucket_min = 2048
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=1e-3,
                          learning_rate=0.1, mf_learning_rate=0.1)
    return recs, desc, cfg


def _assert_slot_importance(tr, recs, desc):
    """Train 3 passes, then slot-replacement importance: destroying the
    label-defining slot collapses AUC; the noise slot does not."""
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.records = recs
    for _ in range(3):
        tr.train_pass(ds)

    def eval_fn(records):
        ds2 = DatasetFactory().create_dataset("InMemoryDataset", desc)
        ds2.records = records
        return tr.eval_pass(ds2)["auc"]

    runner = AucRunner(slots_to_replace=[0, 3], pool_size=2000, seed=3)
    runner.init_pass(recs)
    imp = runner.slot_importance(eval_fn, recs)
    assert imp[0] > 0.2, imp        # label-defining slot: big AUC drop
    assert abs(imp[3]) < 0.05, imp  # noise slot: no real drop


def test_slot_importance_detects_informative_slot():
    """Slot 0 determines the label; slot 3 is pure noise. Destroying
    slot 0 must collapse AUC; destroying slot 3 must not."""
    recs, desc, cfg = _informative_setup(batch_size=256)
    table = EmbeddingTable(mf_dim=8, capacity=1 << 12, cfg=cfg,
                           unique_bucket_min=2048)
    tr = Trainer(CtrDnn(hidden=(32, 32)), table, desc, tx=optax.adam(5e-3))
    _assert_slot_importance(tr, recs, desc)


@pytest.mark.slow  # the 8-dev virtual-CPU mesh run is
# heavy (~20 s) — out of the tier-1 wall budget, runs in the slow tier
def test_slot_importance_on_mesh_trainer():
    """AucRunner composes with the MESH trainer unchanged (it is
    dataset-level — the reference embeds the same machinery in
    BoxWrapper, box_wrapper.h:908-1009, available to every worker
    mode): slot importance via ShardedTrainer.eval_pass on the
    8-device mesh finds the same informative slot."""
    import jax
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.ps.sharded import ShardedEmbeddingTable
    from paddlebox_tpu.train.sharded import ShardedTrainer
    assert len(jax.devices()) >= 8
    recs, desc, cfg = _informative_setup(batch_size=64)
    table = ShardedEmbeddingTable(8, mf_dim=8, capacity_per_shard=1 << 10,
                                  cfg=cfg, req_bucket_min=128,
                                  serve_bucket_min=128)
    tr = ShardedTrainer(CtrDnn(hidden=(32, 32)), table, desc, make_mesh(8),
                        tx=optax.adam(5e-3))
    _assert_slot_importance(tr, recs, desc)
