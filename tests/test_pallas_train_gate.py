"""Tier-1 bit-closeness gates for the Pallas kernel dispatch (ISSUE 12).

A seeded resident train job with ``use_pallas_seqpool=True`` (interpret
mode on this CPU mesh) must reproduce the default XLA composition's
logical state:

- UNIFORM (trivial one-key-per-slot layout): the pool is a reshape on
  both paths, so the ``state_digest`` must match EXACTLY — and this also
  pins the inverse guarantee that default flags keep today's program.
- ZIPF/ragged (real segment streams): the MXU one-hot pooling sums in a
  different order than XLA's scatter-add, so the gate is numeric — table
  rows (pushed grads applied in-table) and dense params within the
  documented f32 tolerance (docs/PERFORMANCE.md §Device kernels:
  rtol 2e-4 against per-step ~1e-6 drift compounding over two passes).
- ``use_pallas_gather=True`` (the table.py line-gather): gather_rows
  returns the identical lines bitwise, so the digest must match EXACTLY.
"""

import jax
import numpy as np
import optax
import pytest

from paddlebox_tpu.config import flags_scope
from paddlebox_tpu.data import DataFeedDesc, DatasetFactory, SlotDef
from paddlebox_tpu.data.criteo import generate_criteo_files
from paddlebox_tpu.models import DeepFM
from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
from paddlebox_tpu.train import Trainer
from paddlebox_tpu.train.checkpoint import state_digest

@pytest.fixture(scope="module")
def criteo_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("criteo_pallas_gate")
    return generate_criteo_files(str(d), num_files=1, rows_per_file=600,
                                 vocab_per_slot=40, seed=21)


def _trainer_uniform(files, bs=200):
    desc = DataFeedDesc.criteo(batch_size=bs)
    desc.key_bucket_min = 512
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.set_thread(1)
    ds.load_into_memory()
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0,
                          learning_rate=0.05, mf_learning_rate=0.05)
    table = EmbeddingTable(mf_dim=4, capacity=1 << 12, cfg=cfg,
                           unique_bucket_min=512)
    tr = Trainer(DeepFM(hidden=(16, 8)), table, desc, tx=optax.adam(1e-2),
                 seed=3)
    return tr, ds


def _ragged_records(n=400, num_slots=4, seed=0):
    """Zipf-ragged multi-key slots — the non-trivial segment stream that
    actually exercises the fused pooling kernel."""
    from paddlebox_tpu.data.record import SlotRecord
    rng = np.random.default_rng(seed)
    recs = []
    for i in range(n):
        counts = np.minimum(rng.zipf(1.5, size=num_slots), 8)
        counts[rng.integers(0, num_slots)] = max(
            1, counts[rng.integers(0, num_slots)])
        offs = np.zeros(num_slots + 1, np.int32)
        np.cumsum(counts, out=offs[1:])
        keys = rng.integers(0, 3000, size=int(offs[-1])).astype(np.uint64)
        recs.append(SlotRecord(
            keys=keys, slot_offsets=offs,
            dense=rng.normal(size=3).astype(np.float32),
            label=float(i % 2), show=1.0, clk=float(i % 2)))
    return recs


def _trainer_ragged(bs=64, seed=0):
    from paddlebox_tpu.data import InMemoryDataset
    slots = [SlotDef("label", "float", 1), SlotDef("d", "float", 3)]
    slots += [SlotDef(f"S{i}", "uint64") for i in range(4)]
    desc = DataFeedDesc(slots=slots, label_slot="label", batch_size=bs,
                        key_bucket_min=512)
    ds = InMemoryDataset(desc)
    ds.records = _ragged_records(seed=seed)
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0,
                          learning_rate=0.05, mf_learning_rate=0.05)
    table = EmbeddingTable(mf_dim=4, capacity=1 << 12, cfg=cfg,
                           unique_bucket_min=512)
    tr = Trainer(DeepFM(hidden=(16, 8)), table, desc, tx=optax.adam(1e-2),
                 seed=3)
    return tr, ds


def _logical_state(tr):
    """(sorted keys, host row blob, param leaves) — the numeric form of
    state_digest, comparable with a tolerance."""
    tr.sync_table()
    with tr.table.host_lock:
        keys, rows = tr.table.index.items()
    order = np.argsort(keys)
    blob = tr.table._gather_host(rows[order])
    leaves = [np.asarray(l) for l in jax.tree.leaves(
        jax.device_get(tr.state.params))]
    return keys[order], blob, leaves


def test_uniform_trivial_layout_digest_exact(criteo_files):
    """Trivial layout: the flag leaves the reshape fast path alone —
    the whole seeded train job is byte-for-byte identical."""
    with flags_scope(use_pallas_seqpool=False):
        tr0, ds = _trainer_uniform(criteo_files)
        tr0.train_pass(ds)
        d0 = state_digest(tr0)
    with flags_scope(use_pallas_seqpool=True):
        tr1, ds = _trainer_uniform(criteo_files)
        tr1.train_pass(ds)
        d1 = state_digest(tr1)
    assert d0 == d1


def test_pallas_gather_digest_exact(criteo_files):
    """use_pallas_gather=True (the already-wired table.py line-gather):
    gather_rows is bitwise a gather, so the digest matches exactly."""
    with flags_scope(use_pallas_gather=False):
        tr0, ds = _trainer_uniform(criteo_files)
        tr0.train_pass(ds)
        d0 = state_digest(tr0)
    with flags_scope(use_pallas_gather=True):
        tr1, ds = _trainer_uniform(criteo_files)
        tr1.train_pass(ds)
        d1 = state_digest(tr1)
    assert d0 == d1


def test_zipf_ragged_state_close(criteo_files):
    """Zipf-ragged resident train, two passes: fused Pallas pooling vs
    the XLA composition — same keys, table rows and dense params within
    the documented f32 tolerance (forward pooled outputs and the pushed
    grads both ride this: the table rows ARE the accumulated pushes)."""
    def run(flag):
        with flags_scope(use_pallas_seqpool=flag):
            tr, ds = _trainer_ragged()
            tr.train_pass(ds)
            tr.train_pass(ds)
            return _logical_state(tr)

    k0, b0, p0 = run(False)
    k1, b1, p1 = run(True)
    np.testing.assert_array_equal(k0, k1)
    for f in sorted(b0):
        np.testing.assert_allclose(
            b1[f], b0[f], rtol=2e-4, atol=2e-5,
            err_msg=f"table field {f} diverged beyond f32 tolerance")
    for a, b in zip(p0, p1):
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-5)


def _pv_train_state(flag_overrides, n_pvs=40, bs=32, seed=0):
    """Compact PV/AdsRank training job (the ISSUE 13 lane): PV-merged
    batches through rank_attention + slot_fc batch_fc + cross_norm,
    pull→train→push on a small table. Returns (params_leaves,
    table_packed) — the byte-comparable logical state."""
    import optax

    import jax.numpy as jnp

    from paddlebox_tpu.data import DataFeedDesc, SlotDef
    from paddlebox_tpu.data.pv import PvBatchBuilder
    from paddlebox_tpu.data.record import SlotRecord
    from paddlebox_tpu.models import AdsRank
    from paddlebox_tpu.ops import fused_seqpool_cvm, init_cross_norm_summary

    from paddlebox_tpu.ps import EmbeddingTable

    S, MR, DM = 4, 3, 8
    rng = np.random.default_rng(seed)
    recs = []
    for sid in range(n_pvs):
        n_ads = int(rng.integers(2, 4))
        ranks = rng.permutation(n_ads) + 1
        for a in range(n_ads):
            keys = (rng.integers(0, 60, S)
                    + np.arange(S) * 60).astype(np.uint64)
            label = float(rng.random() < 0.3)
            recs.append(SlotRecord(
                keys=keys, slot_offsets=np.arange(S + 1, dtype=np.int32),
                dense=rng.normal(size=2).astype(np.float32), label=label,
                show=1.0, clk=label, search_id=sid, rank=int(ranks[a]),
                cmatch=222))
    slots = [SlotDef("label", "float", 1), SlotDef("dense", "float", 2)]
    slots += [SlotDef(f"C{i}", "uint64") for i in range(S)]
    desc = DataFeedDesc(slots=slots, batch_size=bs, label_slot="label",
                        pv_batch_size=8, key_bucket_min=256)
    from paddlebox_tpu.ps import SparseSGDConfig
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0,
                          learning_rate=0.05, mf_learning_rate=0.05)
    with flags_scope(**flag_overrides):
        table = EmbeddingTable(mf_dim=4, capacity=1 << 10, cfg=cfg,
                               unique_bucket_min=256)
        model = AdsRank(d_model=DM, max_rank=MR, hidden=(8,),
                        slot_fc=True, cross_norm=True)
        summary = init_cross_norm_summary(1, DM)
        batches = PvBatchBuilder(desc, max_rank=MR).batches(recs)
        d = 3 + table.mf_dim
        b0, ro0 = batches[0]
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((bs, S, d)), jnp.zeros((bs, 2)),
                            jnp.asarray(ro0), summary)
        import optax as _optax
        tx = _optax.adam(5e-3)
        opt = tx.init(params)

        @jax.jit
        def step(params, opt, values_k, segments, show_clk, dense,
                 label, ro):
            def loss_fn(params, values_k):
                pooled = fused_seqpool_cvm(values_k, segments, show_clk,
                                           bs, S)
                logits = model.apply(params, pooled, dense, ro, summary)
                return jnp.mean(
                    _optax.sigmoid_binary_cross_entropy(logits, label))
            _, (gp, gk) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(params, values_k)
            upd, opt = tx.update(gp, opt, params)
            return _optax.apply_updates(params, upd), opt, gk

        for batch, ro in batches:
            idx = table.prepare(batch)
            values_k = table.pull(idx)
            show_clk = jnp.stack([jnp.asarray(batch.show),
                                  jnp.asarray(batch.clk)], axis=1)
            params, opt, gk = step(
                params, opt, values_k, jnp.asarray(batch.segments),
                show_clk, jnp.asarray(batch.dense),
                jnp.asarray(batch.label), jnp.asarray(ro))
            table.push(idx, gk)
        leaves = [np.asarray(l) for l in jax.tree.leaves(
            jax.device_get(params))]
        packed = np.asarray(table.state.packed)
    return leaves, packed


def test_pv_train_default_off_byte_identical():
    """The ISSUE 13 acceptance digest gate, PV half: a seeded PV/
    AdsRank train job under DEFAULT flags is byte-for-byte identical to
    one with the three CTR flags explicitly off (defaults really are
    off and the seams leave the program untouched)."""
    l0, p0 = _pv_train_state({})
    l1, p1 = _pv_train_state(dict(use_pallas_rank_attention=False,
                                  use_pallas_batch_fc=False,
                                  use_pallas_cross_norm=False))
    assert p0.tobytes() == p1.tobytes()
    for a, b in zip(l0, l1):
        assert a.tobytes() == b.tobytes()


def test_pv_train_pallas_state_close():
    """Flag-on PV train vs the XLA composition: rank_attention/batch_fc
    grads are bitwise, so the only drift is the fused forwards' MXU
    summation order compounding through Adam — the same f32 tolerance
    class as the zipf seqpool gate."""
    l0, p0 = _pv_train_state({})
    l1, p1 = _pv_train_state(dict(use_pallas_rank_attention=True,
                                  use_pallas_batch_fc=True,
                                  use_pallas_cross_norm=True))
    np.testing.assert_allclose(p1, p0, rtol=2e-4, atol=2e-5)
    for a, b in zip(l0, l1):
        np.testing.assert_allclose(b, a, rtol=2e-3, atol=2e-4)


def test_resident_digest_immune_to_ctr_flags(criteo_files):
    """The ISSUE 13 acceptance digest gate, resident half: the CTR op
    family is not on the DeepFM resident path, so flipping all three
    flags ON must reproduce the flag-off resident state_digest EXACTLY
    (no accidental coupling through shared modules)."""
    with flags_scope(use_pallas_rank_attention=False,
                     use_pallas_batch_fc=False,
                     use_pallas_cross_norm=False):
        tr0, ds = _trainer_uniform(criteo_files)
        tr0.train_pass(ds)
        d0 = state_digest(tr0)
    with flags_scope(use_pallas_rank_attention=True,
                     use_pallas_batch_fc=True,
                     use_pallas_cross_norm=True):
        tr1, ds = _trainer_uniform(criteo_files)
        tr1.train_pass(ds)
        d1 = state_digest(tr1)
    assert d0 == d1


def test_stop_polled_build_leaves_host_kv_consistent(criteo_files):
    """A stop-polled (aborted) preloader build leaves the host kv
    consistent — every key resolves to its own distinct row — and the
    pipeline restarts cleanly after clear_stop: a fresh run from this
    table digests identically to a run from scratch."""
    from paddlebox_tpu.resilience import preemption
    from paddlebox_tpu.train.device_pass import PassPreloader
    tr, ds = _trainer_uniform(criteo_files)
    pre = PassPreloader(iter([ds] * 6), tr.table, depth=1)
    try:
        pre.start_next()
        assert pre.wait() is not None
        preemption.request_stop("test")
        while pre.wait() is not None:   # drain staged passes
            pass
        pre.drain(timeout=30)
    finally:
        preemption.clear_stop()
        pre.drain()
    with tr.table.host_lock:
        keys, rows = tr.table.index.items()
        np.testing.assert_array_equal(tr.table.index.lookup(keys), rows)
    assert len(np.unique(rows)) == len(rows) == len(np.unique(keys))
    assert (rows >= 0).all() and (rows < tr.table.capacity).all()
    tr.train_passes_resident([ds], depth=1)
    tr0, ds0 = _trainer_uniform(criteo_files)
    tr0.train_passes_resident([ds0], depth=0)
    assert state_digest(tr) == state_digest(tr0)
