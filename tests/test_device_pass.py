"""Device-resident pass mode: on-device dedup correctness and equivalence
with the streaming (per-batch H2D) trainer path."""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
from paddlebox_tpu.data.criteo import generate_criteo_files
from paddlebox_tpu.models import DeepFM
from paddlebox_tpu.ops.device_unique import dedup_rows
from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
from paddlebox_tpu.train import PassPreloader, ResidentPass, Trainer


def test_dedup_rows_matches_numpy():
    rng = np.random.default_rng(0)
    cap = 500
    for trial in range(5):
        rows = rng.integers(0, cap, size=300).astype(np.int32)
        rows[rng.random(300) < 0.1] = cap  # sentinel (invalid keys)
        uniq, gidx, n = jax.jit(dedup_rows, static_argnums=1)(
            jnp.asarray(rows), cap)
        uniq, gidx = np.asarray(uniq), np.asarray(gidx)
        assert n.dtype == jnp.int32 and int(n) == len(np.unique(rows))
        # expansion reconstructs every key's row
        np.testing.assert_array_equal(uniq[gidx], rows)
        ref = np.unique(rows)
        u = len(ref)
        np.testing.assert_array_equal(uniq[:u], ref)  # ascending, compact
        assert (uniq[u:] > cap).all()         # OOB pads (gathers clamp,
        assert len(set(uniq.tolist())) == len(uniq)  # scatters drop, unique


def test_dedup_rows_all_sentinel():
    cap = 64
    rows = jnp.full(16, cap, jnp.int32)
    uniq, gidx, n = dedup_rows(rows, cap)
    assert int(uniq[0]) == cap and (np.asarray(gidx) == 0).all()
    assert int(n) == 1      # the sentinel entry alone


@pytest.mark.parametrize("pad_keys,dups", [(0, False), (0, True),
                                           (40, False), (40, True)])
def test_dedup_rows_count_includes_the_sentinel_entry(pad_keys, dups):
    """The third return value is ``len(np.unique(rows))``: the sentinel
    entry that pad keys collapse into counts, since ``gather_idx``
    points at it; without pad keys there is no such entry."""
    rng = np.random.default_rng(pad_keys + dups)
    cap, k = 900, 256
    real = k - pad_keys
    rows = (rng.integers(0, 60, real) if dups
            else rng.choice(cap, real, replace=False))
    rows = np.concatenate([rows, np.full(pad_keys, cap)]).astype(np.int32)
    uniq, gidx, n = dedup_rows(jnp.asarray(rows), cap)
    n = int(n)
    assert n == len(np.unique(rows))
    uniq, gidx = np.asarray(uniq), np.asarray(gidx)
    assert gidx.max() == n - 1                 # every slot below n is used
    assert (uniq[:n] <= cap).all() and (uniq[n:] > cap).all()
    assert (uniq[n - 1] == cap) == (pad_keys > 0)


def _dedup_case(name):
    """-> (rows int32 [K], capacity), K = 1,000: no power of two."""
    rng = np.random.default_rng(36)
    cap, k = 5000, 1000
    if name == "all-sentinel":
        rows = np.full(k, cap)
    elif name == "no-repeat":
        rows = rng.choice(cap, k, replace=False)
    elif name == "heavy-repeat":
        rows = rng.integers(0, 7, k) * 700
    elif name == "padded":            # a ragged step: pads at the tail
        rows = np.concatenate([rng.integers(0, 300, k - 137),
                               np.full(137, cap)])
    elif name == "sentinels-inside":  # invalid keys between real ones
        rows = rng.integers(cap - 40, cap + 1, k)
    else:
        raise ValueError(name)
    return rows.astype(np.int32), cap


@pytest.mark.parametrize("name", ["all-sentinel", "no-repeat",
                                  "heavy-repeat", "padded",
                                  "sentinels-inside"])
def test_dedup_rows_places_by_sorts_what_numpy_places(name):
    """ISSUE 36: the results placed by sorts are numpy's, entry for
    entry over the real prefix; the pads keep the docstring's contract
    (distinct, out of bounds, never pointed at)."""
    rows, cap = _dedup_case(name)
    k = len(rows)
    uniq, gidx, n = jax.jit(dedup_rows, static_argnums=1)(
        jnp.asarray(rows), cap)
    uniq, gidx, n = np.asarray(uniq), np.asarray(gidx), int(n)
    ref, inv = np.unique(rows, return_inverse=True)
    assert n == len(ref)
    np.testing.assert_array_equal(uniq[:n], ref)
    np.testing.assert_array_equal(gidx, inv)
    assert uniq.dtype == np.int32 and gidx.dtype == np.int32
    pads = uniq[n:]
    assert (pads > cap).all() and (pads <= cap + k).all()
    assert len(np.unique(pads)) == len(pads) and (np.diff(pads) > 0).all()


def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text()


@pytest.mark.parametrize("what", ["dedup_rows", "unpack_u16m-m8",
                                  "unpack_u16m-m2",
                                  "unpack_u16m-m2-batched"])
def test_key_decode_and_dedup_lower_without_a_gather_or_scatter(what):
    """A TPU gather or scatter is paid by the index (1.0-1.7 ms each at
    cell 1's 212,992 keys: ledger, PR 35): the mechanism of ISSUE 36 is
    that these two functions issue none, and this pins it."""
    from paddlebox_tpu.ops.bitpack import unpack_u16m
    k = 1040
    if what == "dedup_rows":
        text = _lowered(lambda r: dedup_rows(r, 5000),
                        jnp.zeros(k, jnp.int32))
        assert text.count("stablehlo.sort") == 3
    else:
        m = 8 if "m8" in what else 2
        lead = (3,) if "batched" in what else ()
        text = _lowered(lambda lo, hi: unpack_u16m(lo, hi, m),
                        jnp.zeros(lead + (k,), jnp.uint16),
                        jnp.zeros(lead + (k * m // 8,), jnp.uint8))
    assert "gather" not in text and "scatter" not in text


@pytest.mark.parametrize("batched", [False, True],
                         ids=["flat", "leading-axis"])
@pytest.mark.parametrize("m", [1, 2, 4, 8])
def test_u16m_wire_round_trips(m, batched):
    """16-bit lows + m-bit packed highs (ops/bitpack): every value of
    the range's edges and a random fill come back, with and without a
    leading batch axis; the packed highs are K * m / 8 bytes."""
    from paddlebox_tpu.ops.bitpack import pack_u16m, unpack_u16m
    rng = np.random.default_rng(m)
    k, top = 1048, 1 << (16 + m)
    v = rng.integers(0, top, (3, k) if batched else (k,)).astype(np.int32)
    v[..., :4] = (0, top - 1, 0xFFFF, 0x10000)
    lo, hi = pack_u16m(v, m)
    assert lo.dtype == np.uint16 and hi.dtype == np.uint8
    assert lo.shape == v.shape and hi.shape == v.shape[:-1] + (k * m // 8,)
    got = jax.jit(unpack_u16m, static_argnums=2)(
        jnp.asarray(lo), jnp.asarray(hi), m)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), v)


@pytest.fixture(scope="module")
def criteo_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("criteo_dp")
    return generate_criteo_files(str(d), num_files=2, rows_per_file=1500,
                                 vocab_per_slot=40, seed=11)


def _make(files, bs=128):
    desc = DataFeedDesc.criteo(batch_size=bs)
    desc.key_bucket_min = 4096
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.set_thread(2)
    ds.load_into_memory()
    # mf_initial_range=0 → no rng in lazy-mf init, so the streaming and
    # resident paths are numerically comparable
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0,
                          learning_rate=0.05, mf_learning_rate=0.05)
    table = EmbeddingTable(mf_dim=4, capacity=1 << 13, cfg=cfg,
                           unique_bucket_min=4096)
    tr = Trainer(DeepFM(hidden=(16, 8)), table, desc, tx=optax.adam(1e-2),
                 seed=3)
    return tr, ds


def test_resident_matches_streaming(criteo_files):
    tr_a, ds = _make(criteo_files)
    tr_b, _ = _make(criteo_files)
    ra = [tr_a.train_pass(ds) for _ in range(2)][-1]
    rb = [tr_b.train_pass_resident(ds) for _ in range(2)][-1]
    assert rb["batches"] == ra["batches"]
    assert tr_b.table.feature_count == tr_a.table.feature_count
    assert np.isclose(rb["auc"], ra["auc"], atol=2e-3)
    # dense params track closely (order-of-reduction float drift only)
    pa = jax.tree.leaves(tr_a.state.params)
    pb = jax.tree.leaves(tr_b.state.params)
    for a, b in zip(pa, pb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3)
    # sparse table rows agree for the keys both saw
    keys, rows_a = tr_a.table.index.items()
    rows_b = tr_b.table.index.lookup(keys)
    st_a = jax.device_get(tr_a.state.table)
    st_b = jax.device_get(tr_b.state.table)
    np.testing.assert_allclose(np.asarray(st_a.embed_w)[rows_a],
                               np.asarray(st_b.embed_w)[rows_b],
                               rtol=2e-2, atol=2e-3)


def test_resident_learns(criteo_files):
    tr, ds = _make(criteo_files)
    first = tr.train_pass_resident(ds)
    tr.reset_metrics()
    for _ in range(3):
        last = tr.train_pass_resident(ds)
    assert last["auc"] > max(first["auc"], 0.55)
    assert np.isfinite(last["auc"])


def test_resident_chunked_equals_whole(criteo_files):
    tr_a, ds = _make(criteo_files)
    tr_b, _ = _make(criteo_files)
    rp_a = ResidentPass.build(ds, tr_a.table)
    tr_a.train_pass_resident(rp_a)
    from paddlebox_tpu.train.device_pass import ResidentPassRunner
    rp_b = ResidentPass.build(ds, tr_b.table)
    runner = ResidentPassRunner(tr_b.step_fn, tr_b.table.capacity,
                                rp_b.segs is None, chunk=3)
    tr_b.state, _ = runner.run_pass(tr_b.state, rp_b, tr_b._rng)
    tr_b.sync_table()
    pa = jax.tree.leaves(tr_a.state.params)
    pb = jax.tree.leaves(tr_b.state.params)
    for a, b in zip(pa, pb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def _rand_records(n, num_slots=4, seed=0, trivial=False):
    """trivial=True → exactly one key per slot (slot-ordered layout);
    False → variable keys per slot (non-trivial segments)."""
    from paddlebox_tpu.data.record import SlotRecord
    rng = np.random.default_rng(seed)
    recs = []
    for i in range(n):
        if trivial:
            counts = np.ones(num_slots, np.int64)
        else:
            counts = rng.integers(0, 3, size=num_slots)
            counts[rng.integers(0, num_slots)] += 1  # ≥1 key somewhere
        offs = np.zeros(num_slots + 1, np.int32)
        np.cumsum(counts, out=offs[1:])
        keys = rng.integers(0, 5000, size=int(offs[-1])).astype(np.uint64)
        recs.append(SlotRecord(
            keys=keys, slot_offsets=offs,
            dense=rng.normal(size=3).astype(np.float32),
            label=float(i % 2), show=1.0, clk=float(i % 2)))
    return recs


@pytest.mark.parametrize("trivial", [True, False])
def test_build_columnar_matches_record_path(trivial):
    """The vectorized columnar packer must produce byte-identical passes
    to the per-batch record path (incl. a partial tail batch)."""
    from paddlebox_tpu.data import InMemoryDataset, SlotDef
    slots = [SlotDef("label", "float", 1), SlotDef("d", "float", 3)]
    slots += [SlotDef(f"S{i}", "uint64") for i in range(4)]
    desc = DataFeedDesc(slots=slots, label_slot="label", batch_size=64,
                        key_bucket_min=512)
    recs = _rand_records(300, num_slots=4, seed=5, trivial=trivial)

    ds_rec = InMemoryDataset(desc)
    ds_rec.records = list(recs)
    ds_col = InMemoryDataset(desc)
    ds_col.records = list(recs)
    ds_col.columnarize()

    mk = lambda: EmbeddingTable(mf_dim=4, capacity=1 << 13,
                                unique_bucket_min=512)
    ta, tb = mk(), mk()
    rp_rec = ResidentPass.build(ds_rec, ta)   # record path (columnar=None)
    rp_col = ResidentPass.build(ds_col, tb)   # vectorized path
    assert rp_rec.num_batches == rp_col.num_batches
    assert rp_rec.num_records == rp_col.num_records
    np.testing.assert_array_equal(rp_rec.uniq, rp_col.uniq)
    np.testing.assert_array_equal(rp_rec.gidx, rp_col.gidx)
    np.testing.assert_array_equal(ta.slot_host, tb.slot_host)
    assert ta.slot_host.max() > 0  # slots were recorded host-side
    np.testing.assert_array_equal(rp_rec.meta, rp_col.meta)
    np.testing.assert_allclose(rp_rec.floats, rp_col.floats)
    if rp_rec.segs is None:
        assert rp_col.segs is None
    else:
        np.testing.assert_array_equal(rp_rec.segs, rp_col.segs)
    # the pull-index invariants the step relies on: duplicate-free rows,
    # OOB pads after the real block, gather idx within [0, u]
    for i in range(rp_col.num_batches):
        u = rp_col.meta[i, 2]
        assert len(np.unique(rp_col.uniq[i])) == rp_col.uniq.shape[1]
        assert (rp_col.uniq[i, :u] <= ta.capacity).all()
        assert (rp_col.uniq[i, u:] > ta.capacity).all()
        assert (rp_col.gidx[i] <= u).all()


def _decode_uniq(rp, runner):
    """Decode every batch's uniq through the runner's traced view."""
    rp.upload()
    uniq_t, gidx_t = rp.dev[0], rp.dev[1]
    out = []
    for i in range(rp.num_batches):
        view = runner._make_view(
            tuple(jnp.asarray(a[i]) for a in uniq_t),
            tuple(jnp.asarray(a[i]) for a in gidx_t),
            jnp.asarray(rp.floats[i]), jnp.asarray(rp.meta[i]),
            jnp.zeros((1,), jnp.int32) if rp.segs is None
            else jnp.asarray(rp.segs[i]),
            jnp.zeros((2, 0), jnp.float32))
        out.append((np.asarray(view.unique_rows),
                    np.asarray(view.gather_idx)))
    return out


def test_uniq_wire_roundtrip_dense():
    """u16-delta wire: dense row sets (the common case) reconstruct the
    exact pull index through the runner's traced decode."""
    from paddlebox_tpu.data import InMemoryDataset, SlotDef
    from paddlebox_tpu.train.device_pass import ResidentPassRunner
    recs = _rand_records(300, num_slots=4, seed=7, trivial=True)
    slots = [SlotDef("label", "float", 1), SlotDef("d", "float", 3)]
    slots += [SlotDef(f"S{i}", "uint64") for i in range(4)]
    desc = DataFeedDesc(slots=slots, label_slot="label", batch_size=64,
                        key_bucket_min=512)
    ds = InMemoryDataset(desc)
    ds.records = recs
    ds.columnarize()
    table = EmbeddingTable(mf_dim=4, capacity=1 << 13,
                           unique_bucket_min=64)
    rp = ResidentPass.build(ds, table)
    runner = ResidentPassRunner(None, table.capacity, rp.segs is None)
    decoded = _decode_uniq(rp, runner)
    assert len(rp.dev[0]) == 3  # the delta encoding was chosen
    for i, (du, dg) in enumerate(decoded):
        u = rp.meta[i, 2]
        np.testing.assert_array_equal(du[:u], rp.uniq[i, :u])
        assert (du[u:] > table.capacity).all()
        np.testing.assert_array_equal(dg, rp.gidx[i])


@pytest.mark.parametrize("n_rows,expect_delta", [(20, True), (100, False)])
def test_uniq_wire_roundtrip_sparse_gaps(n_rows, expect_delta):
    """Huge row gaps (sparse occupancy of a big table): few gaps ride the
    u16 wire's exception correction; many gaps fall back to u24 halves.
    Built directly (the hash index assigns rows densely in practice)."""
    from paddlebox_tpu.train.device_pass import (ResidentPass,
                                                 ResidentPassRunner)
    from paddlebox_tpu.ps.table import fill_oob_pads
    cap = 1 << 23
    rng = np.random.default_rng(3)
    rows = np.sort(rng.choice(cap - 1, size=n_rows, replace=False)
                   .astype(np.int32))
    u_pad = 64 if n_rows <= 64 else 512
    uniq = np.empty((1, u_pad), np.int32)
    uniq[0, :n_rows] = rows
    fill_oob_pads(uniq[0], n_rows, cap)
    k = 128
    gidx = rng.integers(0, n_rows, size=(1, k)).astype(np.int32)
    floats = np.zeros((1, 4, 7), np.float32)
    meta = np.array([[k, 8, n_rows, int(rows[0])]], np.int32)
    rp = ResidentPass(uniq, gidx, floats, meta, None, 4)
    runner = ResidentPassRunner(None, cap, True)
    decoded = _decode_uniq(rp, runner)
    assert (len(rp.dev[0]) == 3) == expect_delta
    du, dg = decoded[0]
    np.testing.assert_array_equal(du[:n_rows], rows)
    assert (du[n_rows:] > cap).all()
    np.testing.assert_array_equal(dg, gidx[0])


def test_pass_preloader(criteo_files):
    tr, ds = _make(criteo_files)
    datasets = iter([ds, ds, ds])
    pre = PassPreloader(datasets, tr.table)
    assert pre.start_next()
    results = []
    while True:
        rp = pre.wait()
        if rp is None:
            break
        has_more = pre.start_next()  # overlap next build with training
        results.append(tr.train_pass_resident(rp))
        if not has_more:
            break
    assert len(results) == 3
    assert all(np.isfinite(r["auc"]) for r in results)


def test_pass_preloader_depth2_bit_identical_to_depth1(criteo_files):
    """Deep pipeline invariant (ISSUE 5): depth only changes
    scheduling, never results — the depth-2 pipeline's 4 overlapped
    passes produce the exact logical state (params + table rows by
    key + AUC) of the depth-1 run."""
    from paddlebox_tpu.train.checkpoint import state_digest

    def run(depth):
        tr, ds = _make(criteo_files)
        res = tr.train_passes_resident([ds, ds, ds, ds], depth=depth)
        assert len(res) == 4
        return tr, state_digest(tr)

    tr1, d1 = run(1)
    tr2, d2 = run(2)
    assert d1 == d2
    for a, b in zip(jax.tree.leaves(tr1.state.params),
                    jax.tree.leaves(tr2.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_preloader_hbm_budget_clamps(criteo_files):
    """An oversized pass degrades the pipeline to depth 1 — loudly,
    never stacking staged passes until HBM OOMs."""
    tr, ds = _make(criteo_files)
    pre = PassPreloader(iter([ds, ds, ds]), tr.table, depth=3,
                        hbm_budget_bytes=1)  # any real pass overflows
    pre.start_next()
    results = []
    while True:
        rp = pre.wait()
        if rp is None:
            break
        results.append(tr.train_pass_resident(rp))
    assert len(results) == 3           # degraded, but never starved
    assert pre.depth_clamped
    assert pre._effective_depth == 1
    pre.drain()


def test_bulk_assign_matches_serial(criteo_files):
    """Whole-pass bulk key assignment (one host_lock round-trip)
    produces the same per-batch index as the serial per-batch path:
    key→row decode agrees with the index either way, and on the
    native (first-occurrence) index the builds are row-for-row
    identical."""
    from paddlebox_tpu.config import flags_scope
    from paddlebox_tpu.native import load_native
    tr_a, ds = _make(criteo_files)
    tr_b, _ = _make(criteo_files)
    with flags_scope(bulk_pass_assign=True):
        rp_a = ResidentPass.build(ds, tr_a.table)
    with flags_scope(bulk_pass_assign=False):
        rp_b = ResidentPass.build(ds, tr_b.table)
    assert rp_a.num_batches == rp_b.num_batches
    np.testing.assert_array_equal(rp_a.meta[:, (0, 1, 2)],
                                  rp_b.meta[:, (0, 1, 2)])
    # both builds registered the same key set, and each build's wire
    # decodes every key to the row its own index assigned
    keys_a, rows_a = tr_a.table.index.items()
    keys_b, _ = tr_b.table.index.items()
    np.testing.assert_array_equal(np.sort(keys_a), np.sort(keys_b))
    for rp, tr in ((rp_a, tr_a), (rp_b, tr_b)):
        batches = list(ds.batches())
        for i, b in enumerate(batches):
            nk = b.num_keys
            rows_wire = rp.uniq[i][rp.gidx[i][:nk]]
            rows_idx = tr.table.index.lookup(b.keys[:nk])
            np.testing.assert_array_equal(rows_wire, rows_idx)
    if load_native() is not None:
        # native assign_unique is first-occurrence — bulk first-seen
        # allocation reproduces the serial walk row for row
        np.testing.assert_array_equal(rp_a.uniq, rp_b.uniq)
        np.testing.assert_array_equal(rp_a.gidx, rp_b.gidx)
        np.testing.assert_array_equal(rp_a.meta, rp_b.meta)


def test_preloader_error_mid_queue(criteo_files):
    """A mid-queue build failure surfaces on the wait() that would
    have consumed the broken pass; passes built before it stay valid,
    and waits after the raise return None."""
    tr, ds = _make(criteo_files)
    calls = {"n": 0}

    def build(d):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("boom at build 2")
        return ResidentPass.build_streamed(d, tr.table, block=False)

    pre = PassPreloader(iter([ds, ds, ds]), build_fn=build, depth=2)
    pre.start_next()
    rp1 = pre.wait()
    assert rp1 is not None             # build 1 is valid and served
    with pytest.raises(RuntimeError, match="boom at build 2"):
        pre.wait()
    assert pre.wait() is None          # pipeline is dead after a raise
    assert calls["n"] == 2             # build 3 never started


def test_preloader_stops_on_request_stop(criteo_files):
    """Graceful preemption: the pipeline stops building within one
    stage poll of request_stop and drain() leaves no build running —
    a long build can't eat the SIGTERM grace window."""
    from paddlebox_tpu.resilience import preemption
    tr, ds = _make(criteo_files)
    pre = PassPreloader(iter([ds] * 6), tr.table, depth=1)
    try:
        pre.start_next()
        rp = pre.wait()
        assert rp is not None
        preemption.request_stop("test")
        served = 0
        while pre.wait() is not None:  # staged passes stay consumable
            served += 1
        assert served <= 1             # depth 1 → at most one staged
        pre.drain(timeout=30)
        assert not pre._worker.is_alive()
        assert pre.builds < 6
    finally:
        preemption.clear_stop()
        pre.drain()


def _q8_records_dataset(num_records=96, seed=3, bad_label=False):
    """Small NON-columnar in-memory dataset (records path) for the q8
    streaming front."""
    from paddlebox_tpu.data import DataFeedDesc, InMemoryDataset, SlotDef
    from paddlebox_tpu.data.record import SlotRecord
    rng = np.random.default_rng(seed)
    slots = [SlotDef("label", "float", 1), SlotDef("dense", "float", 5)]
    slots += [SlotDef(f"C{i}", "uint64") for i in range(1, 5)]
    desc = DataFeedDesc(slots=slots, batch_size=32, label_slot="label",
                        key_bucket_min=128)
    offs = np.arange(5, dtype=np.int32)
    ds = InMemoryDataset(desc)
    for i in range(num_records):
        label = 0.5 if bad_label else float(rng.random() < 0.3)
        ds.records.append(SlotRecord(
            keys=(rng.integers(0, 64, size=4)
                  + np.arange(4) * 64).astype(np.uint64),
            slot_offsets=offs,
            dense=(rng.normal(size=5) * np.array(
                [1, 10, 0.1, 100, 1])).astype(np.float32),
            label=label, show=1.0, clk=label))
    return ds, desc


def test_q8_streaming_front_matches_staged():
    """The streaming (two-phase, min/max) q8 front reproduces the
    staged whole-pass quantization bit for bit when the winsorize
    branch is idle (< 1000 valid rows, the formulas coincide) — while
    never holding a full-pass f32 float block."""
    from paddlebox_tpu.train.device_pass import ResidentPass as RP
    from paddlebox_tpu.train.step import pack_floats, quantize_floats
    ds, _ = _q8_records_dataset()
    assert ds.columnar is None and ds.supports_reiteration
    per_batch, floats, qmeta, trivial, nrec, side = RP._front(ds, "q8")
    assert floats.dtype == np.uint8
    # reference: the staged path's whole-pass quantize
    blocks = [pack_floats(b.dense, b.label, b.show, b.clk)
              for b in ds.batches()]
    ref = np.stack(blocks)
    nb, bsz, d3 = ref.shape
    flat = ref.reshape(nb * bsz, d3)
    rblock, rqmeta = quantize_floats(flat[:, :-3], flat[:, -3],
                                     flat[:, -2], flat[:, -1],
                                     valid=flat[:, -2] > 0)
    np.testing.assert_array_equal(qmeta, rqmeta)
    np.testing.assert_array_equal(floats, rblock.reshape(nb, bsz, d3))


def test_q8_streaming_front_bf16_fallback():
    """Data outside the exact-u8 wire falls back to bf16, matching
    _encode_floats' contract."""
    from paddlebox_tpu.train.device_pass import ResidentPass as RP
    ds, _ = _q8_records_dataset(bad_label=True)  # label 0.5 ≠ rint
    per_batch, floats, qmeta, *_ = RP._front(ds, "q8")
    assert qmeta is None
    assert floats.dtype == jnp.bfloat16


def test_quantize_floats_roundtrip():
    """q8 float wire: affine dequant error bounded by scale/2 per column;
    label/show/clk ride exactly; out-of-range data falls back (None)."""
    from paddlebox_tpu.train.step import dequantize_floats, quantize_floats
    rng = np.random.default_rng(5)
    dense = rng.normal(size=(64, 5)).astype(np.float32) * \
        np.array([1, 10, 0.1, 100, 1], np.float32)
    label = (rng.random(64) < 0.3).astype(np.float32)
    show = np.ones(64, np.float32)
    clk = label.copy()
    block, qmeta = quantize_floats(dense, label, show, clk)
    d, l, s, c = dequantize_floats(jnp.asarray(block), jnp.asarray(qmeta))
    span = dense.max(axis=0) - dense.min(axis=0)
    assert (np.abs(np.asarray(d) - dense) <= span / 255.0 * 0.51 + 1e-7).all()
    np.testing.assert_array_equal(np.asarray(l), label)
    np.testing.assert_array_equal(np.asarray(s), show)
    np.testing.assert_array_equal(np.asarray(c), clk)
    # constant column: scale clamps to 1, roundtrips exactly
    const = np.full((8, 2), 3.5, np.float32)
    blk2, qm2 = quantize_floats(const, label[:8], show[:8], clk[:8])
    d2 = np.asarray(dequantize_floats(jnp.asarray(blk2),
                                      jnp.asarray(qm2))[0])
    np.testing.assert_allclose(d2, const)
    # fallbacks
    assert quantize_floats(np.array([[np.nan]], np.float32),
                           label[:1], show[:1], clk[:1]) is None
    assert quantize_floats(const[:1], np.array([0.5], np.float32),
                           show[:1], clk[:1]) is None


def test_resident_q8_wire_learns(criteo_files):
    """The q8 wire trains end-to-end and tracks the f32 wire's AUC."""
    tr_a, ds = _make(criteo_files)
    tr_b, _ = _make(criteo_files)
    for _ in range(3):
        ra = tr_a.train_pass_resident(ResidentPass.build(ds, tr_a.table))
        rb = tr_b.train_pass_resident(
            ResidentPass.build(ds, tr_b.table, floats_dtype="q8"))
    assert rb["auc"] > 0.55
    assert np.isclose(rb["auc"], ra["auc"], atol=5e-3)


def test_build_streamed_equals_build(criteo_files):
    """Streamed (chunked, overlapped-upload) build produces the exact
    same staged pass as the plain builder."""
    tr_a, ds = _make(criteo_files)
    tr_b, _ = _make(criteo_files)
    rp_a = ResidentPass.build(ds, tr_a.table, floats_dtype="q8")
    rp_a.upload()
    rp_b = ResidentPass.build_streamed(ds, tr_b.table, floats_dtype="q8")
    np.testing.assert_array_equal(rp_a.uniq, rp_b.uniq)
    np.testing.assert_array_equal(rp_a.gidx, rp_b.gidx)
    np.testing.assert_array_equal(rp_a.meta, rp_b.meta)
    np.testing.assert_array_equal(rp_a.floats, rp_b.floats)
    if rp_a.segs is None:
        assert rp_b.segs is None
    else:
        np.testing.assert_array_equal(rp_a.segs, rp_b.segs)
    assert rp_b.dev is not None
    for a, b in zip(jax.tree.leaves(rp_a.dev), jax.tree.leaves(rp_b.dev)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and it trains
    tr_b.train_pass_resident(rp_b)


def test_uniq_wire_d8(criteo_files):
    """Warm tables produce small row gaps → the u8 delta wire engages."""
    tr, ds = _make(criteo_files)
    ResidentPass.build(ds, tr.table)          # warm the index
    rp = ResidentPass.build(ds, tr.table)     # steady state
    rp.upload()
    assert len(rp.dev[0]) == 3 and rp.dev[0][0].dtype == jnp.uint8
    from paddlebox_tpu.train.device_pass import ResidentPassRunner
    runner = ResidentPassRunner(None, tr.table.capacity, rp.segs is None)
    decoded = _decode_uniq(rp, runner)
    for i, (du, dg) in enumerate(decoded):
        u = rp.meta[i, 2]
        np.testing.assert_array_equal(du[:u], rp.uniq[i, :u])
        assert (du[u:] > tr.table.capacity).all()


def test_q8_range_excludes_padding():
    """Batch-padding rows (zero-filled, show=0) must not widen the q8
    range: a column living far from 0 keeps its tight scale."""
    from paddlebox_tpu.train.step import quantize_floats
    dense = np.full((10, 2), 1000.0, np.float32)
    dense[:, 1] = np.linspace(1000.0, 1010.0, 10)
    dense[8:] = 0.0  # zero-filled pad rows
    show = np.ones(10, np.float32)
    show[8:] = 0.0
    label = np.zeros(10, np.float32)
    block, qmeta = quantize_floats(dense, label, show, label,
                                   valid=show > 0)
    scale, zp = qmeta
    assert zp[1] == 1000.0 and scale[1] <= 10.0 / 255.0 + 1e-6
    # pad rows clip instead of wrapping
    assert (block[8:, :2] == 0).all()


def test_q8_outlier_does_not_collapse_precision():
    """One extreme value must not flatten a column to a single bucket:
    the range winsorizes to the [0.1, 99.9] percentiles and the outlier
    saturates with bounded error."""
    from paddlebox_tpu.train.step import dequantize_floats, quantize_floats
    rng = np.random.default_rng(7)
    n = 4096
    dense = rng.uniform(0, 100, size=(n, 1)).astype(np.float32)
    dense[17, 0] = 1e6  # heavy-tail outlier
    label = np.zeros(n, np.float32)
    show = np.ones(n, np.float32)
    block, qmeta = quantize_floats(dense, label, show, label)
    d = np.asarray(dequantize_floats(jnp.asarray(block),
                                     jnp.asarray(qmeta))[0])
    body = np.delete(np.arange(n), 17)
    err = np.abs(d[body, 0] - dense[body, 0])
    assert err.max() < 1.0          # body keeps ~100/255 resolution
    assert d[17, 0] >= d[body, 0].max()  # outlier saturates high


def _make_arena(files, bs=128):
    desc = DataFeedDesc.criteo(batch_size=bs)
    desc.key_bucket_min = 4096
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.set_thread(2)
    ds.load_into_memory()
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0,
                          learning_rate=0.05, mf_learning_rate=0.05)
    table = EmbeddingTable(mf_dim=4, capacity=1 << 13, cfg=cfg,
                           unique_bucket_min=4096, arena_slots=26,
                           arena_chunk_bits=6)
    tr = Trainer(DeepFM(hidden=(16, 8)), table, desc, tx=optax.adam(1e-2),
                 seed=3)
    return tr, ds


def test_compact_wire_matches_dedup_wire(criteo_files):
    """The compact (slot-arena local rows + device dedup) wire must train
    identically to the host-dedup wire — same per-key embeddings, same
    dense params — despite a completely different row layout."""
    tr_a, ds = _make(criteo_files)          # dedup wire
    tr_b, _ = _make_arena(criteo_files)     # compact wire
    for _ in range(2):
        rp_a = ResidentPass.build_streamed(ds, tr_a.table)
        assert rp_a.wire == "dedup"
        ra = tr_a.train_pass_resident(rp_a)
        rp_b = ResidentPass.build_streamed(ds, tr_b.table)
        assert rp_b.wire == "compact"
        rb = tr_b.train_pass_resident(rp_b)
    assert np.isclose(rb["auc"], ra["auc"], atol=2e-3)
    pa = jax.tree.leaves(tr_a.state.params)
    pb = jax.tree.leaves(tr_b.state.params)
    for a, b in zip(pa, pb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3)
    keys, rows_a = tr_a.table.index.items()
    rows_b = tr_b.table.index.lookup(keys)
    assert (rows_b >= 0).all()
    st_a = jax.device_get(tr_a.state.table)
    st_b = jax.device_get(tr_b.state.table)
    np.testing.assert_allclose(np.asarray(st_a.embed_w)[rows_a],
                               np.asarray(st_b.embed_w)[rows_b],
                               rtol=2e-2, atol=2e-3)


def test_compact_wire_q8_learns(criteo_files):
    tr, ds = _make_arena(criteo_files)
    first = tr.train_pass_resident(
        ResidentPass.build_streamed(ds, tr.table, floats_dtype="q8"))
    for _ in range(3):
        last = tr.train_pass_resident(
            ResidentPass.build_streamed(ds, tr.table, floats_dtype="q8"))
    assert last["auc"] > max(first["auc"], 0.55)


def test_compact_falls_back_after_slotless_assign(criteo_files):
    """Keys that entered through a slotless path poison the compact wire
    for passes touching them — it must fall back to the dedup wire and
    still train correctly."""
    tr, ds = _make_arena(criteo_files)
    some = ds.columnar.keys[:10].astype(np.uint64)
    tr.table.index.assign(some)  # slotless → default arena
    rp = ResidentPass.build_streamed(ds, tr.table)
    assert rp.wire == "dedup"
    res = tr.train_pass_resident(rp)
    assert np.isfinite(res["auc"])


def test_slot_wire_roundtrips_segments():
    """The SLOT segment wire (u8 slots + u16 per-record counts) must
    reconstruct the exact u18 segment stream, pads included."""
    from paddlebox_tpu.train.device_pass import (ResidentPass,
                                                 ResidentPassRunner)
    rng = np.random.default_rng(5)
    nb, B, S, K = 3, 16, 7, 128
    pad_seg = B * S
    segs = np.full((nb, K), pad_seg, np.int32)
    meta = np.zeros((nb, 4), np.int32)
    for i in range(nb):
        counts = rng.integers(0, 4, size=B)
        nk = int(counts.sum())
        rec = np.repeat(np.arange(B), counts)
        slot = rng.integers(0, S, size=nk)
        segs[i, :nk] = rec * S + slot
        meta[i, :2] = (nk, pad_seg)
    enc = ResidentPass._encode_segs_slotwire(segs, meta, B)
    assert enc is not None and enc[0].dtype == np.uint8
    runner = ResidentPassRunner(None, 64, False)  # no num_slots needed:
    # the decode derives S from meta (pad_segment // B)
    enc_j = tuple(jnp.asarray(a) for a in enc)
    for i in range(nb):
        got = np.asarray(runner._decode_segs(
            tuple(a[i] for a in enc_j), jnp.asarray(meta[i])))
        np.testing.assert_array_equal(got, segs[i])
    # violation: keys not grouped by record → falls back (None).
    # Construct a guaranteed record-order inversion: put a key of the
    # LAST record first.
    bad = segs.copy()
    nk0 = int(meta[0, 0])
    assert nk0 >= 2
    bad[0, 0] = (B - 1) * S  # record B-1, slot 0 ahead of everything
    assert ResidentPass._encode_segs_slotwire(bad, meta, B) is None


def test_compact_wire_sentinel_row_stays_zero(criteo_files):
    """The compact wire maps pad keys to the sentinel row (== capacity)
    and device dedup emits it as an in-bounds unique entry. With lazy mf
    creation active (mf_create_thresholds<=0) and a nonzero
    mf_initial_range, the in-table optimizer must NOT seed the sentinel's
    embedx from RNG — unknown keys read zeros (host_pull / ServingModel
    contract)."""
    desc = DataFeedDesc.criteo(batch_size=128)
    desc.key_bucket_min = 4096
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(criteo_files)
    ds.set_thread(2)
    ds.load_into_memory()
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.5,
                          learning_rate=0.05, mf_learning_rate=0.05)
    table = EmbeddingTable(mf_dim=4, capacity=1 << 13, cfg=cfg,
                           unique_bucket_min=4096, arena_slots=26,
                           arena_chunk_bits=6)
    tr = Trainer(DeepFM(hidden=(16, 8)), table, desc, tx=optax.adam(1e-2),
                 seed=3)
    rp = ResidentPass.build_streamed(ds, tr.table)
    assert rp.wire == "compact"
    tr.train_pass_resident(rp)
    from paddlebox_tpu.ps.table import gather_full_rows
    sent = np.asarray(jax.device_get(gather_full_rows(
        tr.state.table, jnp.asarray([table.capacity], jnp.int32))))
    assert not np.any(sent), sent
    # and host_pull of an unknown key reads zeros
    vals = tr.table.host_pull(np.array([0xdeadbeefcafe], dtype=np.uint64))
    assert not np.any(vals)


def test_resident_metric_registry_accumulates(criteo_files):
    """Registry metric variants now accumulate in RESIDENT mode too: the
    runner collects per-batch predictions and the trainer replays the
    AddAucMonitor feed from the dataset's columnar side channels —
    matching the streaming pass's registry results."""
    tr_a, ds = _make(criteo_files)
    tr_b, _ = _make(criteo_files)
    for tr in (tr_a, tr_b):
        tr.metrics.init_metric("auc2", method="auc")
        tr.metrics.init_metric("wu", method="wuauc")
    ra = tr_a.train_pass(ds)
    rb = tr_b.train_pass_resident(ds)
    ma = tr_a.metrics.get_metric_msg("auc2")
    mb = tr_b.metrics.get_metric_msg("auc2")
    assert np.isclose(mb["auc"], ma["auc"], atol=2e-3), (ma, mb)
    wa = tr_a.metrics.get_metric_msg("wu")
    wb = tr_b.metrics.get_metric_msg("wu")
    assert np.isclose(wb["wuauc"], wa["wuauc"], atol=5e-3), (wa, wb)


def test_compact_wire_non_trivial_segments():
    """Compact wire with multi-key slots (non-trivial segments): the
    wire ships segments and the device derives slots from segment % S —
    must match the dedup wire's training exactly."""
    from paddlebox_tpu.data import DataFeedDesc, InMemoryDataset, SlotDef
    slots = [SlotDef("label", "float", 1), SlotDef("d", "float", 3)]
    slots += [SlotDef(f"S{i}", "uint64") for i in range(4)]
    desc = DataFeedDesc(slots=slots, label_slot="label", batch_size=64,
                        key_bucket_min=512)
    # slot-DISJOINT key spaces (CTR feasigns are globally unique, so a
    # key's slot is stable — the arena relies on that)
    from paddlebox_tpu.data.record import SlotRecord
    rng = np.random.default_rng(11)
    recs = []
    for i in range(512):
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

    def mk(arena):
        ds = InMemoryDataset(desc)
        ds.records = list(recs)
        ds.columnarize()
        cfg = SparseSGDConfig(mf_create_thresholds=0.0,
                              mf_initial_range=0.0,
                              learning_rate=0.05, mf_learning_rate=0.05)
        table = EmbeddingTable(mf_dim=4, capacity=1 << 13, cfg=cfg,
                               unique_bucket_min=512,
                               arena_slots=4 if arena else None,
                               arena_chunk_bits=6)
        tr = Trainer(DeepFM(hidden=(16, 8)), table, desc,
                     tx=optax.adam(1e-2), seed=3)
        return tr, ds

    tr_a, ds_a = mk(False)
    tr_b, ds_b = mk(True)
    for _ in range(2):
        rp_a = ResidentPass.build_streamed(ds_a, tr_a.table)
        assert rp_a.wire == "dedup" and rp_a.segs is not None
        ra = tr_a.train_pass_resident(rp_a)
        rp_b = ResidentPass.build_streamed(ds_b, tr_b.table)
        assert rp_b.wire == "compact" and rp_b.segs is not None
        rb = tr_b.train_pass_resident(rp_b)
    assert np.isclose(rb["auc"], ra["auc"], atol=2e-3), (ra["auc"],
                                                         rb["auc"])
    pa = jax.tree.leaves(tr_a.state.params)
    pb = jax.tree.leaves(tr_b.state.params)
    for a, b in zip(pa, pb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3)


def test_u12_locals_wire_roundtrip_and_selection():
    """u12 byte-pair wire (ops/bitpack): exact roundtrip, and
    _encode_locals picks it exactly when locals fit 12 bits (the
    thousand-slot wire diet)."""
    import jax.numpy as jnp

    from paddlebox_tpu.ops.bitpack import pack_u12, unpack_u12

    rng = np.random.default_rng(4)
    v = rng.integers(0, 1 << 12, size=(6, 512)).astype(np.int32)
    (b,) = pack_u12(v)
    assert b.dtype == np.uint8 and b.shape == (6, 768)  # 1.5 B/value
    np.testing.assert_array_equal(np.asarray(unpack_u12(jnp.asarray(b))),
                                  v)
    # boundary values survive
    edge = np.array([[0, 4095, 1, 4094]], np.int32)
    np.testing.assert_array_equal(
        np.asarray(unpack_u12(jnp.asarray(pack_u12(edge)[0]))), edge)

    enc = ResidentPass._encode_locals(v, bits=12)
    assert len(enc) == 1 and enc[0].dtype == np.uint8
    enc16 = ResidentPass._encode_locals(v, bits=13)
    assert enc16[0].dtype == np.uint16
    # odd K cannot pair-pack → u16
    enc_odd = ResidentPass._encode_locals(v[:, :511], bits=12)
    assert enc_odd[0].dtype == np.uint16


def test_compact_wire_u12_matches_u16(criteo_files):
    """A small-vocab arena (locals ≤ 12 bits) trains identically through
    the u12 and u16 local wires."""
    import jax

    def run(force16):
        tr, ds = _make_arena(criteo_files)
        if force16:
            orig = ResidentPass._encode_locals

            def enc16(locs, bits):
                return orig(locs, max(bits, 13))
            ResidentPass._encode_locals = staticmethod(enc16)
        try:
            for _ in range(2):
                out = tr.train_pass_resident(ds)
        finally:
            if force16:
                ResidentPass._encode_locals = staticmethod(orig)
        return out, tr

    (ra, tr_a), (rb, tr_b) = run(False), run(True)
    assert np.isclose(ra["auc"], rb["auc"], atol=1e-9)
    for a, b in zip(jax.tree.leaves(tr_a.state.params),
                    jax.tree.leaves(tr_b.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_grid_segment_wire_roundtrip_and_selection():
    """GRID segment wire (per-(record,slot) u8 counts): picked exactly
    when keys are (record, slot)-ordered, decodes to the same segments
    as the u18 wire; slot-disordered batches fall back to the SLOT wire
    (u8 slots + u16 counts)."""
    import jax.numpy as jnp

    from paddlebox_tpu.train.device_pass import ResidentPassRunner

    rng = np.random.default_rng(6)
    B, S = 8, 5
    counts = rng.integers(0, 3, size=(2, B, S))
    k_real = counts.sum(axis=(1, 2))
    k_pad = int(k_real.max()) + 8
    segs = np.full((2, k_pad), B * S, np.int32)
    for i in range(2):
        seg_list = np.repeat(np.arange(B * S), counts[i].reshape(-1))
        segs[i, :len(seg_list)] = seg_list
    meta = np.zeros((2, 4), np.int32)
    meta[:, 0] = k_real
    meta[:, 1] = B * S
    enc = ResidentPass._encode_segs_slotwire(segs, meta, B)
    assert len(enc) == 1 and enc[0].dtype == np.uint8
    assert enc[0].shape == (2, B, S)          # ~S B/record, not 1 B/key
    for i in range(2):
        got = np.asarray(ResidentPassRunner._decode_segs(
            (jnp.asarray(enc[0][i]),), jnp.asarray(meta[i]), k_pad=k_pad))
        np.testing.assert_array_equal(got, segs[i])

    # slot-disordered (but record-grouped) → SLOT wire fallback:
    # construct a GUARANTEED inversion (swap record 0's slots S-1, 0)
    bad = segs.copy()
    nk0 = int(meta[0, 0])
    bad[0, :nk0] = np.sort(bad[0, :nk0])
    r0 = bad[0, :nk0] // S
    first_rec = bad[0, :nk0][r0 == r0[0]]
    assert len(first_rec) >= 1
    bad[0, 0] = r0[0] * S + (S - 1)           # slot S-1 first
    bad[0, 1:nk0] = np.sort(bad[0, 1:nk0])    # rest still grouped
    enc2 = ResidentPass._encode_segs_slotwire(bad, meta, B)
    assert len(enc2) == 2
    for i in range(2):
        got = np.asarray(ResidentPassRunner._decode_segs(
            (jnp.asarray(enc2[0][i]), jnp.asarray(enc2[1][i])),
            jnp.asarray(meta[i])))
        np.testing.assert_array_equal(got, bad[i])


# ---- ISSUE 27: the compact wire's steps carry their distinct count ----

def _withhold_count(monkeypatch):
    from paddlebox_tpu.train import device_pass
    monkeypatch.setattr(
        device_pass, "dedup_rows",
        lambda rows, cap: dedup_rows(rows, cap)[:2] + (None,))


def _step_counts(rp, capacity):
    """Distinct rows of each step of a compact-wire pass as the device
    counts them: the step's real keys' rows, and the sentinel entry
    where the key axis has pads."""
    out = []
    for i in range(rp.num_batches):
        nk = int(rp.meta[i, 0])
        rows = rp.uniq[i, :nk]
        assert (rows < capacity).all()
        out.append(len(np.unique(rows)) + (nk < rp.key_capacity))
    return out


@pytest.mark.parametrize("chunk", [512, 1000, 8192],
                         ids=["trips-divide-K", "ragged-K", "one-trip"])
def test_compact_pass_with_count_is_bitwise_the_pass_without(
        criteo_files, monkeypatch, chunk):
    """A whole compact-wire resident pass whose gathers and pushes stop
    at each step's distinct count trains what the same pass trains with
    the count withheld: table, dense parameters and AUC state bit for
    bit; and ``pass.finish`` says how many slots the pushes visited."""
    from paddlebox_tpu.obs import trace
    from paddlebox_tpu.ps import table as tbl
    monkeypatch.setattr(tbl, "PUSH_CHUNK", chunk)
    tr_a, ds = _make_arena(criteo_files)
    tr_b, _ = _make_arena(criteo_files)
    outs = {}
    for name, tr in (("count", tr_a), ("none", tr_b)):
        with monkeypatch.context() as m:
            if name == "none":
                _withhold_count(m)
            for _ in range(2):
                rp = ResidentPass.build_streamed(ds, tr.table)
                assert rp.wire == "compact"
                assert rp.unique_capacity == rp.key_capacity
                out = tr.train_pass_resident(rp)
        fin = [r for r in trace.recent_spans()
               if r.name == "pass.finish"][-1]
        outs[name] = (out, rp, fin)
    assert tr_a.table.rows_digest() == tr_b.table.rows_digest()
    np.testing.assert_array_equal(
        np.asarray(tr_a.state.table.packed).view(np.uint32),
        np.asarray(tr_b.state.table.packed).view(np.uint32))
    for a, b in zip(jax.tree.leaves((tr_a.state.params, tr_a.state.opt_state,
                                     tr_a.state.auc)),
                    jax.tree.leaves((tr_b.state.params, tr_b.state.opt_state,
                                     tr_b.state.auc))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the engagement counter: sum over the steps of ceil(n_i / C) x C
    out, rp, fin = outs["count"]
    k = rp.key_capacity
    c = min(chunk, k)
    full = rp.num_batches * k
    want = min(full, sum(-(-n // c) * c
                         for n in _step_counts(rp, tr_a.table.capacity)))
    assert fin.attrs["push_slots"] == out["push_slots"] == want
    assert fin.attrs["push_slots_full"] == out["push_slots_full"] == full
    assert (want < full) == (c < k)
    # with the count withheld every trip is made
    out, _, fin = outs["none"]
    assert fin.attrs["push_slots"] == fin.attrs["push_slots_full"] == full


def test_dedup_wire_reports_every_slot_pushed(criteo_files):
    """The dedup wire's unique axis is a host-chosen bucket of the
    distinct count: it carries no count, and says so."""
    from paddlebox_tpu.obs import trace
    tr, ds = _make(criteo_files)
    rp = ResidentPass.build_streamed(ds, tr.table)
    assert rp.wire == "dedup"
    out = tr.train_pass_resident(rp)
    fin = [r for r in trace.recent_spans() if r.name == "pass.finish"][-1]
    full = rp.num_batches * rp.unique_capacity
    assert fin.attrs == {"push_slots": full, "push_slots_full": full}
    assert out["push_slots"] == out["push_slots_full"] == full


# ---- the streamed build on two lanes (ISSUE 39) --------------------------
# A columnar feed with a float block encodes and puts that block on a
# thread of its own beside the key half; what it builds is what one thread
# builds in a row (``_front`` then the tail), byte for byte.

LANE_THREAD = "pbox-preload-floats_0"   # the process's one float lane


def _ctr_feed(records: int, bs: int = 32, layout: str = "one-key",
              bad_float: bool = False, seed: int = 39):
    """A columnar CTR dataset of ``records`` (no files): 4 slots, 3 dense
    columns; ``multi-key`` gives slot 2 one to three keys a record, so
    the segments cross the wire."""
    from paddlebox_tpu.data import InMemoryDataset, SlotDef
    from paddlebox_tpu.data.columnar import ColumnarRecords
    s = 4
    rng = np.random.default_rng(seed)
    slots = [SlotDef("label", "float", 1), SlotDef("dense", "float", 3)]
    slots += [SlotDef(f"C{i}", "uint64") for i in range(s)]
    desc = DataFeedDesc(slots=slots, batch_size=bs, label_slot="label",
                        key_bucket_min=bs * s)
    per_slot = np.ones((records, s), np.int64)
    if layout == "multi-key":
        per_slot[:, 2] = rng.integers(1, 4, records)
    key_slot = np.repeat(np.tile(np.arange(s, dtype=np.int32), records),
                         per_slot.reshape(-1))
    keys = (rng.integers(0, 60, key_slot.size) + 1000 * key_slot
            ).astype(np.uint64)
    dense = rng.standard_normal((records, 3)).astype(np.float32)
    if bad_float:
        dense[5, 1] = np.inf    # no u8 range holds it: the bf16 wire
    ds = InMemoryDataset(desc)
    ds.columnar = ColumnarRecords(
        keys=keys, key_slot=key_slot,
        offsets=np.concatenate([[0], np.cumsum(per_slot.sum(axis=1))]
                               ).astype(np.int64),
        dense=dense, label=(rng.random(records) < 0.3).astype(np.float32),
        show=np.ones(records, np.float32),
        clk=np.zeros(records, np.float32))
    return ds


def _lane_table(arena: bool):
    return EmbeddingTable(mf_dim=4, capacity=1 << 12, cfg=SparseSGDConfig(),
                          unique_bucket_min=128,
                          arena_slots=4 if arena else None,
                          arena_chunk_bits=5)


def _serial_build(ds, table, floats_dtype):
    """The one-thread order: the whole front, the two puts, the tail."""
    from paddlebox_tpu.train.device_pass import _FloatHalf
    per_batch, floats, qmeta, trivial, nrec, side = ResidentPass._front(
        ds, floats_dtype)
    half = _FloatHalf()
    half.make(floats.shape[1], lambda: (floats, qmeta))
    return ResidentPass._build_streamed_tail(
        per_batch, half, trivial, nrec, side, table, 4, True, {}, [])


def _lane_is_idle() -> bool:
    """The float lane takes new work at once: no build left it busy."""
    from paddlebox_tpu.train.device_pass import _float_lane
    return _float_lane().submit(lambda: True).result(timeout=10)


@pytest.mark.parametrize("layout", ["one-key", "multi-key"])
@pytest.mark.parametrize("records", [256, 230], ids=["even", "ragged"])
@pytest.mark.parametrize("arena", [True, False], ids=["compact", "dedup"])
@pytest.mark.parametrize("wire", ["q8", "q8-falls-back", "f32"])
def test_two_lane_build_is_the_serial_build(wire, arena, records, layout):
    from paddlebox_tpu.obs import trace
    floats_dtype = np.float32 if wire == "f32" else "q8"
    ds = _ctr_feed(records, layout=layout,
                   bad_float=wire == "q8-falls-back")
    t_a, t_b = _lane_table(arena), _lane_table(arena)
    want = _serial_build(ds, t_a, floats_dtype)
    trace.reset()
    got = ResidentPass.build_streamed(ds, t_b, floats_dtype=floats_dtype)
    # it did fork, and the build joined the lane
    (lane,) = [r for r in trace.recent_spans() if r.name == "build.floats"]
    assert lane.lane == trace.LANE_PRELOAD_FLOATS
    assert _lane_is_idle()
    assert got.build_stats["floats"] > 0
    assert got.build_stats["floats_wait"] >= 0
    # the same pass
    assert got.wire == want.wire == ("compact" if arena else "dedup")
    assert got.floats.dtype == {"q8": np.uint8, "f32": np.float32,
                                "q8-falls-back": jnp.bfloat16}[wire]
    assert (got.qmeta is not None) == (wire == "q8")
    assert (got.segs is None) == (layout == "one-key")
    assert got.floats.shape[0] == -(-records // 32)
    assert (got.num_records, got.chunk_bits) == \
        (want.num_records, want.chunk_bits)
    for name in ("uniq", "gidx", "floats", "qmeta", "meta", "segs",
                 "trained_rows"):
        a, b = getattr(want, name), getattr(got, name)
        assert (a is None) == (b is None), name
        if a is not None:
            assert a.dtype == b.dtype and a.shape == b.shape, name
            np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))
    la, lb = jax.tree.leaves(want.dev), jax.tree.leaves(got.dev)
    assert jax.tree.structure(want.dev) == jax.tree.structure(got.dev)
    for a, b in zip(la, lb):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a).view(np.uint8),
                                      np.asarray(b).view(np.uint8))
    assert got.side.keys() == want.side.keys()
    assert got.side["label"] is want.side["label"]
    # and the same index
    for a, b in zip(t_a.index.items(), t_b.index.items()):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(t_a.slot_host, t_b.slot_host)


def test_sequence_feed_builds_on_one_lane(monkeypatch):
    """A sequence feed has an int32 label block and nothing to encode:
    nothing goes to the float lane, no ``build.floats`` span, no lane
    stages."""
    from paddlebox_tpu.data import InMemoryDataset, SlotDef
    from paddlebox_tpu.data.columnar import ColumnarRecords
    from paddlebox_tpu.obs import trace
    from paddlebox_tpu.train import device_pass
    r, bs = 96, 32
    desc = DataFeedDesc(
        slots=[SlotDef("label", "float", 1), SlotDef("token", "uint64")],
        batch_size=bs, label_slot="label", key_bucket_min=bs, seq_len=16,
        bos_key=0)
    rng = np.random.default_rng(0)
    ds = InMemoryDataset(desc)
    ds.columnar = ColumnarRecords(
        keys=rng.integers(0, 50, r).astype(np.uint64),
        key_slot=np.zeros(r, np.int32),
        offsets=np.arange(r + 1, dtype=np.int64),
        dense=np.zeros((r, 0), np.float32),
        label=rng.integers(0, 50, r).astype(np.int32),
        show=np.ones(r, np.float32), clk=np.zeros(r, np.float32))

    def no_fork(*a, **k):
        raise AssertionError("a sequence feed forked")
    monkeypatch.setattr(device_pass._FloatHalf, "fork", no_fork)
    table = EmbeddingTable(mf_dim=4, capacity=1 << 10, cfg=SparseSGDConfig(),
                           unique_bucket_min=64, arena_slots=1)
    trace.reset()
    rp = ResidentPass.build_streamed(ds, table)
    assert rp.floats.dtype == np.int32 and rp.floats.shape == (3, bs, 1)
    names = {s.name for s in trace.recent_spans()}
    assert "build.front" in names and "build.floats" not in names
    assert set(rp.build_stats) == {"front", "dedup", "pack"}


def test_float_lane_error_reaches_the_wait(monkeypatch):
    """An error inside the float lane is re-raised by the build on the
    preloader's thread, so ``wait()`` holds it as any build failure; the
    worker is gone afterwards and the lane takes the next build's work."""
    import threading
    where = []

    def boom(floats, floats_dtype):
        where.append(threading.current_thread().name)
        raise RuntimeError("boom in the float lane")
    monkeypatch.setattr(ResidentPass, "_encode_floats", staticmethod(boom))
    ds = _ctr_feed(128)
    pre = PassPreloader(iter([ds, ds]), _lane_table(True))
    pre.start_next()
    with pytest.raises(RuntimeError, match="boom in the float lane"):
        pre.wait()
    assert where == [LANE_THREAD]      # raised there, once: no second build
    assert pre.wait() is None
    pre.drain(timeout=10)
    assert not pre._worker.is_alive()
    assert _lane_is_idle()


def test_abort_joins_the_float_lane_and_drains_its_transfers(monkeypatch):
    """A build aborted between stages waits the lane out and then every
    transfer issued, the lane's two among them, before it re-raises."""
    import time
    from paddlebox_tpu.obs import trace
    from paddlebox_tpu.train import device_pass
    real_encode = ResidentPass._encode_floats

    def slow(floats, floats_dtype):
        time.sleep(0.3)          # the key half reaches its poll first
        return real_encode(floats, floats_dtype)
    monkeypatch.setattr(ResidentPass, "_encode_floats", staticmethod(slow))
    drained = []
    real_block = jax.block_until_ready
    monkeypatch.setattr(
        device_pass.jax, "block_until_ready",
        lambda x: drained.append([(a.shape, a.dtype) for a in x])
        or real_block(x))
    ds = _ctr_feed(128)
    table = _lane_table(False)      # the dedup tail polls before it dedups
    trace.reset()
    monkeypatch.setattr(device_pass._PRELOAD_TLS, "abort", lambda: True,
                        raising=False)
    with pytest.raises(device_pass.PreloadBuildAborted):
        ResidentPass.build_streamed(ds, table, floats_dtype="q8")
    # the lane's span is in the ring when the build raises: it ran to its
    # end and was joined, not left at work
    (lane,) = [r for r in trace.recent_spans() if r.name == "build.floats"]
    assert lane.dur_ns >= 0.3e9 and _lane_is_idle()
    assert drained == [[((4, 32, 6), np.dtype(np.uint8)),
                        ((2, 3), np.dtype(np.float32))]]
    assert table.feature_count == 0     # no key was assigned
