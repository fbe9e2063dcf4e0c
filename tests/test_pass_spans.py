"""The pass boundary as the program itself spans it (obs/trace, ISSUE
26): with NO sink attached a resident pass leaves its ``pass.*`` spans
in the in-memory ring, one ``pass_seq`` across lanes; the same spans
land in a ``jax.profiler`` trace; and every ``pbox.*`` scope of the
catalog is in the lowered pass program."""

import glob
import os
import re

import jax
import numpy as np
import optax
import pytest

from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
from paddlebox_tpu.data.criteo import generate_criteo_files
from paddlebox_tpu.models import DeepFM
from paddlebox_tpu.obs import reset_hub, trace
from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
from paddlebox_tpu.train import PassPreloader, ResidentPass, Trainer

#: children of ``pass.train`` in the order the pass runs them
BOUNDARY = ["pass.upload", "pass.dispatch", "pass.device_wait",
            "pass.mark_trained", "pass.finish"]


@pytest.fixture(scope="module")
def criteo_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("criteo_spans")
    return generate_criteo_files(str(d), num_files=1, rows_per_file=600,
                                 vocab_per_slot=30, seed=5)


@pytest.fixture()
def no_sinks():
    hub = reset_hub()
    trace.reset()
    assert not hub.active
    yield hub
    reset_hub()
    trace.reset()


def _make(files, arena: bool = False):
    desc = DataFeedDesc.criteo(batch_size=128)
    desc.key_bucket_min = 4096
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.set_thread(1)
    ds.load_into_memory()
    cfg = SparseSGDConfig(mf_create_thresholds=0.0, mf_initial_range=0.0)
    table = EmbeddingTable(
        mf_dim=4, capacity=1 << 13, cfg=cfg, unique_bucket_min=4096,
        arena_slots=len(desc.sparse_slots) if arena else None,
        arena_chunk_bits=6)
    tr = Trainer(DeepFM(hidden=(16, 8)), table, desc, tx=optax.adam(1e-2),
                 seed=3)
    return tr, ds


def _names(lowered_text: str, scope: str) -> bool:
    """Whether a component of some op's name stack is ``scope``, bare
    or inside ``jvp(...)`` / ``transpose(jvp(...))``."""
    return re.search(r"[/(]" + re.escape(scope) + r"[/)]",
                     lowered_text) is not None


def _by_name(spans):
    out = {}
    for r in spans:
        out.setdefault(r.name, []).append(r)
    return out


def test_resident_pass_leaves_its_boundary_in_the_ring(criteo_files,
                                                       no_sinks):
    tr, ds = _make(criteo_files)
    pre = PassPreloader(iter([ds, ds]), tr.table, depth=1)
    pre.start_next()
    seqs = []
    for _ in range(2):
        rp = pre.wait()
        seqs.append(rp.pass_seq)
        tr.train_pass_resident(rp)
    pre.drain()
    assert not no_sinks.active            # nothing attached, all along
    assert seqs[0] is not None and seqs[1] == seqs[0] + 1
    spans = trace.recent_spans()
    for seq in seqs:
        mine = _by_name([r for r in spans if r.pass_seq == seq])
        (train,) = mine["pass.train"]
        assert train.lane == trace.LANE_MAIN and train.parent_id == 0
        assert train.attrs["records"] == 600
        assert train.attrs["batches"] == rp.num_batches
        (consume,) = mine["pass.consume"]
        assert consume.parent_id == train.span_id
        kids = [mine[n][0] for n in BOUNDARY]
        # in that order, one after the other, inside pass.train
        for a, b in zip(kids, kids[1:]):
            assert a.t0_ns + a.dur_ns <= b.t0_ns
        assert kids[0].t0_ns >= train.t0_ns
        assert kids[-1].t0_ns + kids[-1].dur_ns <= \
            train.t0_ns + train.dur_ns
        for k in kids[:3]:     # upload, dispatch, device_wait
            assert k.parent_id == consume.span_id
        for k in kids[3:]:     # mark_trained, finish
            assert k.parent_id == train.span_id
        assert mine["pass.upload"][0].attrs == {"staged": True}
        assert mine["pass.dispatch"][0].attrs == {"chunks": 1}
        # the rows it flags: each batch's distinct rows, no pad
        assert mine["pass.mark_trained"][0].attrs["rows"] == \
            int(rp.meta[:, 2].sum())
        # the other lanes carry the same identifier
        (build,) = mine["pass.build"]
        (wait,) = mine["pass.wait"]
        assert build.lane == trace.LANE_PRELOAD
        assert wait.lane == trace.LANE_MAIN and "depth" in wait.attrs
        # the consume span's link is the build span (the flow arrow)
        assert consume.link_from == build.span_id
        for child in ("build.front", "build.dedup", "build.pack",
                      "build.upload"):
            (c,) = mine[child]
            assert c.parent_id == build.span_id
            assert c.lane == trace.LANE_PRELOAD
        assert mine["build.front"][0].attrs["keys"] > 0


@pytest.mark.parametrize("arena", [False, True],
                         ids=["dedup_wire", "compact_wire"])
def test_float_lane_rides_beside_the_build(criteo_files, no_sinks, arena):
    """A CTR pass's float block is encoded on a lane of its own (ISSUE
    39): ``build.floats`` lies on ``preload.floats`` inside the build's
    extent, carries the pass's ``pass_seq`` and links to ``pass.build``,
    which says how long its key half waited where the two meet."""
    tr, ds = _make(criteo_files, arena=arena)
    pre = PassPreloader(iter([ds, ds]), tr.table, floats_dtype="q8")
    pre.start_next()
    passes = [pre.wait(), pre.wait()]
    pre.drain()
    spans = trace.recent_spans()
    for rp in passes:
        mine = _by_name([r for r in spans if r.pass_seq == rp.pass_seq])
        (build,), (lane,) = mine["pass.build"], mine["build.floats"]
        assert lane.lane == trace.LANE_PRELOAD_FLOATS
        assert lane.parent_id == 0 and lane.link_from == build.span_id
        assert build.t0_ns <= lane.t0_ns
        assert lane.t0_ns + lane.dur_ns <= build.t0_ns + build.dur_ns
        # the key half's stages stay the build's children on the
        # worker's lane
        for child in ("build.front", "build.dedup", "build.pack"):
            assert mine[child][0].parent_id == build.span_id
            assert mine[child][0].lane == trace.LANE_PRELOAD
        assert build.attrs["floats_wait_ms"] == pytest.approx(
            1e3 * rp.build_stats["floats_wait"])
        assert build.attrs["floats_wait_ms"] >= 0
        # the lane's own seconds: its span, less the span's bookkeeping
        assert 0 < rp.build_stats["floats"] <= lane.dur_ns / 1e9
    assert pre.build_stage_sec["floats"] == pytest.approx(
        sum(rp.build_stats["floats"] for rp in passes))


@pytest.mark.parametrize("arena", [False, True],
                         ids=["dedup_wire", "compact_wire"])
def test_mark_trained_flags_the_rows_the_pass_trained(
        criteo_files, no_sinks, tmp_path, arena):
    """``mark_trained_rows`` flags every row of the pass that is no pad
    (the rule before ISSUE 34, written out), from the build's distinct
    rows on the compact wire and from ``uniq`` on the dedup wire; the
    spans count what was deduplicated and what was flagged, and the next
    delta save exports the pass's keys."""
    tr, ds = _make(criteo_files, arena=arena)
    table = tr.table
    rp = ResidentPass.build_streamed(ds, table)
    assert rp.wire == ("compact" if arena else "dedup")
    assert (rp.trained_rows is not None) == arena
    assert not table._touched.any()      # a built pass has not trained
    tr.train_pass_resident(rp)
    rows = rp.uniq.ravel()
    want = np.zeros_like(table._touched)
    want[rows[rows <= table.capacity]] = True
    np.testing.assert_array_equal(table._touched, want)
    assert not table._touched[table.capacity]    # pads never flagged
    keys = np.unique(ds.columnar.keys)
    assert want.sum() == len(keys)
    by = _by_name(trace.recent_spans())
    (mark,), (dedup,) = by["pass.mark_trained"], by["build.dedup"]
    assert dedup.attrs["keys"] == ds.columnar.keys.size
    if arena:    # one walk of the pass's distinct keys, one scatter
        assert dedup.attrs["distinct"] == len(keys)
        assert mark.attrs["rows"] == len(keys)
    else:        # a batch's distinct rows, batch by batch
        assert "distinct" not in dedup.attrs
        assert mark.attrs["rows"] == int(rp.meta[:, 2].sum())
    path = str(tmp_path / "delta.npz")
    assert table.save_delta(path) == len(keys)
    np.testing.assert_array_equal(np.sort(np.load(path)["keys"]), keys)
    assert not table._touched.any()


def test_inline_build_gets_one_pass_seq(criteo_files, no_sinks):
    """A Dataset handed to ``train_pass_resident`` is built inside
    ``pass.train``; the pass and all its spans carry one new id."""
    tr, ds = _make(criteo_files)
    tr.train_pass_resident(ds)
    spans = trace.recent_spans()
    (train,) = [r for r in spans if r.name == "pass.train"]
    assert train.pass_seq is not None
    assert {r.pass_seq for r in spans} == {train.pass_seq}
    assert [r.name for r in spans if r.name in BOUNDARY] == BOUNDARY
    # and a pass built by hand draws its own
    rp = ResidentPass.build(ds, tr.table)
    assert rp.pass_seq == train.pass_seq + 1


def test_sharded_resident_pass_uses_the_same_names(criteo_files,
                                                   no_sinks):
    """The mesh trainer's resident pass, fed by the preloader through
    ``build_fn``: the same span names at the matching places, one
    ``pass_seq`` across lanes, and the exchange scopes in its program."""
    from paddlebox_tpu.config import flags_scope
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.ps.sharded import ShardedEmbeddingTable
    from paddlebox_tpu.train.sharded import ShardedTrainer
    n = 2
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(criteo_files)
    ds.load_into_memory()
    table = ShardedEmbeddingTable(
        n, mf_dim=2, capacity_per_shard=2048,
        cfg=SparseSGDConfig(mf_create_thresholds=0.0,
                            mf_initial_range=0.0),
        req_bucket_min=128, serve_bucket_min=128)
    with flags_scope(log_period_steps=10000):
        tr = ShardedTrainer(DeepFM(hidden=(16,)), table, desc,
                            make_mesh(n), tx=optax.adam(1e-3))
        pre = PassPreloader(iter([ds]), build_fn=tr.build_resident_pass)
        pre.start_next()
        rp = pre.wait()
        tr.train_pass_resident(rp)
        pre.drain()
    mine = _by_name([r for r in trace.recent_spans()
                     if r.pass_seq == rp.pass_seq])
    assert rp.pass_seq is not None
    (train,) = mine["pass.train"]
    kids = [mine[k][0] for k in BOUNDARY]
    for a, b in zip(kids, kids[1:]):
        assert a.t0_ns + a.dur_ns <= b.t0_ns
    assert mine["pass.upload"][0].attrs == {"staged": True}
    assert mine["pass.build"][0].lane == trace.LANE_PRELOAD
    assert mine["build.upload"][0].parent_id == \
        mine["pass.build"][0].span_id
    assert mine["pass.consume"][0].link_from == \
        mine["pass.build"][0].span_id
    assert mine["pass.wait"][0].lane == trace.LANE_MAIN
    # the step's program: the single-chip names plus the two exchanges
    runner = tr.step_fn._resident_runner(
        rp.num_batches, tuple(sorted(rp.fmt.items())) if rp.fmt else None,
        rp.capacity or 0, sections=rp.sections)
    text = runner._factory(rp.dev).lower(
        tr.state, rp.dev, jax.numpy.asarray(0, jax.numpy.int32),
        tr._rng).as_text(debug_info=True)
    for s in trace.SHARDED_SCOPES + (trace.SCOPE_PULL, trace.SCOPE_PUSH,
                                     trace.SCOPE_DENSE_OPT):
        assert _names(text, s), s


def test_spans_land_in_the_profilers_trace(criteo_files, no_sinks,
                                           tmp_path):
    """Inside a ``jax.profiler`` session the same names are in the
    xplane's host plane, with lane and pass_seq as stats — one file, one
    clock with the device's ops."""
    tr, ds = _make(criteo_files)
    rp = ResidentPass.build(ds, tr.table)
    tr.train_pass_resident(rp)        # compile outside the session
    rp = ResidentPass.build(ds, tr.table)
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr.train_pass_resident(rp)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    seen = {}
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("pass."):
                    seen[ev.name] = dict(ev.stats)
    assert set(seen) >= set(BOUNDARY) | {"pass.train", "pass.consume"}
    for name, stats in seen.items():
        assert stats["lane"] == trace.LANE_MAIN, name
        assert stats["pass_seq"] == rp.pass_seq, name


@pytest.mark.parametrize("arena", [False, True],
                         ids=["dedup-wire", "compact-wire"])
def test_pass_program_carries_every_scope(criteo_files, arena):
    """The lowered text of the resident pass program names every
    ``pbox.*`` scope of the catalog (``pbox.dedup`` exists on the compact
    wire only: the dedup wire dedups on the host)."""
    from paddlebox_tpu.train.device_pass import ResidentPassRunner
    tr, ds = _make(criteo_files, arena=arena)
    if arena:
        rp = ResidentPass.build_streamed(ds, tr.table)
        assert rp.wire == "compact"
    else:
        rp = ResidentPass.build(ds, tr.table)
        rp.upload()
    runner = ResidentPassRunner(
        tr.step_fn, tr.table.capacity, rp.segs is None, wire=rp.wire,
        num_slots=tr.step_fn.num_slots, chunk_bits=rp.chunk_bits)
    lowered = runner._run(rp.num_batches).lower(
        tr.state, *rp.dev, jax.numpy.asarray(0, jax.numpy.int32), tr._rng)
    text = lowered.as_text(debug_info=True)
    want = set(trace.STEP_SCOPES)
    if not arena:
        want.discard(trace.SCOPE_DEDUP)
    missing = {s for s in want if not _names(text, s)}
    assert not missing, missing
    # the backward of a differentiated scope is named for the reducer
    assert f"transpose(jvp({trace.SCOPE_POOL_CVM}))" in text
    assert f"transpose(jvp({trace.SCOPE_DENSE}))" in text
