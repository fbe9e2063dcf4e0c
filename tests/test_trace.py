"""Causal pass tracing (obs/trace): span nesting/lanes, the Chrome
lane sink's tid rows + flow arrows, the critical-path block math, and
the cross-thread span contract over a REAL depth-2 tiered pipeline job
(ISSUE 10 acceptance surface)."""

import importlib.util
import json
import os
import threading

import jax
import numpy as np
import optax
import pytest

from paddlebox_tpu.config import flags_scope
from paddlebox_tpu.obs import (ChromeLaneTraceSink, JsonlSink, MemorySink,
                               get_hub, reset_hub)
from paddlebox_tpu.obs import trace
from paddlebox_tpu.utils.profiler import ChromeTraceWriter

N = 8


@pytest.fixture()
def fresh_hub():
    hub = reset_hub()
    trace.reset()
    yield hub
    reset_hub()
    trace.reset()


# ---- span layer --------------------------------------------------------
def test_span_inert_without_sinks(fresh_hub):
    """"Off" means no sink: the ring gets the span, no sink is called
    and no counter moves."""
    assert not trace.tracing_active()
    with trace.span("x", k=1) as h:
        assert h.span_id > 0 and h.lane == trace.LANE_MAIN
    (rec,) = trace.recent_spans()
    assert (rec.name, rec.span_id, rec.attrs) == ("x", h.span_id, {"k": 1})
    assert rec.dur_ns >= 0 and rec.t0_ns > 0
    assert not fresh_hub.active
    assert fresh_hub.snapshot() == {}  # no instrument was created


def test_ring_is_bounded_and_reset_clears_it(fresh_hub):
    for i in range(trace.RING_SPANS + 10):
        with trace.span("tick", i=i):
            pass
    spans = trace.recent_spans()
    assert len(spans) == trace.RING_SPANS
    assert spans[0].attrs == {"i": 10}           # the oldest fell off
    assert spans[-1].attrs == {"i": trace.RING_SPANS + 9}
    assert spans is not trace.recent_spans()     # a copy each time
    trace.reset()
    assert trace.recent_spans() == []


def test_parent_pass_seq_and_link_survive_without_sinks(fresh_hub):
    """Identity is the ring's own: ids, nesting per thread, the pass a
    child inherits from its parent, a pass set late on the handle, and
    the cross-thread link, all with nothing attached."""
    box = {}

    def producer():
        trace.set_lane(trace.LANE_PRELOAD)
        with trace.span("pass.build", pass_seq=7) as hb:
            with trace.span("build.front"):
                pass
        box["sid"] = hb.span_id

    t = threading.Thread(target=producer)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    with trace.span("pass.wait") as hw:
        hw.pass_seq = 7                  # known only once the pass pops
    with trace.span("pass.train", pass_seq=7):
        with trace.span("pass.consume", link_from=box["sid"]):
            pass
    with trace.span("elsewhere"):
        pass
    by = {r.name: r for r in trace.recent_spans()}
    assert by["build.front"].parent_id == by["pass.build"].span_id
    assert by["build.front"].lane == trace.LANE_PRELOAD
    assert by["pass.consume"].parent_id == by["pass.train"].span_id
    assert by["pass.consume"].link_from == by["pass.build"].span_id
    assert by["pass.train"].parent_id == 0
    for name in ("pass.build", "build.front", "pass.wait", "pass.train",
                 "pass.consume"):
        assert by[name].pass_seq == 7, name
    assert by["elsewhere"].pass_seq is None
    assert len({r.span_id for r in by.values()}) == len(by)
    a, b = trace.next_pass_seq(), trace.next_pass_seq()
    assert b == a + 1


def test_span_nesting_and_parent_ids(fresh_hub):
    w = ChromeTraceWriter()
    fresh_hub.add_sink(ChromeLaneTraceSink(w))
    assert trace.tracing_active()
    with trace.span("outer") as ho:
        assert trace.current_span_id() == ho.span_id
        with trace.span("inner") as hi:
            assert hi.span_id != ho.span_id
            assert trace.current_span_id() == hi.span_id
        assert trace.current_span_id() == ho.span_id
    assert trace.current_span_id() == 0
    evs = {e["name"]: e for e in w._events if e["ph"] == "X"}
    assert evs["inner"]["args"]["parent_id"] == ho.span_id
    assert "parent_id" not in evs["outer"]["args"]
    # only the TOP-LEVEL span books lane-busy seconds (children are
    # contained in the parent's wall)
    busy = fresh_hub.counter("pbox_lane_busy_seconds_total", "x")
    assert busy.value(lane="main") > 0


def test_lane_scope_and_set_lane(fresh_hub):
    fresh_hub.add_sink(ChromeLaneTraceSink(ChromeTraceWriter()))
    assert trace.current_lane() == trace.LANE_MAIN
    with trace.lane_scope("ssd.compact"):
        assert trace.current_lane() == "ssd.compact"
        with trace.span("inside") as h:
            assert h.lane == "ssd.compact"
    assert trace.current_lane() == trace.LANE_MAIN
    seen = {}

    def worker():
        seen["default"] = trace.current_lane()
        trace.set_lane("preload.worker")
        seen["set"] = trace.current_lane()

    t = threading.Thread(target=worker, name="pbox-t")
    t.start()
    t.join()
    assert seen["default"] == "pbox-t"      # thread name fallback
    assert seen["set"] == "preload.worker"


def test_chrome_lane_sink_rows_and_flow(fresh_hub):
    """Per-lane tid rows with thread_name metadata; a link_from span
    draws a flow arrow from source end to destination start."""
    w = ChromeTraceWriter()
    fresh_hub.add_sink(ChromeLaneTraceSink(w))
    with trace.span("pass.build", lane="preload.worker") as hb:
        pass
    with trace.span("pass.consume", lane="main",
                    link_from=hb.span_id):
        pass
    metas = [e for e in w._events
             if e["ph"] == "M" and e["name"] == "thread_name"]
    names = {e["args"]["name"]: e["tid"] for e in metas}
    assert set(names) == {"preload.worker", "main"}
    assert names["preload.worker"] != names["main"]
    flows = [e for e in w._events if e["ph"] in ("s", "f")]
    assert len(flows) == 2
    start = next(e for e in flows if e["ph"] == "s")
    end = next(e for e in flows if e["ph"] == "f")
    assert start["id"] == end["id"] == hb.span_id
    assert start["tid"] == names["preload.worker"]
    assert end["tid"] == names["main"]
    assert end.get("bp") == "e"
    assert start["ts"] <= end["ts"]
    # the trace JSON round-trips
    spans = [e for e in w._events if e["ph"] == "X"]
    assert {e["name"] for e in spans} == {"pass.build", "pass.consume"}
    json.dumps(w._events)


def test_cross_thread_span_links(fresh_hub):
    """The producer stashes its span id; a consumer on another thread
    links — the real PassPreloader hand-off shape."""
    w = ChromeTraceWriter()
    fresh_hub.add_sink(ChromeLaneTraceSink(w))
    box = {}

    def producer():
        trace.set_lane("preload.worker")
        with trace.span("pass.build") as h:
            pass
        box["sid"] = h.span_id

    t = threading.Thread(target=producer)
    t.start()
    t.join()
    with trace.span("pass.consume", link_from=box["sid"]):
        pass
    flows = [e for e in w._events if e["ph"] in ("s", "f")]
    assert {e["ph"] for e in flows} == {"s", "f"}
    assert all(e["id"] == box["sid"] for e in flows)


# ---- critical-path math ------------------------------------------------
def test_critical_path_block_sums_and_verdicts(fresh_hub):
    # device-bound: train dominates
    blk = trace.critical_path_block(1.0, {"build_wait": 0.2,
                                          "stage_wait": 0.1})
    assert blk["bottleneck"] == "device"
    assert blk["wall_sec"] == pytest.approx(1.3)
    assert blk["train_sec"] == pytest.approx(1.0)
    assert blk["stall_sec"] == pytest.approx(0.3)
    # build-bound: the largest stall beats train
    blk = trace.critical_path_block(0.5, {"build_wait": 0.74,
                                          "fence_wait": 0.1})
    assert blk["bottleneck"] == "build_wait"
    assert blk["stall_sec"] == pytest.approx(0.74)
    assert blk["wall_sec"] == pytest.approx(0.5 + 0.74 + 0.1)
    # no parts at all → trivially device-bound, wall == train
    blk = trace.critical_path_block(2.0, {})
    assert blk["bottleneck"] == "device"
    assert blk["wall_sec"] == pytest.approx(2.0)
    # zero/negative parts are dropped
    blk = trace.critical_path_block(1.0, {"stage_wait": 0.0,
                                          "end_submit": -1.0})
    assert blk["wall_sec"] == pytest.approx(1.0)


def test_note_and_consume_pass_parts(fresh_hub):
    fresh_hub.add_sink(MemorySink())
    trace.note_pass_part("build_wait", 0.5)
    trace.note_pass_part("build_wait", 0.25)
    trace.note_pass_part("stage_wait", 0.1)
    trace.note_pass_part("fence_wait", 0.0)   # dropped
    parts = trace.consume_pass_parts()
    assert parts == {"build_wait": 0.75, "stage_wait": 0.1}
    assert trace.consume_pass_parts() == {}   # consumed exactly once


def test_parts_inert_without_sinks(fresh_hub):
    trace.note_pass_part("build_wait", 1.0)
    assert trace.consume_pass_parts() == {}


def test_pass_event_carries_critical_path(fresh_hub):
    from paddlebox_tpu.obs.hub import emit_pass_event
    sink = MemorySink()
    fresh_hub.add_sink(sink)
    trace.note_pass_part("build_wait", 0.74)
    emit_pass_event("train_pass_resident",
                    {"batches": 4, "elapsed_sec": 0.5})
    ev = next(e for e in sink.events if e["event"] == "pass")
    cp = ev["critical_path"]
    assert cp["bottleneck"] == "build_wait"
    assert cp["wall_sec"] == pytest.approx(1.24)
    assert fresh_hub.counter("pbox_pass_bottleneck_total", "x").value(
        stage="build_wait") == 1


# ---- the real thing: depth-2 tiered pipeline --------------------------
@pytest.fixture(scope="module")
def mesh():
    from paddlebox_tpu.parallel import make_mesh
    assert len(jax.devices()) >= N
    return make_mesh(N)


def _mk_ds(tmp_path, seed):
    from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
    from paddlebox_tpu.data.criteo import generate_criteo_files
    files = generate_criteo_files(str(tmp_path / f"tr{seed}"),
                                  num_files=1, rows_per_file=600,
                                  vocab_per_slot=50, seed=seed)
    desc = DataFeedDesc.criteo(batch_size=32)
    desc.key_bucket_min = 1024
    ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
    ds.set_filelist(files)
    ds.load_into_memory()
    return ds, desc


def test_depth2_tiered_job_emits_linked_lane_spans(mesh, tmp_path):
    """ISSUE 10 satellite: a depth-2 tiered job emits linked
    build/stage/consume/epilogue spans with correct lane labels, and
    each pass event's critical-path block sums (within tolerance) to
    the measured pass wall."""
    import time as _time

    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.ps import SparseSGDConfig
    from paddlebox_tpu.ps.tiered import TieredShardedEmbeddingTable
    from paddlebox_tpu.train.sharded import ShardedTrainer
    hub = reset_hub()
    trace.reset()
    writer = ChromeTraceWriter()
    sink = ChromeLaneTraceSink(writer)
    mem = MemorySink()
    hub.add_sink(sink)
    hub.add_sink(mem)
    try:
        built = [_mk_ds(tmp_path, s) for s in range(2)]
        datasets = [built[0][0], built[1][0], built[0][0]]
        desc = built[0][1]
        cfg = SparseSGDConfig(mf_create_thresholds=0.0,
                              mf_initial_range=0.0)
        table = TieredShardedEmbeddingTable(
            N, mf_dim=4, capacity_per_shard=512, cfg=cfg,
            req_bucket_min=256, serve_bucket_min=256,
            ssd_dir=str(tmp_path / "ssd"))
        with flags_scope(log_period_steps=10000):
            tr = ShardedTrainer(DeepFM(hidden=(16, 16)), table, desc,
                                mesh, tx=optax.adam(2e-3))
        pipe = tr.tiered_pass_pipeline(iter(datasets), depth=2)
        pipe.start_next()
        walls = []
        while True:
            t0 = _time.perf_counter()
            rp = pipe.wait()
            if rp is None:
                break
            pipe.begin_pass()
            pipe.start_next()
            tr.train_pass_resident(rp)
            pipe.end_pass()
            walls.append(_time.perf_counter() - t0)
        pipe.drain()
        table.fence()
    finally:
        reset_hub()
        trace.reset()

    spans = [e for e in writer._events if e["ph"] == "X"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    # the four pipeline span kinds, one per pass
    for name in ("pass.build", "pass.stage", "pass.consume",
                 "pass.begin", "pass.end_submit",
                 "endpass.writeback"):
        assert len(by_name.get(name, [])) >= 3, \
            f"missing spans for {name}: {sorted(by_name)}"
    # lane labels are correct per span kind
    metas = {e["tid"]: e["args"]["name"] for e in writer._events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    lane_of = lambda e: metas[e["tid"]]
    assert all(lane_of(e) == "preload.worker"
               for e in by_name["pass.build"])
    assert all(lane_of(e) == "preload.worker"
               for e in by_name["pass.stage"])
    assert all(lane_of(e) == "main" for e in by_name["pass.consume"])
    assert all(lane_of(e) == "epilogue.lane"
               for e in by_name["endpass.writeback"])
    # stage is a CHILD of its build (same worker, nested)
    build_ids = {e["args"]["span_id"] for e in by_name["pass.build"]}
    assert all(e["args"].get("parent_id") in build_ids
               for e in by_name["pass.stage"])
    # the ssd maintenance lane rode the epilogue jobs
    assert any(lane_of(e) == "ssd.compact"
               for e in by_name.get("ssd.maintain", [])), \
        "ssd.maintain spans missing or mislabeled"
    # ≥4 distinct lanes in one trace
    assert {"main", "preload.worker", "epilogue.lane",
            "ssd.compact"} <= set(metas.values())
    # flow links: every consume links back to a build span id
    flows = [e for e in writer._events if e["ph"] in ("s", "f")]
    consume_links = {e["id"] for e in flows}
    assert build_ids & consume_links, \
        "no build→consume flow arrows recorded"
    # per-pass critical_path blocks sum (within tolerance) to the
    # measured pass wall: sum over passes to absorb the end_submit /
    # fence parts booking into the NEXT pass's block
    cps = [e["critical_path"] for e in mem.events
           if e.get("event") == "pass" and "critical_path" in e]
    assert len(cps) == len(walls) == 3
    block_total = sum(cp["wall_sec"] for cp in cps)
    wall_total = sum(walls)
    assert block_total <= wall_total * 1.05 + 0.05
    assert block_total >= wall_total * 0.5 - 0.05, \
        (block_total, wall_total, cps)
    for cp in cps:
        parts = sum(v for k, v in cp.items()
                    if k.endswith("_sec") and k not in ("wall_sec",
                                                        "train_sec",
                                                        "stall_sec"))
        assert cp["wall_sec"] == pytest.approx(
            cp["train_sec"] + parts, rel=1e-6, abs=1e-6)
        assert cp["bottleneck"] in ("device", "build_wait",
                                    "stage_wait", "fence_wait",
                                    "ssd_promote", "evict_emergency",
                                    "evict_scatter", "end_submit")


def _telemetry_report():
    spec = importlib.util.spec_from_file_location(
        "telemetry_report",
        os.path.join(os.path.dirname(__file__), os.pardir, "scripts",
                     "telemetry_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_jsonl_report_renders_bottleneck_column(tmp_path, fresh_hub):
    """telemetry_report renders the per-pass bottleneck column and the
    whole-run critical-path summary from synthetic events."""
    mod = _telemetry_report()
    events = []
    for i in range(8):
        # pass 2: the build stall (0.74s) exceeds its train (0.5s) —
        # the one build-bound pass of the run
        train = 0.5 if i == 1 else 1.0
        cp = (trace.critical_path_block(train, {"build_wait": 0.74})
              if i == 1 else
              trace.critical_path_block(train, {"build_wait": 0.01}))
        events.append({"event": "pass", "ts": i, "seq": i, "proc": 0,
                       "kind": "train_pass_resident", "pass_seq": i + 1,
                       "batches": 4, "examples": 100,
                       "elapsed_sec": train,
                       "examples_per_sec": 100.0 / train,
                       "critical_path": cp})
    report = mod.render_report(events)
    assert "bottleneck" in report
    assert "7/8 passes device-bound" in report
    assert "pass 2 build_wait-bound: +0.740s" in report


def test_critical_path_smoke_end_to_end():
    """Deterministic synthetic pass parts → block math → report
    verdicts, no trainers involved."""
    tr = _telemetry_report()
    # 4 device-bound passes, one fence-bound straggler
    events = []
    specs = [(1.0, {"build_wait": 0.05}), (1.0, {}),
             (0.8, {"fence_wait": 1.2}), (1.0, {"stage_wait": 0.02}),
             (1.0, {"evict_emergency": 0.4})]
    for i, (train, parts) in enumerate(specs):
        blk = trace.critical_path_block(train, parts)
        assert blk["wall_sec"] == pytest.approx(
            train + sum(parts.values()))
        events.append({"event": "pass", "ts": i, "seq": i, "proc": 0,
                       "kind": "train_pass_resident",
                       "pass_seq": i + 1, "batches": 1, "examples": 10,
                       "elapsed_sec": train,
                       "examples_per_sec": 10 / train,
                       "critical_path": blk})
    line = tr.critical_path_summary(events)
    assert "4/5 passes device-bound" in line
    assert "pass 3 fence_wait-bound: +1.200s" in line
    report = tr.render_report(events)
    assert "bottleneck" in report
    assert "fence_wait +1.200s" in report
