"""Observability layer (paddlebox_tpu/obs): instrument semantics, JSONL
event round-trip, Prometheus exposition + HTTP endpoint, channel gauge
wiring under producer/consumer load, straggler watchdog detection, and
the trainer pass-event integration (ISSUE 1 acceptance surface)."""

import importlib.util
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from paddlebox_tpu.obs import (DirHeartbeatStore, JsonlSink,
                               LocalHeartbeatStore, MemorySink,
                               StragglerTimeout, StragglerWatchdog,
                               TelemetryHub, get_hub, reset_hub)
from paddlebox_tpu.obs.hub import emit_pass_event
from paddlebox_tpu.obs.instruments import Counter, Gauge, Histogram
from paddlebox_tpu.utils.channel import (Channel, channel_stats_snapshot,
                                         reset_channel_stats)


@pytest.fixture()
def fresh_hub():
    hub = reset_hub()
    yield hub
    reset_hub()


# ---- instruments -------------------------------------------------------
def test_counter_semantics():
    c = Counter("req_total")
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    c.inc(1, shard=0)
    c.inc(2, shard=0)
    c.inc(5, shard=1)
    assert c.value(shard=0) == 3 and c.value(shard=1) == 5
    assert c.value() == 3.5  # labelless series is independent
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_semantics():
    g = Gauge("depth")
    g.set(7)
    assert g.value() == 7
    g.set(3)
    assert g.value() == 3
    g.set_max(1)   # watermark keeps the max
    assert g.value() == 3
    g.set_max(10)
    assert g.value() == 10
    g.inc(2, host=1)
    g.inc(3, host=1)
    assert g.value(host=1) == 5


def test_histogram_semantics():
    h = Histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 5
    assert s["sum"] == pytest.approx(56.05)
    # cumulative le semantics; 50.0 only lands in +Inf (== count)
    assert s["buckets"][0.1] == 1
    assert s["buckets"][1.0] == 3
    assert s["buckets"][10.0] == 4


def test_histogram_quantiles():
    """Bucket-interpolated p50/p90/p99 (ISSUE 15 satellite): the
    serving-latency SLO surface. Linear interpolation inside the
    target bucket; ranks past the last finite bucket clamp to it."""
    h = Histogram("lat", buckets=(0.001, 0.01, 0.1, 1.0))
    for _ in range(50):
        h.observe(0.0005)
    for _ in range(40):
        h.observe(0.005)
    for _ in range(10):
        h.observe(0.05)
    # rank 50 lands exactly at the first bucket's upper bound
    assert h.quantile(0.5) == pytest.approx(0.001)
    # rank 90 at the second bucket's bound; rank 99 interpolates 9/10
    # into the third bucket [0.01, 0.1)
    assert h.quantile(0.9) == pytest.approx(0.01)
    assert h.quantile(0.99) == pytest.approx(0.01 + 0.09 * 0.9)
    # labeled series are independent (one sample in [0.1, 1.0):
    # rank q interpolates q of the way through its bucket); empty
    # series read 0
    h.observe(0.5, op="predict")
    assert h.quantile(0.5, op="predict") == pytest.approx(0.55)
    assert h.quantile(0.99, op="predict") == pytest.approx(0.991)
    assert h.quantile(0.5, op="nope") == 0.0
    with pytest.raises(ValueError):
        h.quantile(0.0)
    with pytest.raises(ValueError):
        h.quantile(1.5)
    # +Inf overflow clamps to the last finite bucket
    h2 = Histogram("of", buckets=(0.1, 1.0))
    for _ in range(10):
        h2.observe(50.0)
    assert h2.quantile(0.99) == pytest.approx(1.0)


def test_histogram_prom_quantile_lines():
    """The text exposition carries scrapeable p50/p90/p99 quantile
    lines per series alongside the buckets and _count/_sum."""
    from paddlebox_tpu.obs.instruments import iter_prom_lines
    h = Histogram("pbox_lat_seconds", "latency",
                  buckets=(0.001, 0.01, 0.1))
    for _ in range(99):
        h.observe(0.005, op="lookup")
    h.observe(0.05, op="lookup")
    text = "\n".join(iter_prom_lines(h))
    assert "# TYPE pbox_lat_seconds histogram" in text
    assert 'pbox_lat_seconds_bucket{op="lookup",le="0.01"} 99' in text
    assert 'pbox_lat_seconds_bucket{op="lookup",le="+Inf"} 100' in text
    # quantiles live in a SIBLING declared gauge family — bare-name
    # quantile samples inside a histogram family are invalid exposition
    assert "# TYPE pbox_lat_seconds_quantile gauge" in text
    q50 = h.quantile(0.5, op="lookup")
    q99 = h.quantile(0.99, op="lookup")
    assert (f'pbox_lat_seconds_quantile{{op="lookup",quantile="0.5"}} '
            f"{q50:g}") in text
    assert (f'pbox_lat_seconds_quantile{{op="lookup",quantile="0.99"}} '
            f"{q99:g}") in text
    assert 'pbox_lat_seconds_count{op="lookup"} 100' in text
    assert "pbox_lat_seconds_sum" in text
    # the quantile family declaration comes after the histogram block
    assert text.index("# TYPE pbox_lat_seconds_quantile gauge") \
        > text.index("pbox_lat_seconds_count")


def test_instrument_kind_collision(fresh_hub):
    fresh_hub.counter("x_total")
    with pytest.raises(TypeError):
        fresh_hub.gauge("x_total")
    # idempotent get-or-create returns the same instance
    assert fresh_hub.counter("x_total") is fresh_hub.counter("x_total")


# ---- sinks + events ----------------------------------------------------
def test_jsonl_sink_roundtrip(tmp_path, fresh_hub):
    path = str(tmp_path / "run.jsonl")
    fresh_hub.add_sink(JsonlSink(path))
    assert fresh_hub.active
    for i in range(5):
        fresh_hub.emit("tick", i=i, note="x" * i)
    fresh_hub.close_sinks()
    assert not fresh_hub.active
    lines = open(path).read().splitlines()
    assert len(lines) == 5
    evs = [json.loads(l) for l in lines]  # every line is valid JSON
    assert [e["i"] for e in evs] == list(range(5))
    ts = [e["ts"] for e in evs]
    seqs = [e["seq"] for e in evs]
    assert ts == sorted(ts), "timestamps must be monotone"
    assert seqs == sorted(seqs) and len(set(seqs)) == 5
    assert all(e["event"] == "tick" and "run" in e for e in evs)


def test_no_sink_fast_path(fresh_hub):
    assert not fresh_hub.active
    # emit_pass_event must return before creating any instrument
    emit_pass_event("train_pass", {"batches": 1, "elapsed_sec": 1.0})
    assert fresh_hub.snapshot() == {}


def test_prom_exposition(fresh_hub):
    fresh_hub.counter("pbox_req_total", "requests").inc(3, kind="a")
    fresh_hub.gauge("pbox_depth").set(2.5)
    h = fresh_hub.histogram("pbox_lat_seconds", buckets=(0.5, 1.0))
    h.observe(0.2)
    h.observe(0.7)
    h.observe(7.0)
    text = fresh_hub.snapshot_prom()
    assert "# TYPE pbox_req_total counter" in text
    assert 'pbox_req_total{kind="a"} 3' in text
    assert "# TYPE pbox_depth gauge" in text
    assert "pbox_depth 2.5" in text
    assert 'pbox_lat_seconds_bucket{le="0.5"} 1' in text
    assert 'pbox_lat_seconds_bucket{le="1.0"} 2' in text
    assert 'pbox_lat_seconds_bucket{le="+Inf"} 3' in text
    assert "pbox_lat_seconds_count 3" in text
    # legacy StatRegistry bridges as pbox_stat gauges
    from paddlebox_tpu.utils.monitor import STATS
    STATS.set("obs_test_stat", 42)
    try:
        assert 'pbox_stat{name="obs_test_stat"} 42' \
            in fresh_hub.snapshot_prom()
    finally:
        STATS.reset("obs_test_stat")


def test_prom_http_endpoint(fresh_hub):
    fresh_hub.counter("pbox_http_total").inc(7)
    srv = fresh_hub.start_prom_http(0)
    try:
        port = srv.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "pbox_http_total 7" in body
    finally:
        fresh_hub.stop_prom_http()


def test_healthz_route(fresh_hub):
    """/healthz on the prom endpoint (ISSUE 10 satellite): run_id,
    uptime, and last-pass age — the serving/streaming liveness probe."""
    srv = fresh_hub.start_prom_http(0)
    try:
        port = srv.server_address[1]
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5)
        assert resp.headers["Content-Type"] == "application/json"
        h = json.loads(resp.read().decode())
        assert h["status"] == "ok"
        assert h["run_id"] == fresh_hub.run_id
        assert h["uptime_sec"] >= 0
        # no pass yet: age is null, count 0
        assert h["passes_total"] == 0
        assert h["last_pass_age_sec"] is None
        emit_pass_event("train_pass", {"batches": 1, "elapsed_sec": 0.1})
        h = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5
            ).read().decode())
        assert h["passes_total"] == 1
        assert h["last_pass_age_sec"] is not None
        assert 0 <= h["last_pass_age_sec"] < 60
        # /metrics still serves exposition on the same port
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
            ).read().decode()
        assert "pbox_passes_total" in body
    finally:
        fresh_hub.stop_prom_http()


def test_readyz_route_and_serving_block(fresh_hub):
    """/readyz (ISSUE 15 satellite): 503 until the serving probe
    reports a first snapshot adoption; /healthz grows the ``serving``
    block once a probe registers."""
    srv = fresh_hub.start_prom_http(0)
    try:
        port = srv.server_address[1]

        def get(route):
            try:
                resp = urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{route}", timeout=5)
                return resp.status, json.loads(resp.read().decode())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read().decode())

        # no serving model in the process: unready, no serving block
        code, body = get("/readyz")
        assert code == 503 and body["ready"] is False
        assert "serving" not in fresh_hub.health()
        # a registered probe with no adoption yet: still 503, but the
        # health endpoint now shows the serving state
        state = {"adopted": None, "epoch": None,
                 "last_reload_ts": None, "staleness_sec": 0.0,
                 "stale": False}
        fresh_hub.set_serving_probe(lambda: dict(state))
        code, body = get("/readyz")
        assert code == 503
        assert body["reason"] == "no snapshot adopted yet"
        h = get("/healthz")[1]
        assert h["serving"]["adopted"] is None
        # first adoption flips readiness; the block carries the id
        state.update(adopted="v0000000007", epoch=7,
                     last_reload_ts=123.0, staleness_sec=1.5)
        code, body = get("/readyz")
        assert code == 200 and body["ready"] is True
        assert body["serving"]["adopted"] == "v0000000007"
        h = get("/healthz")[1]
        assert h["serving"]["staleness_sec"] == 1.5
        # a crashing probe degrades the block, never the endpoint
        def boom():
            raise RuntimeError("probe died")
        fresh_hub.set_serving_probe(boom)
        code, body = get("/readyz")
        assert code == 503
        assert get("/healthz")[0] == 200
    finally:
        fresh_hub.stop_prom_http()


def test_serving_report_column():
    """telemetry_report renders the serving-latency column + summary
    line from serving_stats/serving_reload events (ISSUE 15
    satellite); training-only JSONLs keep their compact rows."""
    spec = importlib.util.spec_from_file_location(
        "telemetry_report_sv",
        os.path.join(os.path.dirname(__file__), os.pardir, "scripts",
                     "telemetry_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    events = [
        {"event": "serving_stats", "adopted": "v0000000001",
         "staleness_sec": 0.0, "lookup_p99_ms": 0.21, "queries": 10},
        {"event": "pass", "kind": "train_pass", "batches": 4,
         "elapsed_sec": 1.0, "examples": 128,
         "examples_per_sec": 128.0, "proc": 0},
        {"event": "serving_reload", "artifact": "v0000000002"},
        {"event": "serving_stats", "adopted": "v0000000002",
         "staleness_sec": 2.1, "predict_p99_ms": 5.99, "queries": 30},
        {"event": "pass", "kind": "train_pass", "batches": 4,
         "elapsed_sec": 1.0, "examples": 128,
         "examples_per_sec": 128.0, "proc": 0},
        {"event": "serving_degraded", "tip": "v0000000003",
         "adopted": "v0000000002", "staleness_sec": 4.0},
    ]
    rows = mod.build_rows(events)
    assert rows[0]["serve p99"] == "p99 0.21ms @v0000000001"
    assert rows[1]["serve p99"] \
        == "p99 5.99ms @v0000000002 (+2.1s stale)"
    rep = mod.render_report(events)
    assert "serving: 1 reloads → v0000000002" in rep
    assert "1 degraded polls" in rep and "max staleness 4.0s" in rep
    # training-only runs: no serving column
    rows = mod.build_rows([e for e in events if e["event"] == "pass"])
    assert "serve p99" not in rows[0]


def test_add_sink_dual_capability_registers_both(fresh_hub):
    """Regression (ISSUE 10 satellite): a sink exposing BOTH emit and
    span_full used to be silently registered span-only — its events
    were dropped. It must land in both lists; kind= narrows explicitly.
    A span sink is classified by ``span_full`` alone (every span comes
    through ``obs.trace.span``)."""
    from paddlebox_tpu.obs import trace

    class Dual:
        def __init__(self):
            self.events, self.spans = [], []

        def emit(self, ev):
            self.events.append(ev)

        def span_full(self, rec):
            self.spans.append(rec["name"])

        def close(self):
            pass

    d = Dual()
    fresh_hub.add_sink(d)
    assert d in fresh_hub.event_sinks()
    assert d in fresh_hub.span_sinks()
    fresh_hub.emit("tick")
    with trace.span("s1"):
        pass
    assert [e["event"] for e in d.events] == ["tick"]
    assert d.spans == ["s1"]
    # explicit kinds narrow; impossible kinds are loud
    only_ev = Dual()
    fresh_hub.add_sink(only_ev, kind="event")
    assert only_ev in fresh_hub.event_sinks()
    assert only_ev not in fresh_hub.span_sinks()
    with pytest.raises(ValueError):
        fresh_hub.add_sink(Dual(), kind="bogus")
    with pytest.raises(TypeError):
        fresh_hub.add_sink(object())

    class PlainOnly:                      # the removed PR 1 surface
        def span(self, name, start, dur, attrs):
            pass

    with pytest.raises(TypeError):
        fresh_hub.add_sink(PlainOnly())
    with pytest.raises(TypeError):
        fresh_hub.add_sink(PlainOnly(), kind="span")
    # close_sinks closes a dual sink exactly once
    closes = []

    class CountingDual(Dual):
        def close(self):
            closes.append(1)

    fresh_hub.add_sink(CountingDual())
    fresh_hub.close_sinks()
    assert len(closes) == 1


# ---- channel gauges ----------------------------------------------------
def test_channel_blocked_put_and_watermark():
    reset_channel_stats()
    ch = Channel(capacity=2, name="t.full")
    done = threading.Event()

    def slow_consumer():
        while True:
            try:
                ch.get(timeout=5)
            except Exception:
                break
            time.sleep(0.02)
        done.set()

    th = threading.Thread(target=slow_consumer, daemon=True)
    th.start()
    for i in range(10):
        ch.put(i)
    m = ch.metrics()
    assert m["high_watermark"] == 2
    assert m["blocked_put_sec"] > 0.01
    assert m["puts"] == 10
    ch.close()
    done.wait(5)
    snap = channel_stats_snapshot()
    assert "t.full" in snap
    assert snap["t.full"]["blocked_put_sec"] > 0.01
    assert snap["t.full"]["high_watermark"] == 2


def test_channel_blocked_get_under_starvation():
    reset_channel_stats()
    ch = Channel(capacity=8, name="t.starved")

    def slow_producer():
        for i in range(3):
            time.sleep(0.03)
            ch.put(i)
        ch.close()

    threading.Thread(target=slow_producer, daemon=True).start()
    got = list(ch)  # batched get path
    assert got == [0, 1, 2]
    snap = channel_stats_snapshot()
    assert snap["t.starved"]["blocked_get_sec"] > 0.02
    assert snap["t.starved"]["gets"] == 3


def test_anonymous_channel_not_registered():
    reset_channel_stats()
    ch = Channel(capacity=4)
    ch.put(1)
    ch.close()
    assert channel_stats_snapshot() == {}


# ---- straggler watchdog ------------------------------------------------
class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def make_wd(store, clock, **kw):
    kw.setdefault("step_lag", 10)
    kw.setdefault("heartbeat_timeout", 30.0)
    return StragglerWatchdog(store, process_index=0, num_processes=2,
                             clock=clock, hub=TelemetryHub(), **kw)


def test_watchdog_silent_on_healthy():
    clock = FakeClock()
    store = LocalHeartbeatStore()
    wd = make_wd(store, clock)
    for step in range(0, 50, 5):
        store.publish(0, step, clock())
        store.publish(1, step - 3, clock())  # within lag
        clock.t += 5
        assert wd.check() == []


def test_watchdog_fires_on_step_lag():
    clock = FakeClock()
    store = LocalHeartbeatStore()
    wd = make_wd(store, clock)
    store.publish(0, 100, clock())
    store.publish(1, 50, clock())  # 50 behind > lag 10
    reps = wd.check()
    assert len(reps) == 1
    r = reps[0]
    assert r.process == 1 and r.reason == "step_lag" and r.behind == 50


def test_watchdog_fires_on_stale_heartbeat():
    clock = FakeClock()
    store = LocalHeartbeatStore()
    wd = make_wd(store, clock)
    store.publish(0, 10, clock())
    store.publish(1, 10, clock())
    clock.t += 100  # both stale, but proc publishing again recovers
    store.publish(0, 11, clock())
    reps = wd.check()
    assert [r.process for r in reps] == [1]
    assert reps[0].reason == "stale"
    assert reps[0].age_sec == pytest.approx(100.0)


def test_watchdog_missing_process_after_grace():
    clock = FakeClock()
    store = LocalHeartbeatStore()
    wd = make_wd(store, clock)
    store.publish(0, 5, clock())
    assert wd.check() == []  # inside the startup grace window
    clock.t += 60
    store.publish(0, 6, clock())
    reps = wd.check()
    assert [r.reason for r in reps] == ["missing"]
    assert reps[0].process == 1 and reps[0].step == -1


def test_watchdog_ignores_prior_run_leftovers():
    """A reused heartbeat dir (restart/elastic downsize) must not let
    the old run's files define the front-runner or report stragglers."""
    clock = FakeClock(2000.0)
    store = LocalHeartbeatStore()
    store.publish(1, 120_000, 100.0)   # old run, huge step, stale ts
    store.publish(7, 120_000, 100.0)   # rank beyond this 2-process mesh
    wd = make_wd(store, clock)
    store.publish(0, 3, clock())
    store.publish(1, 2, clock())       # fresh beat replaces the leftover
    assert wd.check() == []


def test_watchdog_abort_arms_and_beat_raises():
    clock = FakeClock()
    store = LocalHeartbeatStore()
    seen = []
    wd = make_wd(store, clock, abort_after=20.0,
                 on_straggler=lambda reps: seen.append(reps))
    store.publish(0, 100, clock())
    store.publish(1, 0, clock())
    wd.poll_once()              # detection; stall clock starts
    assert seen and not wd._abort_exc
    wd.beat(101)                # still fine before the deadline
    clock.t += 25
    wd.poll_once()              # past abort_after → abort armed
    with pytest.raises(StragglerTimeout):
        wd.beat(102)


def test_watchdog_emits_events():
    clock = FakeClock()
    store = LocalHeartbeatStore()
    hub = TelemetryHub()
    sink = MemorySink()
    hub.add_sink(sink)
    wd = StragglerWatchdog(store, 0, 2, step_lag=10, clock=clock, hub=hub)
    store.publish(0, 100, clock())
    store.publish(1, 0, clock())
    wd.poll_once()
    evs = [e for e in sink.events if e["event"] == "straggler"]
    assert evs and evs[0]["stragglers"][0]["process"] == 1
    assert hub.counter("pbox_straggler_events_total").value() == 1


def test_watchdog_background_thread_detects():
    store = LocalHeartbeatStore()
    fired = threading.Event()
    wd = StragglerWatchdog(store, 0, 2, step_lag=5, poll_interval=0.02,
                           hub=TelemetryHub(),
                           on_straggler=lambda reps: fired.set())
    store.publish(0, 100, time.time())
    store.publish(1, 1, time.time())
    wd.start()
    try:
        assert fired.wait(5), "watchdog thread never fired"
    finally:
        wd.stop()


def test_dir_heartbeat_store_roundtrip(tmp_path):
    store = DirHeartbeatStore(str(tmp_path / "hb"))
    store.publish(0, 12, 100.0)
    store.publish(3, 7, 101.5)
    store.publish(0, 13, 102.0)  # overwrite
    beats = store.read()
    assert beats == {0: (13, 102.0), 3: (7, 101.5)}
    # torn/foreign files are skipped, not fatal
    with open(tmp_path / "hb" / "hb_9.json", "w") as fh:
        fh.write("{not json")
    assert store.read() == beats


def test_make_straggler_watchdog_single_process(tmp_path):
    from paddlebox_tpu.train.multihost import make_straggler_watchdog
    wd = make_straggler_watchdog(start=False)
    assert isinstance(wd.store, LocalHeartbeatStore)
    wd2 = make_straggler_watchdog(heartbeat_dir=str(tmp_path / "hb"),
                                  start=False)
    assert isinstance(wd2.store, DirHeartbeatStore)
    wd2.beat(5)
    assert wd2.store.read()[wd2.process_index][0] == 5


# ---- scatter warmup (AOT, no device allocation) ------------------------
def test_scatter_warmup_emits_event(fresh_hub):
    from paddlebox_tpu.config import flags_scope
    from paddlebox_tpu.ps.table import init_table_state, \
        start_scatter_warmup
    sink = MemorySink()
    fresh_hub.add_sink(sink)
    st = init_table_state(63, 8)
    with flags_scope(scatter_chunk_rows=64, warmup_pass_scatter=True):
        start_scatter_warmup(st, sharded=False)
        deadline = time.time() + 60
        while time.time() < deadline:
            if any(e["event"] == "scatter_warmup" for e in sink.events):
                break
            time.sleep(0.05)
    evs = [e for e in sink.events if e["event"] == "scatter_warmup"]
    assert evs, "warmup never reported"
    assert evs[0]["outcome"] == "ok"
    assert fresh_hub.counter("pbox_scatter_warmup_total").value(
        outcome="ok") == 1


# ---- trainer integration (pass events end to end) ----------------------
@pytest.fixture(scope="module")
def tiny_trainer_run(tmp_path_factory):
    """One streaming + one resident pass with the JSONL sink attached;
    yields (events, report_text)."""
    import optax

    from paddlebox_tpu.config import flags_scope
    from paddlebox_tpu.data import DataFeedDesc, DatasetFactory
    from paddlebox_tpu.data.criteo import generate_criteo_files
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.ps import EmbeddingTable, SparseSGDConfig
    from paddlebox_tpu.train import Trainer

    d = tmp_path_factory.mktemp("obs_run")
    files = generate_criteo_files(str(d), num_files=1, rows_per_file=400,
                                  vocab_per_slot=40, seed=11)
    path = str(d / "run.jsonl")
    hub = reset_hub()
    hub.add_sink(JsonlSink(path))
    try:
        desc = DataFeedDesc.criteo(batch_size=128)
        desc.key_bucket_min = 4096
        ds = DatasetFactory().create_dataset("InMemoryDataset", desc)
        ds.set_filelist(files)
        ds.set_thread(2)
        ds.load_into_memory()
        cfg = SparseSGDConfig(mf_create_thresholds=0.0,
                              mf_initial_range=1e-3)
        table = EmbeddingTable(mf_dim=4, capacity=1 << 13, cfg=cfg,
                               unique_bucket_min=4096)
        with flags_scope(log_period_steps=10000):
            tr = Trainer(CtrDnn(hidden=(16,)), table, desc,
                         tx=optax.adam(1e-3))
            tr.train_pass(ds)
            tr.train_pass_resident(ds)
    finally:
        reset_hub()
    events = [json.loads(l) for l in open(path)]
    spec = importlib.util.spec_from_file_location(
        "telemetry_report",
        os.path.join(os.path.dirname(__file__), os.pardir, "scripts",
                     "telemetry_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return events, mod.render_report(events)


def test_pass_events_schema(tiny_trainer_run):
    events, _ = tiny_trainer_run
    passes = [e for e in events if e["event"] == "pass"]
    kinds = [e["kind"] for e in passes]
    assert kinds == ["train_pass", "train_pass_resident"]
    for e in passes:
        json.dumps(e)  # round-trips
        assert e["batches"] >= 1 and e["elapsed_sec"] > 0
        assert "step" in e["stage_sec"], "new 'step' stage must be timed"
        assert e["stage_count"]["step"] >= 1
        assert set(e["hbm"]) == {"bytes_in_use", "peak_bytes_in_use",
                                 "bytes_limit"}
        assert e["table"]["used"] > 0
        assert e["table"]["capacity"] == 1 << 13
        assert "channels" in e
    stream = passes[0]
    # prefetch pipeline gauges present with put/get accounting
    assert stream["channels"]["trainer.prepare"]["puts"] >= 1
    assert "blocked_put_sec" in stream["channels"]["trainer.prepare"]
    assert "trainer.h2d" in stream["channels"]
    # streaming pass timed prepare/h2d/step/(metrics when registered)
    assert stream["stage_sec"]["prepare"] >= 0
    seqs = [e["ts"] for e in events]
    assert seqs == sorted(seqs)


def test_report_renders(tiny_trainer_run):
    _, report = tiny_trainer_run
    assert "train_pass_resident" in report
    assert "queue stall" in report
    assert "2 passes" in report


def test_trainer_without_sinks_stays_inert(tmp_path_factory):
    """Default-off contract: no sink → no events, no instruments."""
    hub = reset_hub()
    assert not hub.active
    emit_pass_event("train_pass", {"batches": 1})
    assert hub.snapshot() == {}
    reset_hub()
