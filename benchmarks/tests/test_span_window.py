"""The readers of the program's span ring (``span_window`` and the five
``pipeline.*_ms`` metrics) on rings made by hand: the window's passes are
found among warm, window and traced passes by their durations alone, the
five numbers come out as computed by hand, and a ring that is too short
or does not agree with the harness reads as nothing."""

import collections
import json
import os

import pytest

from benchmarks import harness, span_window

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NEW = ["pipeline.boundary_gap_ms", "pipeline.host_tail_ms",
       "pipeline.mark_trained_ms", "pipeline.host_head_ms",
       "pipeline.queue_wait_ms"]

Rec = collections.namedtuple(
    "Rec", "name lane pass_seq span_id parent_id link_from t0_ns dur_ns "
    "attrs")
MS = 1_000_000


def rec(name, t0_ms, dur_ms, lane="main"):
    return Rec(name, lane, None, 0, 0, 0, int(t0_ms * MS),
               int(dur_ms * MS), None)


def one_pass(t0_ms, head_ms, device_ms, mark_ms, finish_ms, wait_ms):
    """A pass as the trainer spans it, the wait before it first; returns
    (records in completion order, end of the pass in ms)."""
    out = [rec("pass.wait", t0_ms, wait_ms),
           rec("pass.build", t0_ms - 500, 400, lane="preload.worker")]
    t = t0_ms + wait_ms + 0.01            # the harness between the calls
    start = t
    out.append(rec("pass.upload", t + 0.1, 0.2))
    out.append(rec("pass.dispatch", t + 0.3, head_ms - 0.3))
    t += head_ms
    out.append(rec("pass.device_wait", t, device_ms))
    t += device_ms
    out.append(rec("pass.consume", start + 0.05, t - start - 0.05))
    out.append(rec("pass.mark_trained", t + 0.5, mark_ms))
    out.append(rec("pass.finish", t + 0.5 + mark_ms, finish_ms))
    t += 0.5 + mark_ms + finish_ms + 0.25
    out.append(rec("pass.train", start, t - start))
    return out, t


def made_ring(spec):
    """spec: [(head, device, mark, finish, wait)] a pass -> (ring,
    [train_s of every pass])."""
    ring, train_s, t = [], [], 1000.0
    for head, device, mark, finish, wait in spec:
        recs, end = one_pass(t, head, device, mark, finish, wait)
        ring += recs
        train_s.append(recs[-1].dur_ns / 1e9)
        t = end + 0.02
    return ring, train_s


#: 2 warm passes (slower: they compile), 3 of the window, 2 traced (the
#: profiler slows the host); every pass has its own durations
WARM = [(30.0, 2000.0, 50.0, 2.0, 5.0), (3.0, 960.0, 40.0, 1.5, 0.1)]
WINDOW = [(2.0, 945.0, 34.0, 1.0, 0.05), (2.5, 946.0, 60.0, 1.2, 0.04),
          (1.5, 944.0, 35.0, 0.8, 0.06)]
TRACED = [(4.0, 950.0, 70.0, 1.0, 0.2), (4.5, 951.0, 72.0, 1.1, 0.3)]


def by_hand(spec_before, spec):
    """The five numbers for the passes of ``spec``, the last pass of
    ``spec_before`` standing before the first (ms)."""
    tail = lambda p: 0.5 + p[2] + p[3] + 0.25       # noqa: E731
    prev = [spec_before[-1]] + spec[:-1]
    n = len(spec)
    return {
        "pipeline.boundary_gap_ms": sum(
            tail(q) + 0.02 + p[4] + 0.01 + p[0]
            for q, p in zip(prev, spec)) / n,
        "pipeline.host_tail_ms": sum(tail(p) for p in spec) / n,
        "pipeline.mark_trained_ms": sum(p[2] for p in spec) / n,
        "pipeline.host_head_ms": sum(p[0] for p in spec) / n,
        "pipeline.queue_wait_ms": sum(p[4] for p in spec) / n}


@pytest.fixture
def with_ring(monkeypatch):
    def put(ring):
        monkeypatch.setattr(span_window, "ring", lambda: ring)
    return put


@pytest.mark.parametrize("after", [TRACED, []],
                         ids=["warm-window-traced", "warm-window"])
def test_window_is_found_and_the_five_read_by_hand(with_ring, after):
    ring, train_s = made_ring(WARM + WINDOW + after)
    with_ring(ring)
    win = train_s[len(WARM):len(WARM) + len(WINDOW)]
    # the harness's clock sits outside the span: a few microseconds more
    ctx = {"window": {"train_s": [s + 20e-6 for s in win]}, "trace": None}
    passes = span_window.window_passes(ctx["window"], ring)
    assert [p["train"].dur_ns for p in passes] == \
        [int(round(s * 1e9)) for s in win]
    assert [len(p["waits"]) for p in passes] == [1, 1, 1]
    vals = harness.read_layer_metrics(NEW, ctx)
    want = by_hand(WARM, WINDOW)
    assert set(vals) == set(NEW)
    for name in NEW:
        assert vals[name] == pytest.approx(want[name], abs=1e-3), name
    # tail + head + wait is the gap, but for the harness's microseconds
    # and for the tail being the pass's own where the gap's is the one of
    # the pass before (the last warm pass, for the window's first)
    assert vals["pipeline.boundary_gap_ms"] == pytest.approx(
        vals["pipeline.host_tail_ms"] + vals["pipeline.host_head_ms"]
        + vals["pipeline.queue_wait_ms"], abs=2.5)


def test_window_of_one_pass_is_measured_against_the_pass_before(with_ring):
    ring, train_s = made_ring(WARM + WINDOW[:1])
    with_ring(ring)
    vals = harness.read_layer_metrics(
        NEW, {"window": {"train_s": [train_s[2]]}, "trace": None})
    want = by_hand(WARM, WINDOW[:1])
    for name in NEW:
        assert vals[name] == pytest.approx(want[name], abs=1e-3), name


def test_short_ring_and_disagreement_read_as_nothing(with_ring):
    ring, train_s = made_ring(WARM + WINDOW)
    win = train_s[len(WARM):]
    # the ring holds the window's passes and nothing before them
    only = [r for r in ring if r.t0_ns >= ring[2 * 9].t0_ns]
    assert sum(r.name == "pass.train" for r in only) == len(WINDOW)
    with_ring(only)
    assert harness.read_layer_metrics(
        NEW, {"window": {"train_s": win}, "trace": None}) == {}
    # inside and outside disagree by more than 2%: every pass 3% longer
    with_ring(ring)
    assert harness.read_layer_metrics(
        NEW, {"window": {"train_s": [s * 1.03 for s in win]},
              "trace": None}) == {}
    assert harness.read_layer_metrics(
        NEW, {"window": {"train_s": [s * 1.015 for s in win]},
              "trace": None}) != {}
    # no train_s, no ring at all (a program from before the ring)
    assert harness.read_layer_metrics(
        NEW, {"window": {"wait_s": [0.1]}, "trace": None}) == {}
    with_ring(None)
    assert harness.read_layer_metrics(
        NEW, {"window": {"train_s": win}, "trace": None}) == {}
    # a pass that lacks a child span: that metric alone falls silent
    with_ring([r for r in ring if r.name != "pass.mark_trained"])
    vals = harness.read_layer_metrics(
        NEW, {"window": {"train_s": win}, "trace": None})
    assert set(NEW) - set(vals) == {"pipeline.mark_trained_ms"}


def test_new_metrics_are_appended_and_name_their_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    # found by name: later PRs append their own after these
    names = [m["name"] for m in per_layer]
    assert [n for n in names if n in NEW] == NEW
    assert names.index(NEW[0]) > names.index("device.idle_share")
    for m in per_layer:
        if m["name"] not in NEW:
            continue
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "ms", "lower", "program_counter", "pass pipeline")
        assert m["moves"] == "train_examples_per_s_per_chip"
        assert m["workloads"] == ["deepfm-criteo-kaggle.train-resident"]
