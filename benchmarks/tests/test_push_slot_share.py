"""``step.push_slot_share`` on rings made by hand: the share is the
window's ``push_slots`` over its ``push_slots_full``, read from the
``pass.finish`` span of each window pass and of no other; a ring whose
spans lack the counters (a parent from before them) reads as nothing."""

import pytest

from benchmarks import harness, span_window
from benchmarks.tests.test_span_window import (TRACED, WARM, WINDOW,
                                               made_ring)

NAME = "step.push_slot_share"
K = 212_992
#: (push_slots, push_slots_full) a pass: warm, window, traced
COUNTS = ([(32 * K, 32 * K), (8 * K, 32 * K)]
          + [(32 * 57_344, 32 * K), (32 * 65_536, 32 * K),
             (31 * 57_344 + 49_152, 32 * K)]
          + [(K, 32 * K), (2 * K, 32 * K)])


def counted(ring, counts, drop=()):
    """The ring with the counters on its ``pass.finish`` spans, in
    order; the passes in ``drop`` keep none."""
    out, i = [], 0
    for r in ring:
        if r.name == "pass.finish":
            if i not in drop:
                r = r._replace(attrs={"push_slots": counts[i][0],
                                      "push_slots_full": counts[i][1]})
            i += 1
        out.append(r)
    return out


@pytest.fixture
def ctx_of(monkeypatch):
    def put(ring, train_s):
        monkeypatch.setattr(span_window, "ring", lambda: ring)
        win = train_s[len(WARM):len(WARM) + len(WINDOW)]
        return {"window": {"train_s": win}, "trace": None}
    return put


def test_share_is_the_windows_slots_over_its_full_slots(ctx_of):
    ring, train_s = made_ring(WARM + WINDOW + TRACED)
    ctx = ctx_of(counted(ring, COUNTS), train_s)
    win = COUNTS[len(WARM):len(WARM) + len(WINDOW)]
    want = 100.0 * sum(a for a, _ in win) / sum(b for _, b in win)
    assert 27 < want < 29
    assert harness.read_layer_metrics([NAME], ctx) == {
        NAME: pytest.approx(want, rel=1e-12)}


@pytest.mark.parametrize("drop", [range(7), [3]],
                         ids=["no-span-has-them", "one-window-pass-lacks"])
def test_spans_without_the_counters_read_as_nothing(ctx_of, drop):
    ring, train_s = made_ring(WARM + WINDOW + TRACED)
    ctx = ctx_of(counted(ring, COUNTS, drop=set(drop)), train_s)
    assert harness.read_layer_metrics([NAME], ctx) == {}


def test_no_ring_and_no_window_read_as_nothing(ctx_of):
    ring, train_s = made_ring(WARM + WINDOW)
    ctx = ctx_of(None, train_s)
    assert harness.read_layer_metrics([NAME], ctx) == {}
    ctx = ctx_of(counted(ring, COUNTS), train_s)
    ctx["window"] = {"wait_s": [0.1]}
    assert harness.read_layer_metrics([NAME], ctx) == {}
