"""The window's passes as the program's own span ring saw them.

The program keeps every ``obs.trace.span`` in memory (``recent_spans()``),
sink or no sink, so a reader that runs in the run's own process can ask
what the trainer did at each pass boundary. This helper finds the window:
of the ring's ``pass.train`` spans in time order, the run of
``W = len(window["train_s"])`` consecutive ones whose durations lie
closest, one by one, to the harness's own ``train_s`` (least sum of
absolute differences over all offsets), accepted only if that sum is under
``AGREE`` of ``sum(train_s)``: inside and outside must agree on what a
pass is, and no count of warm or traced passes is assumed. A pass's
boundary is measured against the pass before it, so the run starts at the
ring's second pass at the earliest.
"""

from __future__ import annotations

from typing import Callable, List, Optional

#: the ring's durations and the harness's may differ by this share
AGREE = 0.02
LANE = "main"


def ring() -> Optional[list]:
    """The program's recent spans, or None where the program keeps none
    (a parent commit from before the ring)."""
    try:
        from paddlebox_tpu.obs import trace
        return trace.recent_spans()
    except (ImportError, AttributeError):
        return None


def window_passes(window: dict, spans: Optional[list]) -> Optional[List[dict]]:
    """-> one dict a window pass, in order: ``train`` and ``prev`` (the
    ``pass.train`` records of the pass and of the one before it),
    ``dispatch``, ``device_wait``, ``mark_trained`` (the pass's own
    children, None where missing), ``prev_device_wait`` and ``waits``
    (the ``pass.wait`` records between the two passes). None where the
    window has no ``train_s``, the ring is too short or nothing agrees.
    """
    train_s = window.get("train_s")
    if not train_s or not spans:
        return None
    main = sorted((r for r in spans if r.lane == LANE),
                  key=lambda r: r.t0_ns)
    trains = [r for r in main if r.name == "pass.train"]
    w = len(train_s)
    if len(trains) < w + 1:
        return None
    want = [s * 1e9 for s in train_s]
    best, at = None, None
    for o in range(1, len(trains) - w + 1):
        diff = sum(abs(trains[o + i].dur_ns - want[i]) for i in range(w))
        if best is None or diff < best:
            best, at = diff, o
    if best >= AGREE * sum(want):
        return None

    def inside(outer, name):
        lo, hi = outer.t0_ns, outer.t0_ns + outer.dur_ns
        for r in main:
            if r.name == name and lo <= r.t0_ns and \
                    r.t0_ns + r.dur_ns <= hi:
                return r
        return None

    out = []
    for k in range(at, at + w):
        prev, cur = trains[k - 1], trains[k]
        gap_lo = prev.t0_ns + prev.dur_ns
        out.append({
            "train": cur, "prev": prev,
            "dispatch": inside(cur, "pass.dispatch"),
            "device_wait": inside(cur, "pass.device_wait"),
            "mark_trained": inside(cur, "pass.mark_trained"),
            "prev_device_wait": inside(prev, "pass.device_wait"),
            "waits": [r for r in main if r.name == "pass.wait"
                      and gap_lo <= r.t0_ns < cur.t0_ns]})
    return out


def end(rec) -> int:
    return rec.t0_ns + rec.dur_ns


def mean_ms(ctx: dict, per_pass: Callable[[dict], Optional[float]]
            ) -> Optional[float]:
    """Mean over the window's passes of ``per_pass(pass) -> ns``, in ms;
    None where the window cannot be found or a pass lacks the spans."""
    passes = window_passes(ctx.get("window") or {}, ring())
    if not passes:
        return None
    vals = [per_pass(p) for p in passes]
    if any(v is None for v in vals):
        return None
    return sum(vals) / len(vals) / 1e6
