"""Ratios of two counters that the program writes on its ``pass.finish``
spans (``Trainer._finish_sequence_pass``: a model's ``step_scalars``),
each summed over the window's passes."""

from benchmarks import span_window as sw


def ratio(ctx, top: str, bottom: str):
    """``sum(top) / sum(bottom)`` over the ``pass.finish`` spans of the
    window's passes; nothing where a span lacks either counter or the
    bottom sums to 0."""
    spans = sw.ring()
    passes = sw.window_passes(ctx.get("window") or {}, spans)
    if not passes:
        return None
    num = den = 0.0
    for p in passes:
        lo, hi = p["train"].t0_ns, sw.end(p["train"])
        attrs = next((r.attrs for r in spans
                      if r.name == "pass.finish" and r.lane == sw.LANE
                      and lo <= r.t0_ns and sw.end(r) <= hi), None)
        if not attrs or attrs.get(top) is None or not attrs.get(bottom):
            return None
        num += attrs[top]
        den += attrs[bottom]
    return num / den
