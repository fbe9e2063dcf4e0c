"""How unevenly the routed token-choices fall on the experts held here:
``moe_expert_load_max / moe_expert_load_mean`` summed over the window's
``pass.finish`` spans (a step's most loaded expert layer: its busiest
held expert over its mean). 1 is an even spread. Read from the program's
span ring; nothing where the spans lack the counters."""

from benchmarks import span_window as sw


def read(ctx):
    spans = sw.ring()
    passes = sw.window_passes(ctx.get("window") or {}, spans)
    if not passes:
        return None
    top = mean = 0.0
    for p in passes:
        lo, hi = p["train"].t0_ns, sw.end(p["train"])
        attrs = next((r.attrs for r in spans
                      if r.name == "pass.finish" and r.lane == sw.LANE
                      and lo <= r.t0_ns and sw.end(r) <= hi), None)
        if not attrs or not attrs.get("moe_expert_load_mean"):
            return None
        top += attrs["moe_expert_load_max"]
        mean += attrs["moe_expert_load_mean"]
    return top / mean
