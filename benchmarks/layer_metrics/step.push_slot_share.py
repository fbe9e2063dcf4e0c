"""Share of the unique axis' slots that the pushes of the window's passes
visited: ``push_slots / push_slots_full`` summed over the window's
``pass.finish`` spans, %. 100 where every step's gather and push run over
the whole axis; the compact wire's steps stop at their distinct count
(``ps/table.apply_push``'s ``num_unique``). Read from the program's span
ring; nothing where the spans lack the counters (a program from before
them)."""

from benchmarks import span_window as sw


def read(ctx):
    spans = sw.ring()
    passes = sw.window_passes(ctx.get("window") or {}, spans)
    if not passes:
        return None
    slots = full = 0
    for p in passes:
        lo, hi = p["train"].t0_ns, sw.end(p["train"])
        attrs = next((r.attrs for r in spans
                      if r.name == "pass.finish" and r.lane == sw.LANE
                      and lo <= r.t0_ns and sw.end(r) <= hi), None)
        if not attrs or "push_slots" not in attrs \
                or not attrs.get("push_slots_full"):
            return None
        slots += attrs["push_slots"]
        full += attrs["push_slots_full"]
    return 100.0 * slots / full
