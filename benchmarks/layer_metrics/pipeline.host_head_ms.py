"""Runner lookup, upload and dispatch before the device has work: start
of ``pass.train`` -> end of ``pass.dispatch``, mean over the window's
passes, ms; read from the program's span ring."""

from benchmarks import span_window as sw


def _head(p):
    if p["dispatch"] is None:
        return None
    return sw.end(p["dispatch"]) - p["train"].t0_ns


def read(ctx):
    return sw.mean_ms(ctx, _head)
