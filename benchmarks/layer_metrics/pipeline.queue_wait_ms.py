"""Time blocked in the preloader's queue: the ``pass.wait`` span(s)
between the pass before and the pass (emitted also when nothing blocked),
mean over the window's passes, ms. The inside twin of
``pipeline.preload_wait_share``; read from the program's span ring."""

from benchmarks import span_window as sw


def _wait(p):
    return sum(r.dur_ns for r in p["waits"])


def read(ctx):
    return sw.mean_ms(ctx, _wait)
