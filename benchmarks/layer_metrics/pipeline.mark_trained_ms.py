"""Duration of ``pass.mark_trained`` (``ResidentPass.mark_trained_rows``,
on the main thread after every pass), mean over the window's passes, ms;
read from the program's span ring."""

from benchmarks import span_window as sw


def _mark(p):
    return None if p["mark_trained"] is None else p["mark_trained"].dur_ns


def read(ctx):
    return sw.mean_ms(ctx, _mark)
