"""Device time a batch in the push where a table row is several
128-float lines (``pbox.push`` over ``ps/table``'s wide-row paths: the
in-row optimizer over the step's distinct rows and the scatter of their
lines) in the traced passes, ms."""

from benchmarks import tracered


def read(ctx):
    return tracered.scope_ms_per_batch(ctx["trace"], ("pbox.push",))
