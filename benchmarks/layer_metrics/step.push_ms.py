"""Device time a batch in the push (``pbox.push``: the in-table optimizer
over the unique rows and the scatter of their lines) in the traced
passes, ms (mean over chips)."""

from benchmarks import tracered


def read(ctx):
    return tracered.scope_ms_per_batch(ctx["trace"], ("pbox.push",))
