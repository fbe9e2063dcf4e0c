"""Device time a batch in the final norm, the output head and the
per-position loss, forward and backward (``pbox.head`` + ``pbox.loss``
and their ``.bwd``) in the traced passes, ms."""

from benchmarks import tracered


def read(ctx):
    return tracered.scope_ms_per_batch(ctx["trace"],
                                       ("pbox.head", "pbox.loss"))
