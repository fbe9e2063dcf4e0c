"""Device time a batch in the wire's decode and the dedup of its keys
(``pbox.decode`` + ``pbox.dedup``) in the traced passes, ms (mean over
chips)."""

from benchmarks import tracered


def read(ctx):
    return tracered.scope_ms_per_batch(ctx["trace"],
                                       ("pbox.decode", "pbox.dedup"))
