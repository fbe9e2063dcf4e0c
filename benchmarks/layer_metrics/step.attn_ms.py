"""Device time a batch in the attention layers, forward and backward
(``pbox.attn`` and its ``.bwd``: norm, projections, blockwise attention)
in the traced passes, ms."""

from benchmarks import tracered


def read(ctx):
    return tracered.scope_ms_per_batch(ctx["trace"], ("pbox.attn",))
