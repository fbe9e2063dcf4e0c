"""Device time a batch in the leading dense SwiGLU feed-forward, forward
and backward (``pbox.mlp`` and its ``.bwd``) in the traced passes, ms.
Nothing where the program has no such scope."""

from benchmarks import tracered


def read(ctx):
    return tracered.scope_ms_per_batch(ctx["trace"], ("pbox.mlp",))
