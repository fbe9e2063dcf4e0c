"""Device time a batch in the attention layers' head-wise output gates,
forward and backward (``pbox.attn_gate`` and its ``.bwd``: the gate's
projection, its sigmoid and its product with the heads, inside a full or
a sliding layer's own scope) in the traced passes, ms. Nothing where the
program has no such scope."""

from benchmarks import tracered


def read(ctx):
    return tracered.scope_ms_per_batch(ctx["trace"], ("pbox.attn_gate",))
