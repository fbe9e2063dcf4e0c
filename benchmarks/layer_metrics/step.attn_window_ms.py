"""Device time a batch in the sliding-window attention layers, forward
and backward (``pbox.attn_window`` and its ``.bwd``: norm, projections,
head norms, rotary, windowed blockwise attention, ``o``) in the traced
passes, ms; the full layers' is ``step.attn_ms``. Nothing where the
program has no such scope."""

from benchmarks import tracered


def read(ctx):
    return tracered.scope_ms_per_batch(ctx["trace"], ("pbox.attn_window",))
