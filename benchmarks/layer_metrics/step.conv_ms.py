"""Device time a batch in the gated short-convolution operators, forward
and backward (``pbox.conv_proj``: norm, ``in_proj``, ``out_proj``;
``pbox.conv_mix``: the two gates and the depthwise conv; and their
``.bwd``) in the traced passes, ms. Nothing where the program has no such
scope."""

from benchmarks import tracered


def read(ctx):
    return tracered.scope_ms_per_batch(
        ctx["trace"], ("pbox.conv_proj", "pbox.conv_mix"))
