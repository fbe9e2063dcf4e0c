"""The attention layer (``pbox.attn``: projections and blockwise causal
attention) against its roofline, % (``scope_roofline.share``)."""

from benchmarks import scope_roofline


def read(ctx):
    return scope_roofline.share(ctx, "pbox.attn")
