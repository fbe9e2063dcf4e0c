"""The sliding-window attention layers (``pbox.attn_window``: projections
and blockwise attention over the windows' pairs) against their roofline,
% (``scope_roofline.share``)."""

from benchmarks import scope_roofline


def read(ctx):
    return scope_roofline.share(ctx, "pbox.attn_window")
