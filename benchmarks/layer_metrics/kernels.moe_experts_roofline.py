"""The routed experts held here (``pbox.moe_experts``: sort, grouped
products, combine) against their roofline, % (``scope_roofline.share``)."""

from benchmarks import scope_roofline


def read(ctx):
    return scope_roofline.share(ctx, "pbox.moe_experts")
