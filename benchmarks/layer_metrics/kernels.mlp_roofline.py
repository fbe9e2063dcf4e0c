"""The dense SwiGLU feed-forward (``pbox.mlp``: its norms and three
products) against its roofline, % (``scope_roofline.share``). Nothing
where the family counts no work for the scope."""

from benchmarks import scope_roofline


def read(ctx):
    return scope_roofline.share(ctx, "pbox.mlp")
