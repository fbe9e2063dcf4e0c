"""The chunked delta rule (``pbox.kda_scan``) against its roofline, %
(``scope_roofline.share``): the family counts what the recurrence needs,
whatever chunk the program computes it in. Nothing where the program has
no such scope or the family counts no work for it."""

from benchmarks import scope_roofline


def read(ctx):
    return scope_roofline.share(ctx, "pbox.kda_scan")
