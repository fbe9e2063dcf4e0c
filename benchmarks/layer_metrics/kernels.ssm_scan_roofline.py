"""The chunked state-space scan (``pbox.ssm_scan``) against its roofline,
% (``scope_roofline.share``)."""

from benchmarks import scope_roofline


def read(ctx):
    return scope_roofline.share(ctx, "pbox.ssm_scan")
