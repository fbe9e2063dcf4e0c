"""The gated short convolution's elementwise chain (``pbox.conv_mix``:
``C * conv_3(B * v)``, memory-bound) against its roofline, %
(``scope_roofline.share``)."""

from benchmarks import scope_roofline


def read(ctx):
    return scope_roofline.share(ctx, "pbox.conv_mix")
