"""Device time a batch in the Kimi Delta Attention mixers, forward and
backward (``pbox.kda_proj``: norm, the seven projections, decay, beta,
gated output norm; ``pbox.kda_conv``: the three depthwise convs, SiLU,
L2 norms; ``pbox.kda_scan``: the chunked delta rule; and their ``.bwd``)
in the traced passes, ms. Nothing where the program has no such scope."""

from benchmarks import tracered


def read(ctx):
    return tracered.scope_ms_per_batch(
        ctx["trace"], ("pbox.kda_proj", "pbox.kda_conv", "pbox.kda_scan"))
