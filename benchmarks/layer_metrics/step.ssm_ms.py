"""Device time a batch in the Mamba-2 layers, forward and backward
(``pbox.ssm_proj`` + ``pbox.ssm_conv`` + ``pbox.ssm_scan`` and their
``.bwd``) in the traced passes, ms."""

from benchmarks import tracered


def read(ctx):
    return tracered.scope_ms_per_batch(
        ctx["trace"], ("pbox.ssm_proj", "pbox.ssm_conv", "pbox.ssm_scan"))
