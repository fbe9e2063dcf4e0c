"""Device time a batch in the expert layers, forward and backward
(``pbox.moe_route`` + ``pbox.moe_experts`` + ``pbox.moe_shared`` and
their ``.bwd``) in the traced passes, ms."""

from benchmarks import tracered


def read(ctx):
    return tracered.scope_ms_per_batch(
        ctx["trace"],
        ("pbox.moe_route", "pbox.moe_experts", "pbox.moe_shared"))
