"""Device time a batch in the gather of the step's distinct rows and the
merge of their gradients where a table row is several 128-float lines
(``pbox.pull`` and its ``.bwd`` over ``ps/table``'s wide-row paths: a
language model's token vectors) in the traced passes, ms."""

from benchmarks import tracered


def read(ctx):
    return tracered.scope_ms_per_batch(ctx["trace"], ("pbox.pull",))
