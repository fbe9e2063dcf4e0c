"""Device time a batch in the row gather and pool + CVM, forward and
backward (``pbox.pull`` + ``pbox.pool_cvm`` and their ``.bwd``: the merge
of the unique rows' gradients is ``pbox.pull.bwd``) in the traced passes,
ms (mean over chips)."""

from benchmarks import tracered


def read(ctx):
    return tracered.scope_ms_per_batch(ctx["trace"],
                                       ("pbox.pull", "pbox.pool_cvm"))
