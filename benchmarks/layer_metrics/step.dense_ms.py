"""Device time a batch in the dense net, its loss, its optimizer and the
AUC booking, forward and backward (``pbox.dense`` + ``pbox.loss`` +
``pbox.dense_opt`` + ``pbox.auc`` and their ``.bwd``) in the traced
passes, ms (mean over chips)."""

from benchmarks import tracered


def read(ctx):
    return tracered.scope_ms_per_batch(
        ctx["trace"],
        ("pbox.dense", "pbox.loss", "pbox.dense_opt", "pbox.auc"))
