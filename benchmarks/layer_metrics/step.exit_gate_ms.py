"""Device time a batch in the exit gate of a looped model, forward and
backward (``pbox.exit_gate`` and its ``.bwd``: the gate's product and
sigmoid at every run, the exit distribution, its entropy, the mixing of
the exits' per-position losses) in the traced passes, ms. Nothing where
the program has no such scope."""

from benchmarks import tracered


def read(ctx):
    return tracered.scope_ms_per_batch(ctx["trace"], ("pbox.exit_gate",))
