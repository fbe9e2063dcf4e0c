"""The run of the loop at which a position is expected to leave,
``sum_r r p_r`` averaged over the window's positions:
``loop_exit_step_sum / loop_positions`` over the window's ``pass.finish``
spans (``span_counters.ratio``). A gate at logit 0 over four runs reads
1.875 (p = .5, .25, .125, .125), a gate under which nothing leaves early
4, one that has learned to leave at once 1. Nothing where the spans lack
either counter."""

from benchmarks import span_counters


def read(ctx):
    return span_counters.ratio(ctx, "loop_exit_step_sum", "loop_positions")
