"""The share of the routers' choices that fell on the experts held here:
``100 * moe_choices_held / moe_choices`` over the window's ``pass.finish``
spans (``span_counters.ratio``). An even router over E experts of which
this chip holds H reads 100 H / E (12.5 for 8 of 64): the cell's load. On
one chip only the held experts answer, so a router that trains learns to
prefer them and the share, with it the expert loops' rows and the step,
grows through a run: this number says by how much. Nothing where the
spans lack either counter."""

from benchmarks import span_counters


def read(ctx):
    share = span_counters.ratio(ctx, "moe_choices_held", "moe_choices")
    return None if share is None else 100.0 * share
