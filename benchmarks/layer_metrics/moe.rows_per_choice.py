"""Rows the expert loops compute over the token-choices that fell on the
experts held: ``moe_rows_computed / moe_choices_held`` over the window's
``pass.finish`` spans (``span_counters.ratio``): every run's padding to
whole blocks of 512 rows. 1 is no padding. Nothing where the spans lack
the counters."""

from benchmarks import span_counters


def read(ctx):
    return span_counters.ratio(ctx, "moe_rows_computed", "moe_choices_held")
