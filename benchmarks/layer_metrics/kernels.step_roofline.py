"""Least time of one step on this chip (roofline.py: the algorithm's
operations and bytes against the published peaks) over the measured
device time of one step in the traced passes, %."""

from benchmarks import roofline


def read(ctx):
    t = ctx["trace"]
    if not t or t["batches"] <= 0 or t["busy_s"] <= 0:
        return None
    least = roofline.least_step_seconds(ctx["work"], ctx["peaks"])["seconds"]
    return 100.0 * least / (t["busy_s"] / t["batches"])
