"""Device busy time a batch in the traced passes, ms (mean over chips)."""


def read(ctx):
    t = ctx["trace"]
    if not t or t["batches"] <= 0:
        return None
    return 1e3 * t["busy_s"] / t["batches"]
