"""Host build seconds a pass: the preloader's ``build_sec_total`` over its
``builds``, both as deltas over the window."""


def read(ctx):
    w = ctx["window"]
    if w["builds"] <= 0:
        return None
    return w["build_s"] / w["builds"]
