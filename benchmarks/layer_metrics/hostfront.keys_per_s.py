"""Keys the host build front handled a second of build time, over the
passes built inside the window."""


def read(ctx):
    w = ctx["window"]
    if w["builds"] <= 0 or w["build_s"] <= 0:
        return None
    keys_a_pass = w["records"] / w["passes"] * ctx["keys_per_example"]
    return keys_a_pass * w["builds"] / w["build_s"]
