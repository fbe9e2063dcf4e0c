"""Share of the window the trainer spent blocked in ``pre.wait()``, %."""


def read(ctx):
    w = ctx["window"]
    return 100.0 * sum(w["wait_s"]) / w["seconds"]
