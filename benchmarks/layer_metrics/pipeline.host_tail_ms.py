"""What the trainer does after the device is done: end of
``pass.device_wait`` -> end of ``pass.train``, mean over the window's
passes, ms; read from the program's span ring."""

from benchmarks import span_window as sw


def _tail(p):
    if p["device_wait"] is None:
        return None
    return sw.end(p["train"]) - sw.end(p["device_wait"])


def read(ctx):
    return sw.mean_ms(ctx, _tail)
