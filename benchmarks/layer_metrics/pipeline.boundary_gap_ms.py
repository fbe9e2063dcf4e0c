"""How long the device has nothing to do at a pass boundary, as the host
sees it: end of ``pass.device_wait`` of the pass before -> end of
``pass.dispatch`` of the pass, mean over the window's passes, ms. The
inside twin of ``device.idle_share``; read from the program's span ring."""

from benchmarks import span_window as sw


def _gap(p):
    if p["prev_device_wait"] is None or p["dispatch"] is None:
        return None
    return sw.end(p["dispatch"]) - sw.end(p["prev_device_wait"])


def read(ctx):
    return sw.mean_ms(ctx, _gap)
