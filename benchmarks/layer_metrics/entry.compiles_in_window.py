"""Programs that needed an executable between the window's opening and
its close (jax.monitoring backend_compile events). 0 is the only healthy
value."""


def read(ctx):
    return ctx["window"]["compiles"]
