"""Thread-summed backend compile seconds before the window (jax.monitoring);
near 0 when the persistent cache served every program."""


def read(ctx):
    return ctx["setup"]["compile_s"]
