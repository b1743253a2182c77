"""Backend compiles (jax.monitoring) between the window's start and end.
Expected 0; whatever it reads is reported."""


def read(ctx):
    return ctx["window"]["compiles"]
