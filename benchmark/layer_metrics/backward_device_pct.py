"""Share of the busiest device's busy time, over the profiled epoch, under
``backward``: what JAX names ``transpose(jvp(forward))``."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "backward")
