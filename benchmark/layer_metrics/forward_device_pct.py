"""Share of the busiest device's busy time, over the profiled epoch, under the
program's ``forward`` scope: the model's apply and the loss."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "forward")
