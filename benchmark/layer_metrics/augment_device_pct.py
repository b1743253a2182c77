"""Share of the busiest device's busy time, over the profiled epoch, that ran
under the program's ``augment`` scope: crop, flip, normalise and cast of a
training batch (``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "augment")
