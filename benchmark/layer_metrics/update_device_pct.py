"""Share of the busiest device's busy time, over the profiled epoch, under the
program's ``update`` and ``clip`` scopes: the optimizer's step and the
per-worker gradient clip before it."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "update", "clip")
