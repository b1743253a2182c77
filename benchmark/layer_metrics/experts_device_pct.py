"""Share of the busiest device's busy time, over the profiled epoch, under the
program's ``router`` and ``experts`` scopes: scores and top-k, then dispatch,
grouped products and weighted combine of the held experts, forward and
backward passes and recomputation alike."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "router", "experts")
