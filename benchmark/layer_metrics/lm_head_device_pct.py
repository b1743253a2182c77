"""Share of the busiest device's busy time, over the profiled epoch, under the
program's ``lm_head`` scope: the product with the output vocabulary, forward
and backward passes and recomputation alike."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "lm_head")
