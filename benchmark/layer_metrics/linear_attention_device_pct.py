"""Share of the busiest device's busy time, over the profiled epoch, under the
program's ``linear_attention`` scope and the ``delta_rule`` scope inside it:
the whole linear mixer (projections, convolution, norms, decays, the delta
rule, gate and output), forward and backward passes and recomputation alike."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "linear_attention", "delta_rule")
