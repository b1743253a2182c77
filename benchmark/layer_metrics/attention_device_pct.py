"""Share of the busiest device's busy time, over the profiled epoch, under the
program's ``attention_window`` and ``attention_full`` scopes: projections,
head norms, rotary positions, scores, mix, gate and output, forward and
backward passes and recomputation alike."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "attention_window", "attention_full")
