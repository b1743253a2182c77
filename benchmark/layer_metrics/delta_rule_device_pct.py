"""Share of the busiest device's busy time, over the profiled epoch, under the
program's ``delta_rule`` scope: the gated delta rule alone (what is done for
all chunks at once and the scan over them), forward and backward passes and
recomputation alike. What a kernel could win, against
``linear_attention_device_pct`` that holds what stands around it as well."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "delta_rule")
