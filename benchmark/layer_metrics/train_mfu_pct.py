"""The whole step's share of the chips' peak: the window's samples/s (all its
epochs over all its wall, as the plain run's ``samples_per_s``) x model FLOPs
per sample (3 x forward, from the benchmark's own layer-table count; an
injected load, padding rows and recomputation do not count) over chips x the
peak bf16 FLOP/s of ``benchmark/peaks.json``."""

from benchmark import flops


def read(ctx):
    if ctx["peak"] is None or ctx["window"]["samples_per_s"] <= 0:
        return None
    peak = ctx["cell"]["chips"] * ctx["peak"]["bf16_flops_per_s"]
    return (100.0 * ctx["window"]["samples_per_s"]
            * flops.train_flops_per_sample(ctx["model"]) / peak)
