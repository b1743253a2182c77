"""Seconds of set-up in which some thread traced or lowered a program: the
length of the union of the graftscope ``jax_trace``, ``jax_lower`` (both from
``jax.monitoring``) and ``aot_lower`` spans that start before the window."""

from benchmark import trace_reduce

NAMES = ("jax_trace", "jax_lower", "aot_lower")


def read(ctx):
    before = [(s[0], s[2], s[3]) for s in ctx["spans"]
              if s[0] in NAMES and s[2] < ctx["window"]["t0"]]
    if not any(name != "aot_lower" for name, _, _ in before):
        return None
    return trace_reduce.union_seconds(before)
