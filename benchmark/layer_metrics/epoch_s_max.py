"""Slowest whole epoch of the window on the harness's clock."""


def read(ctx):
    return max(e["t1"] - e["t0"] for e in ctx["epochs"]) if ctx["epochs"] else None
