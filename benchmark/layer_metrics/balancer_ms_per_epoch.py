"""Mean over the window's epochs of the graftscope ``plan_solve`` span plus
the ``probe`` spans: what the balancer's deciding and measuring cost."""

from benchmark.harness import window_spans


def read(ctx):
    spans = window_spans(ctx, "plan_solve", "probe")
    if not spans or not ctx["epochs"]:
        return None
    return 1e3 * sum(s[3] for s in spans) / len(ctx["epochs"])
