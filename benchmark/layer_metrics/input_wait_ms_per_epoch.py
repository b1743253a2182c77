"""Mean over the window's epochs of the graftscope ``input_wait`` spans: the
controller thread waiting for the gather thread's next window of rows (or of
indices, with the device cache)."""

from benchmark.harness import window_spans


def read(ctx):
    spans = window_spans(ctx, "input_wait")
    if not spans or not ctx["epochs"]:
        return None
    return 1e3 * sum(s[3] for s in spans) / len(ctx["epochs"])
