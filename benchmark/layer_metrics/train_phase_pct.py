"""Sum of the graftscope ``train`` spans over the window's wall: how much of
the job is the training scan itself (the per-worker probes run inside it), as
against plan, validation and record."""

from benchmark.harness import window_spans


def read(ctx):
    spans = window_spans(ctx, "train")
    if not spans or ctx["window"]["wall_s"] <= 0:
        return None
    return 100.0 * sum(s[3] for s in spans) / ctx["window"]["wall_s"]
