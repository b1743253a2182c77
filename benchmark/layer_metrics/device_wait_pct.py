"""Share of the window's wall in which the controller thread was blocked on
the device: the union of the graftscope ``device_wait`` spans and of the
``probe`` and ``sync_probe`` spans, whose paired timing loops are waits that
take no span of their own. The rest is host work between dispatches."""

from benchmark import scope_reduce
from benchmark.harness import window_spans


def read(ctx):
    if not window_spans(ctx, "device_wait") or ctx["window"]["wall_s"] <= 0:
        return None
    waited = scope_reduce.wait_seconds(ctx["spans"], ctx["window"]["t0"], ctx["window"]["t1"])
    return 100.0 * waited / ctx["window"]["wall_s"]
