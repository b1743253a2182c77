"""Over the window's epochs, the largest time the host held an epoch while
not waiting on the device: the graftscope ``epoch`` span less the union of the
``device_wait``, ``probe`` and ``sync_probe`` spans inside it. A stalled epoch
(PERF.md, PR 23, Findings 7) shows here if the host caused it and under
``device_wait`` if the runtime did."""

from benchmark import scope_reduce
from benchmark.harness import window_spans


def read(ctx):
    epochs = window_spans(ctx, "epoch")
    if not epochs or not window_spans(ctx, "device_wait"):
        return None
    return 1e3 * max(dur - scope_reduce.wait_seconds(ctx["spans"], start, start + dur)
                     for _, _, start, dur in epochs)
