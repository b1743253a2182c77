"""Share of the busiest device's busy time, over the profiled epoch, in no
scope: programs that left no map, copies and prefetches, a scan's own
bookkeeping. With ``augment``, ``forward``, ``backward``, ``update`` + ``clip``
and the scopes no metric reads alone (``eval``, ``inject``, ``combine``) it
adds up to 100."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, scope_reduce.UNSCOPED)
