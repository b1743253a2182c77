"""Idle share of the busiest device over the profiled epoch with the host's
``probe`` and ``sync_probe`` spans cut out of both the idle time and the span:
the device's idle share while it trains and validates, whether or not the
profiler met a probe epoch (``device_idle_pct`` reads 1.8 or 5.0 by that)."""

from benchmark import scope_reduce


def read(ctx):
    t = scope_reduce.table(ctx)
    if not t or not t["steady"]:
        return None
    return 100.0 * t["steady"]["idle_s"] / t["steady"]["steady_s"]
