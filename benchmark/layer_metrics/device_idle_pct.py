"""1 - (union of the device-operation intervals on the busiest device over
the traced span), from the profiler's trace."""


def read(ctx):
    profile = ctx["profile"]
    if not profile:
        return None
    return 100.0 * (1.0 - profile["busiest_busy_s"] / profile["window_s"])
