"""G002 seed: wall-clock window over an async dispatch with no sync.

The unsynchronised-wall gotcha: the jit call
returns as soon as the work is enqueued, so the wall measures dispatch
latency, not compute."""

import time

import jax

step = jax.jit(lambda p, b: (p * b).sum())


def timed_epoch(params, batches):
    t0 = time.time()
    loss = None
    for b in batches:
        loss = step(params, b)  # async: returns before the device runs
    dt = time.time() - t0  # measures enqueue time, not the epoch
    return loss, dt
