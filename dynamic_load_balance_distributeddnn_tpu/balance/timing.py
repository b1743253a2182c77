"""Per-worker time measurement and exchange.

The reference measures each worker's epoch compute time with wall-clock
deltas, *excluding* accumulated communication wait (dbs.py:226-250), then ring
all-gathers the scalar times so every worker can run the solver on an
identical vector (dbs.py:479-499). That compute/comm split is load-bearing:
the balancer must react to compute speed, not network jitter (SURVEY §2.4).

Here the controller process dispatches every logical worker's step and blocks
on each worker's outputs in completion order, so per-worker durations fall out
of completion timestamps; combine/update (the communication) is timed
separately. Across hosts, the ring all-gather becomes a host-level
``process_allgather`` (per-epoch metadata — no reason to burn an ICI
collective on 8 scalars).

Superstep epochs (ISSUE 2): the elastic hot loop dispatches whole windows, so
there is no per-step host boundary left to time — per-worker walls still come
from the standalone probe steps (raw-wall differencing against the per-device
dispatch overhead, exactly as before), and the host's own cost of driving the
epoch is accumulated separately by :class:`HostOverheadMeter` (dispatch/enqueue
walls vs transfer walls), the quantity the superstep exists to shrink.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

import numpy as np


class TimeKeeper:
    """Accumulates per-worker compute and injected-straggler seconds for one
    epoch; the engine combines them (with any fault time multipliers) into the
    solver's node-time vector. Comm time is deliberately absent: the balancer
    reacts to compute speed only (reference contract, dbs.py:250/425).
    Not thread-safe; the engine drives it from the controller thread."""

    def __init__(self, world_size: int):
        self.world_size = world_size
        self.reset()

    def reset(self) -> None:
        self.compute_s = np.zeros(self.world_size, dtype=np.float64)
        self.injected_s = np.zeros(self.world_size, dtype=np.float64)

    def add_compute(self, worker: int, seconds: float) -> None:
        self.compute_s[worker] += seconds

    def add_injected(self, worker: int, seconds: float) -> None:
        """Virtual straggler seconds (fault_mode='virtual'): counted into the
        time vector the solver sees, mirroring the reference's sleeps being
        measured into train_time (dbs.py:103, 241)."""
        self.injected_s[worker] += seconds


class HostOverheadMeter:
    """Per-epoch accounting of the HOST's cost of driving the device: seconds
    spent enqueueing work (``dispatch()`` — Python dispatch loops; async, so
    this is pure host overhead, not device compute) and seconds spent in
    host→device transfers (``add_put_s`` — called from the transfer
    pipeline's worker threads, hence the lock). These walls deliberately do
    NOT sync the device: they measure the controller, which is exactly what
    wall-clock-around-async-dispatch measures (the G002 failure mode, here
    the intended quantity). The elastic superstep path exists to shrink
    them; the engine records them per epoch (``host_dispatch_s``,
    ``host_put_s``, ``host_overhead_per_step_s``) and its window loop reads
    ``mark_window``."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.dispatch_s = 0.0
            self.put_s = 0.0
            self.dispatches = 0
            self._mark_dispatch_s = 0.0
            self._mark_put_s = 0.0
            self._mark_dispatches = 0

    @contextmanager
    def dispatch(self):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self.dispatch_s += dt
                self.dispatches += 1

    def add_put_s(self, seconds: float) -> None:
        with self._lock:
            self.put_s += float(seconds)

    def mark_window(self) -> "tuple[float, float, int]":
        """Per-window snapshot: (dispatch_s, put_s, dispatches) accumulated
        since the previous mark — the host-side component of the window
        controller's step-wall signal (ISSUE 11). The cumulative epoch
        totals above are untouched; marks only move the window baseline."""
        with self._lock:
            d = self.dispatch_s - getattr(self, "_mark_dispatch_s", 0.0)
            p = self.put_s - getattr(self, "_mark_put_s", 0.0)
            n = self.dispatches - getattr(self, "_mark_dispatches", 0)
            self._mark_dispatch_s = self.dispatch_s
            self._mark_put_s = self.put_s
            self._mark_dispatches = self.dispatches
            return d, p, n

    def per_step(self, num_steps: int) -> float:
        """Host overhead (dispatch + put walls) amortized per plan step."""
        with self._lock:
            return (self.dispatch_s + self.put_s) / max(int(num_steps), 1)


def exchange_times(local_times: np.ndarray) -> np.ndarray:
    """All-gather per-worker times across hosts (reference's time_allreduce
    ring, dbs.py:479-499). Single-host: identity. Multi-host: each host
    contributes its local workers' slice; result is rank-ordered like the
    reference's rotate+reverse step (dbs.py:495-498)."""
    import jax

    if jax.process_count() == 1:
        return np.asarray(local_times, dtype=np.float64)
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(
        np.asarray(local_times, dtype=np.float64)
    )
    return np.asarray(gathered).reshape(-1)


def ring_exchange_times(local_times: np.ndarray, mesh=None) -> np.ndarray:
    """Device-side ring all-gather of per-worker scalar times over the mesh's
    ICI — the literal structure of the reference's isend/recv ring
    (dbs.py:487-493: size-1 hops, each device forwarding what it received),
    built from ``lax.ppermute``. The host ``exchange_times`` is the default
    (8 scalars per epoch do not merit a device collective, SURVEY §5.8); this
    exists for topology faithfulness and as the pattern to scale metadata
    exchange on large meshes where host gathers would serialize on one
    coordinator.

    ``local_times``: [n_dev] — entry d is the time measured for the worker on
    mesh device d. Returns the full rank-ordered [n_dev] vector, identical on
    every device (and to the input, since every device contributes its slot).
    """
    import jax
    import jax.numpy as jnp

    from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import data_mesh

    mesh = mesh or data_mesh()
    n = len(mesh.devices.flat)
    times = jnp.asarray(local_times, dtype=jnp.float32)
    return np.asarray(_build_ring_exchange(mesh, n)(times), dtype=np.float64)


_RING_EXCHANGE_CACHE: dict = {}


def _build_ring_exchange(mesh, n: int):
    """Compile the ring all-gather ONCE per (mesh, n): the pre-fix form built
    a fresh jit wrapper (a fresh closure identity, so a fresh XLA compile)
    inside ring_exchange_times on every call — graftlint G001."""
    cached = _RING_EXCHANGE_CACHE.get((mesh, n))
    if cached is not None:
        return cached

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dynamic_load_balance_distributeddnn_tpu.parallel.mesh import (
        DATA_AXIS,
        shard_map,
    )

    def ring(t_local):
        # t_local: [1] — this device's scalar. Accumulate into slot idx of a
        # local [n] buffer, then forward the received value around the ring
        # n-1 times (dbs.py:487-493's loop, one ppermute per hop).
        idx = jax.lax.axis_index(DATA_AXIS)
        out = jnp.zeros((n,), jnp.float32).at[idx].set(t_local[0])
        perm = [(i, (i + 1) % n) for i in range(n)]

        def hop(carry, _):
            buf, recv, src = carry
            recv = jax.lax.ppermute(recv, DATA_AXIS, perm)
            src = jax.lax.ppermute(src, DATA_AXIS, perm)
            buf = buf.at[src].set(recv)
            return (buf, recv, src), None

        (out, _, _), _ = jax.lax.scan(
            hop, (out, t_local[0], idx), None, length=n - 1
        )
        return out

    sharded = jax.jit(
        shard_map(
            ring,
            mesh=mesh,
            in_specs=P(DATA_AXIS),
            out_specs=P(None),
            check_vma=False,
        )
    )
    _RING_EXCHANGE_CACHE[(mesh, n)] = sharded
    return sharded
