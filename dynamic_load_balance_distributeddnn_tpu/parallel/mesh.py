"""Mesh construction and shardings.

The distributed backend of this framework is XLA itself: a 1-D ``Mesh`` over
all chips with a ``data`` axis, gradients combined by XLA collectives over
ICI/DCN — the TPU-native replacement for the reference's gloo process group
(dbs.py:511-515; SURVEY §2.4). Multi-host runs call
``jax.distributed.initialize`` first (the rendezvous analogue of
MASTER_ADDR/MASTER_PORT env rendezvous, dbs.py:513-514).

The mesh is 1-D today because data parallelism with dynamic shards is the
reference's only strategy (SURVEY §2.3); the axis name is threaded through
everything so additional axes (tensor/pipeline/sequence) can be added without
reshaping the core.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"

# Two-level ICI/DCN factorization (ISSUE 12): the flat data axis splits into
# an in-host axis (chips wired by ICI — fast) and a cross-host axis (DCN —
# the slow link on pods). The hierarchical gradient collective
# reduce-scatters over DEVICE_AXIS at full precision, crosses HOST_AXIS on a
# compressed wire, and all-gathers back over DEVICE_AXIS.
HOST_AXIS = "host"
DEVICE_AXIS = "device"


# Every shard_map in the repo routes through these two names, so call sites
# import them from here rather than reaching into jax themselves.
shard_map = jax.shard_map
axis_size = jax.lax.axis_size


def initialize_multihost(coordinator: Optional[str] = None, **kw) -> None:
    """Cross-host rendezvous (the MASTER_ADDR/PORT + init_process_group
    analogue, dbs.py:513-515). No-op without a coordinator, and idempotent —
    wrappers that call the CLI several times in one process (sweeps,
    gen_statis) must not re-initialize."""
    if coordinator is None or jax.distributed.is_initialized():
        return
    jax.distributed.initialize(coordinator_address=coordinator, **kw)


def data_mesh(devices: Optional[Sequence] = None, axis: str = DATA_AXIS) -> Mesh:
    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devices), (axis,))


def tree_mesh(devices: Sequence, names: Sequence[str], sizes: Sequence[int]) -> Mesh:
    """N-level mesh over a flat device list (ISSUE 17): reshape ROW-MAJOR to
    the topology tree's level sizes, outermost-first — so the flat device
    numbering (mixed-radix over the axis coordinates) matches the flat
    :func:`data_mesh` order and per-device work (rng folds, batch slices) is
    identical under ANY factorization. The device list must already be
    grouped in mesh order (contiguous blocks per outer level —
    ``parallel/topology.py`` derives exactly such trees)."""
    devices = list(devices)
    names, sizes = tuple(names), tuple(int(s) for s in sizes)
    n = 1
    for s in sizes:
        n *= s
    if len(names) != len(sizes) or n != len(devices):
        raise ValueError(
            f"{len(devices)} devices do not factor into levels {list(zip(names, sizes))}"
        )
    return Mesh(np.array(devices).reshape(sizes), names)


def hier_mesh(
    devices: Sequence,
    hosts: int,
    host_axis: str = HOST_AXIS,
    device_axis: str = DEVICE_AXIS,
) -> Mesh:
    """Two-level ``(host, device)`` mesh over a flat device list: row k holds
    host k's chips (the list must already be host-grouped in mesh order —
    parallel/topology.py ``factor_hosts`` validates exactly that). A thin
    delegate onto the N-level :func:`tree_mesh`."""
    devices = list(devices)
    if hosts < 1 or len(devices) % hosts:
        raise ValueError(
            f"{len(devices)} devices do not factor into {hosts} hosts"
        )
    return tree_mesh(
        devices, (host_axis, device_axis), (hosts, len(devices) // hosts)
    )


def mesh_batch_axes(mesh: Mesh) -> Union[str, tuple]:
    """The PartitionSpec entry that shards a batch dimension over the WHOLE
    mesh: the lone axis name on a flat mesh, the axis-name tuple on a
    two-level one (P treats a tuple entry as that dim split over all named
    axes, major-to-minor — the flat device order)."""
    names = tuple(mesh.axis_names)
    return names[0] if len(names) == 1 else names


def zero1_chunk_axes(mesh: Mesh) -> Union[str, tuple]:
    """The PartitionSpec entry for a ZeRO-1 1/n optimizer chunk's flat
    vector: the data axis on a flat mesh; on a tree mesh the REVERSED axis
    tuple — innermost-major, the reverse of the batch entry. The tree
    sharded update produces exactly this block order: each reduce-scatter
    (innermost level first) hands a device its coordinate's slice of the
    remaining vector and the top hop's re-split hands it the outermost
    coordinate's sub-slice, so device ``(a_0, .., a_k)`` owns flat block
    ``a_k`` most-significant down to ``a_0`` least — which is what a dim
    split over ``reversed(names)`` means (two-level: block ``d*H + h``,
    the PR-13 layout, unchanged)."""
    names = tuple(mesh.axis_names)
    if len(names) == 1:
        return names[0]
    return tuple(reversed(names))


def probe_link_bandwidth(
    mesh: Mesh,
    floats_per_device: int = 1 << 18,
    reps: int = 3,
    tracer=None,
    gate_ratio: float = 0.95,
) -> Dict[str, object]:
    """Tiny per-link bandwidth probe of a tree mesh (ISSUE 12, N-level since
    ISSUE 17): time the three phases of the tree combine standalone — the
    full-precision reduce-scatter cascade over the inner axes (ICI and
    friends), a psum over the OUTERMOST axis on the scattered chunk (the DCN
    hop), and the all-gather cascade back — and derive bytes/s per link
    class from the logical per-device payload. Additionally measures each
    LEVEL's link rate in isolation (one psum per axis on the chunk payload,
    ``level_bytes_per_s`` outermost-first) — the signal the per-hop codec
    chooser (``parallel/wire.py choose_wires``) and the learned topology
    clustering consume. The engine gates ``--grad_comm hier`` on the wall
    ratio when ``--dcn_bandwidth_probe`` is set (a mesh whose "DCN" is as
    fast as its ICI — one host, or a CPU test mesh — gains nothing from the
    extra hops and falls back to flat); ``gate_ratio`` is the required
    margin (``--dcn_probe_gate``): hier must beat ``gate_ratio * flat``.

    Each phase runs under its own graftscope span (``comm_reduce_scatter`` /
    ``comm_dcn`` / ``comm_gather``, cat="comm") so a traced run shows the
    per-link attribution directly."""
    import time

    import jax.numpy as jnp

    if tracer is None:
        from dynamic_load_balance_distributeddnn_tpu.obs.trace import get_tracer

        tracer = get_tracer()
    names = tuple(mesh.axis_names)
    sizes = tuple(int(mesh.shape[a]) for a in names)
    inner_axes = names[1:]
    n_h = sizes[0]
    n_d = 1  # product of the inner levels: the "devices per host" class
    for s in sizes[1:]:
        n_d *= s
    n = n_h * n_d
    c = -(-floats_per_device // n_d) * n_d  # per-device payload, RS-divisible
    both = names
    sh = NamedSharding(mesh, P(both))

    def _program(body):
        # one-shot probe wrappers, built once per PROBE (at most once per
        # engine init, never in a hot scope) — caching them would pin the
        # mesh alive for the life of the process
        return jax.jit(  # graftlint: disable=G001
            shard_map(
                body, mesh=mesh, in_specs=P(both), out_specs=P(both),
                check_vma=False,
            )
        )

    def _payload(size):
        return jax.device_put(np.zeros((size,), np.float32), sh)

    # two inputs serve all four programs — the full payload (RS and the
    # flat reference) and the post-RS chunk (c/D floats per device; the
    # DCN psum's output is host-replicated, and declaring it
    # P((host, device)) just keeps every device's copy addressable — fine
    # for a timing probe, check_vma off)
    x_full = _payload(n * c)
    x_chunk = _payload(n * (c // n_d))

    def _rs_body(v):
        for a in reversed(inner_axes):  # innermost first, as the tree walks
            v = jax.lax.psum_scatter(v, a, scatter_dimension=0, tiled=True)
        return v

    def _ag_body(v):
        for a in inner_axes:
            v = jax.lax.all_gather(v, a, tiled=True)
        return v

    rs = _program(_rs_body)
    dcn = _program(lambda v: jax.lax.psum(v, names[0]))
    ag = _program(_ag_body)

    def timed(name: str, fn, x) -> float:
        jax.block_until_ready(fn(x))  # compile + warm
        best = float("inf")
        with tracer.span(name, cat="comm"):
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x))
                best = min(best, time.perf_counter() - t0)
        return best

    walls = {
        "comm_reduce_scatter": timed("comm_reduce_scatter", rs, x_full),
        "comm_dcn": timed("comm_dcn", dcn, x_chunk),
        "comm_gather": timed("comm_gather", ag, x_chunk),
    }
    # The gating reference: the flat combine IS one psum over every axis at
    # full width, so the gate compares the measured three-phase hier wall
    # against the measured flat wall on the same payload — a derived
    # bandwidth ratio would misread overhead-dominated links (a tiny DCN
    # chunk pays full dispatch latency and reads as "slow" even when the
    # link is not).
    flat_fn = _program(lambda v: jax.lax.psum(v, both))
    flat_wall = timed("comm_flat_ref", flat_fn, x_full)
    hier_wall = sum(walls.values())
    ici_wall = 0.5 * (walls["comm_reduce_scatter"] + walls["comm_gather"])
    chunk_bytes = (c // n_d) * 4
    # Per-LEVEL isolated link rates on the same chunk payload: one psum per
    # axis, so differences between entries are link speed, not payload. This
    # is what choose_wires / TopologyTree.learned consume.
    level_walls = [
        timed(
            f"comm_level_{a}",
            _program(lambda v, a=a: jax.lax.psum(v, a)),
            x_chunk,
        )
        for a in names
    ]
    return {
        "ici_bytes_per_s": (c * 4) / max(ici_wall, 1e-9),
        "dcn_bytes_per_s": chunk_bytes / max(walls["comm_dcn"], 1e-9),
        "level_bytes_per_s": [
            chunk_bytes / max(w, 1e-9) for w in level_walls
        ],
        "levels": [[a, int(s)] for a, s in zip(names, sizes)],
        "phase_s": {k: round(v, 6) for k, v in walls.items()},
        "flat_wall_s": round(flat_wall, 6),
        "hier_wall_s": round(hier_wall, 6),
        # hier must beat flat with margin at FULL precision structure; the
        # compressed wire only widens its win (fewer DCN bytes)
        "hier_wins": bool(hier_wall < gate_ratio * flat_wall),
        "gate_ratio": float(gate_ratio),
        "wall_ratio": round(hier_wall / max(flat_wall, 1e-9), 4),
        "hosts": int(n_h),
        "devices_per_host": int(n_d),
    }


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def stacked_sharding(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Leading axis split across the mesh — used for [n_devices, ...] stacks
    (per-device gradient partials, sharded batches)."""
    return NamedSharding(mesh, P(axis))


def batch_sharding(
    mesh: Mesh, ndim: int, axis: str = DATA_AXIS, axis_dim: int = 0
) -> NamedSharding:
    """Shard one dimension (``axis_dim``) over the mesh axis, replicate the
    rest."""
    spec = [None] * ndim
    spec[axis_dim] = axis
    return NamedSharding(mesh, P(*spec))
