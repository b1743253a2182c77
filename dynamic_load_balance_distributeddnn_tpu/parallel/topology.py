"""Worker topology: logical workers mapped onto physical devices.

The reference forks one OS process per worker and pins each to a GPU from the
``-gpu`` list — several workers may share a card, which is how the README's
canonical 3:1 straggler profile arises (`0,0,0,1`: three workers contend on
GPU 0, dbs.py:518-520, README.md:28). Here the same idea is a pure mapping:
``world_size`` logical workers assigned to the mesh's devices. Workers that
share a device have their step computations dispatched back-to-back and the
XLA runtime serializes them on that chip — contention by construction, no
processes involved.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple


def factor_hosts(devices: Sequence, requested: int = 0) -> Optional[int]:
    """Two-level ICI/DCN factorization of a mesh-ordered device list: the
    host-group count H such that ``devices`` splits into H equal contiguous
    blocks, each living on one host — the precondition for
    ``parallel/mesh.py hier_mesh`` (row k = host k's chips, row-major device
    order identical to the flat mesh).

    ``requested > 0`` forces a SYNTHETIC factorization (single-process CPU
    tiers, tests — there is no real DCN but the
    collective structure is exercised end to end). Returns None when no
    usable two-level structure exists (fewer than two groups, uneven or
    non-contiguous host blocks) — the caller falls back to the flat
    combine."""
    n = len(devices)
    if requested:
        if requested < 2 or requested > n or n % requested:
            return None
        return int(requested)
    by_proc: Dict[int, List[int]] = {}
    for i, d in enumerate(devices):
        by_proc.setdefault(int(getattr(d, "process_index", 0)), []).append(i)
    if len(by_proc) < 2:
        return None  # one host: no DCN link to shorten
    sizes = {len(v) for v in by_proc.values()}
    if len(sizes) != 1:
        return None  # ragged hosts cannot form a rectangular axis
    for idxs in by_proc.values():
        if idxs != list(range(idxs[0], idxs[0] + len(idxs))):
            return None  # host blocks must be contiguous in mesh order
    return len(by_proc)


def parse_hier_levels(spec: str) -> Tuple[Tuple[str, int], ...]:
    """Parse a declared topology spec (``--hier_levels host:4,rack:2``) into
    ``((name, size), ...)`` outermost-first. Raises ValueError on malformed
    entries — the config validator calls this so a typo dies at parse time,
    not at mesh-build time."""
    levels: List[Tuple[str, int]] = []
    seen = set()
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if ":" not in part:
            raise ValueError(
                f"hier_levels entry {part!r} must be name:size (e.g. host:4)"
            )
        name, _, size_s = part.partition(":")
        name = name.strip()
        try:
            size = int(size_s)
        except ValueError:
            raise ValueError(f"hier_levels size {size_s!r} is not an integer")
        if not name or name in seen:
            raise ValueError(f"hier_levels names must be unique, got {name!r}")
        if size < 2:
            raise ValueError(f"hier_levels size for {name!r} must be >= 2")
        seen.add(name)
        levels.append((name, size))
    return tuple(levels)


@dataclasses.dataclass(frozen=True)
class TopologyTree:
    """An N-level factorization of the mesh-ordered device list into nested
    contiguous groups — the structure the tree collective walks (ISSUE 17,
    after DynamiQ's multi-hop all-reduce).

    ``levels`` is ``((name, size), ...)`` OUTERMOST-first: ``levels[0]`` is
    the slowest link class (the one compressed hardest), the last level the
    fastest (in-host ICI; its hop always runs at fp32). The product of every
    level's size times the implicit innermost remainder equals the device
    count; ``tree_mesh`` reshapes devices row-major so the flat device
    numbering (and every per-device rng fold) is unchanged vs the flat mesh.

    Three ways to get one:

    * ``declared(spec, n)`` — the ``--hier_levels host:4,rack:2`` string;
    * ``from_process_topology(devices, requested)`` — the PR-12 two-level
      host/device split (real process blocks, or a synthetic
      ``--hier_hosts`` count);
    * ``learned(probe)`` — cluster a bandwidth probe's per-level bytes/s and
      merge adjacent levels whose measured rates are indistinguishable (the
      structure was not worth a hop).

    ``restrict(n)`` re-derives the tree over a survivor count at an elastic
    re-shard: outer levels that still divide the fleet are kept, levels that
    no longer fit are dropped (absorbed into their inner neighbour), so a
    churned fleet keeps whatever hierarchy remains instead of the old
    all-or-nothing equal-host-blocks-or-flat fallback."""

    levels: Tuple[Tuple[str, int], ...]  # outermost-first, innermost LAST

    def __post_init__(self):
        if len(self.levels) < 2:
            raise ValueError("TopologyTree needs >= 2 levels (else run flat)")
        names = [n for n, _ in self.levels]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate level names: {names}")
        for name, size in self.levels:
            if size < 2:
                raise ValueError(f"level {name!r} size {size} < 2")

    # ------------------------------------------------------------ accessors

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.levels)

    @property
    def sizes(self) -> Tuple[int, ...]:
        return tuple(s for _, s in self.levels)

    @property
    def n_devices(self) -> int:
        n = 1
        for _, s in self.levels:
            n *= s
        return n

    def key(self) -> Tuple:
        """Hashable identity for signatures/registry keys."""
        return tuple(self.levels)

    # --------------------------------------------------------- construction

    @classmethod
    def declared(cls, spec: str, n_devices: int) -> Optional["TopologyTree"]:
        """Build from a ``--hier_levels`` string over ``n_devices``. The
        declared levels are OUTER levels; the innermost "device" level is
        implicit and absorbs the remainder. Returns None when the declared
        product does not divide the device count (the caller logs and runs
        flat) — a malformed string raises instead (config bug, not fleet
        shape)."""
        declared = parse_hier_levels(spec)
        if not declared:
            return None
        outer = 1
        for _, s in declared:
            outer *= s
        if outer > n_devices or n_devices % outer:
            return None
        remainder = n_devices // outer
        if remainder >= 2:
            inner_name = "device" if "device" not in {n for n, _ in declared} else "chip"
            levels = declared + ((inner_name, remainder),)
        else:
            levels = declared
        if len(levels) < 2:
            return None
        return cls(levels)

    @classmethod
    def from_process_topology(
        cls, devices: Sequence, requested: int = 0
    ) -> Optional["TopologyTree"]:
        """The PR-12 two-level host/device split: real contiguous process
        blocks, or a synthetic ``requested`` host count (``--hier_hosts``)."""
        hosts = factor_hosts(devices, requested)
        if hosts is None:
            return None
        per = len(devices) // hosts
        if per < 2:
            # one device per "host": a single level — no tree to walk
            return None
        return cls((("host", hosts), ("device", per)))

    @classmethod
    def learned(
        cls,
        candidate: "TopologyTree",
        level_bytes_per_s: Sequence[float],
        merge_ratio: float = 2.0,
    ) -> Optional["TopologyTree"]:
        """Cluster a candidate tree's levels by MEASURED per-level link rate
        (``probe_link_bandwidth``'s ``level_bytes_per_s``, outermost-first):
        adjacent levels whose rates are within ``merge_ratio`` of each other
        are the same link class — the extra hop buys no codec distinction, so
        they merge (sizes multiply, the faster neighbour's name wins). Rates
        that are unmeasured/non-positive inhibit merging (keep the declared
        structure rather than guess). Returns None when everything merges
        into one level (a symmetric fabric — run flat)."""
        if len(level_bytes_per_s) != len(candidate.levels):
            raise ValueError("one measured rate per candidate level")
        merged: List[Tuple[str, int, float]] = []
        for (name, size), rate in zip(candidate.levels, level_bytes_per_s):
            r = float(rate) if rate and rate > 0 else 0.0
            if merged:
                pname, psize, prate = merged[-1]
                if prate > 0 and r > 0 and max(prate, r) / min(prate, r) < merge_ratio:
                    # same link class: collapse the hop (inner name wins —
                    # it is the axis the combined level actually spans)
                    merged[-1] = (name, psize * size, max(prate, r))
                    continue
            merged.append((name, size, r))
        if len(merged) < 2:
            return None
        return cls(tuple((n, s) for n, s, _ in merged))

    # -------------------------------------------------------------- elastic

    def restrict(self, n_devices: int) -> Optional["TopologyTree"]:
        """Re-derive the tree over a survivor fleet: walk outermost-to-
        innermost keeping every level whose size still divides the remaining
        device count; a level that no longer fits is dropped (its structure
        is gone from the fleet). The innermost kept level absorbs whatever
        quotient remains. Returns None when fewer than two levels survive —
        the caller falls back to the flat combine."""
        if n_devices < 4:
            return None
        kept: List[Tuple[str, int]] = []
        remaining = n_devices
        for name, size in self.levels[:-1]:
            if remaining % size == 0 and remaining // size >= 2:
                kept.append((name, size))
                remaining //= size
        if remaining >= 2:
            inner_name = self.levels[-1][0]
            if any(n == inner_name for n, _ in kept):
                inner_name = inner_name + "_r"
            kept.append((inner_name, remaining))
        if len(kept) < 2:
            return None
        return TopologyTree(tuple(kept))


@dataclasses.dataclass(frozen=True)
class WorkerTopology:
    world_size: int
    devices: Tuple  # jax devices, mesh order
    worker_device: Tuple[int, ...]  # worker rank -> index into devices

    @classmethod
    def build(cls, world_size: int, devices: Sequence, device_ids: Sequence[int]) -> "WorkerTopology":
        if len(device_ids) != world_size:
            raise ValueError("device_ids must have one entry per worker")
        n = len(devices)
        ids = tuple(d % n for d in device_ids)
        return cls(world_size=world_size, devices=tuple(devices), worker_device=ids)

    @classmethod
    def round_robin(cls, world_size: int, devices: Sequence) -> "WorkerTopology":
        return cls.build(world_size, devices, [r % len(devices) for r in range(world_size)])

    def device_of(self, rank: int):
        return self.devices[self.worker_device[rank]]

    @property
    def groups(self) -> Dict[int, List[int]]:
        """device index -> workers on it, in dispatch (rank) order."""
        g: Dict[int, List[int]] = {}
        for r, d in enumerate(self.worker_device):
            g.setdefault(d, []).append(r)
        return g

    @property
    def used_device_indices(self) -> List[int]:
        return sorted(self.groups.keys())

    @property
    def one_worker_per_device(self) -> bool:
        return self.world_size == len(self.devices) and len(self.groups) == self.world_size

    @property
    def single_group(self) -> bool:
        """Every logical worker lives on ONE device (the reference's full
        contention map, -gpu 0,0,0,0). This is the topology where a per-step
        cross-worker gradient combine is local to one chip, so the elastic
        superstep scan (train/steps.py) can carry the optimizer update inside
        one compiled window and stay bitwise-identical to per-step dispatch."""
        return len(self.groups) == 1

    def group_shape_key(self, padded_batches: Sequence[int], window: int) -> Tuple:
        """Cache identity of one device group's superstep executable:
        (window length, each worker's bucketed batch in dispatch order).
        The engine's compile-once sentinel keys on this."""
        return (int(window),) + tuple(int(b) for b in padded_batches)

    def contention_factor(self, rank: int) -> int:
        """How many workers share this worker's device."""
        return len(self.groups[self.worker_device[rank]])
