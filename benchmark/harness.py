"""The benchmark's own machinery: the manifest and the data files a cell is
made of, weights from the seed, compile and cache counters, the table of
peaks, the per-layer readers, and the decision on ``correct``. What a row is
(rows from the seed, sizes, samples, the plan's checks, the job's recipe)
belongs to the configuration's task, ``benchmark/tasks/<task>.py``.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric sits in a file of its own and is found by the name
``BENCHMARK.json`` gives it; this module is never edited to add one.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import threading
from typing import Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
PHASES = ("plan_solve", "aot_drain", "train", "speculate", "validate", "record", "probe",
          "sync_probe")
COMPILE_EVENT = "/jax/core/compile/backend_compile"
JOB_SEED_MOD = 2**26  # the program folds seed * 31 + epoch into an int32
CLASSIFIER_GAIN = 0.1  # the classifier's kernel against He-normal (see make_weights)


# ------------------------------------------------------------ data files


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(path: str = MANIFEST) -> dict:
    return _load_json(path)


def data_roots(manifest_path: Optional[str] = None) -> List[str]:
    """Where a cell's data files are looked for. A manifest other than the
    repository's (``tests/benchmark`` keeps one for its fixture cell) brings
    its own directory first: its ``traffic/``, ``limits/`` and
    ``layer_metrics/`` are found before this one's."""
    own = os.path.dirname(os.path.abspath(manifest_path or MANIFEST))
    return [HERE] if own == ROOT else [own, HERE]


def find_file(roots: Sequence[str], *parts: str) -> str:
    """The first root that holds ``parts``; the last root's path where none does."""
    paths = [os.path.join(root, *parts) for root in roots]
    return next((p for p in paths if os.path.isfile(p)), paths[-1])


def load_cell(workload: str, manifest: Optional[dict] = None,
              manifest_path: Optional[str] = None) -> dict:
    """The cell's entry with its configuration, traffic mix and limits."""
    manifest = manifest or load_manifest(manifest_path or MANIFEST)
    roots = data_roots(manifest_path)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; the manifest has {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = _load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = _load_json(find_file(roots, "traffic", cell["traffic"] + ".json"))
    limits = _load_json(find_file(roots, "limits", workload + ".json"))
    return {"cell": cell, "config": config, "traffic": traffic, "limits": limits,
            "manifest": manifest, "roots": roots}


def cell_metrics(manifest: dict, workload: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in manifest[kind] if workload in m.get("workloads", [workload])]


def peak_for(device_kind: str) -> dict:
    table = _load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"({sorted(table)}): add its published peaks with their source"
        )
    return table[device_kind]


def peak_bytes(memory_stats: Optional[dict]) -> int:
    """A device's memory peak as JAX reports it: the allocator's peak of live
    buffers plus the peak it reserved for programs' temporaries, which the
    TPU runtime counts apart (a program with 512 MiB of temporaries moves
    ``peak_bytes_reserved`` by 512 MiB and ``peak_bytes_in_use`` not at all:
    PERF.md, PR 23). 0 where the backend reports nothing."""
    stats = memory_stats or {}
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))


def job_argv(config: dict, traffic: dict, rehearsal: bool) -> List[str]:
    return list(config["rehearsal_argv" if rehearsal else "argv"]) + list(traffic["argv"])


def job_definition(config: dict, traffic: dict, sizes: dict, job_seed: int, task) -> dict:
    """What the reference needs to know of the job to follow its first epoch:
    the mix's workers, the seed, the learning rate, and the task's own keys."""
    if not traffic["one_chip"]:
        raise SystemExit(f"traffic mix {traffic['name']!r} spreads its workers over chips: the "
                         "reference draws the rows of workers that share one chip, and the "
                         "PR that brings such a mix brings the other draw")
    return {"world_size": traffic["world_size"], "seed": job_seed, "epoch": 0,
            "lr": config["lr"], **task.job_keys(config, sizes)}


# ------------------------------------------------- inputs from the seed


def make_weights(shapes, shardings, seed: int, init_std=None):
    """Every parameter drawn on the device in one jitted call from the seed,
    in the float32 the program keeps them in: He-normal convolution kernels,
    the classifier's kernel at a tenth of that (logits start near nought and
    the loss near ln(classes), as a job trained from scratch starts: from a
    loss of 4.4 the first steps are violent enough to carry a rounding-sized
    difference 25 times farther on one seed than on the next, PERF.md PR 23),
    scales around 1 and biases around 0 (not exactly 1 and 0, so that every
    leaf's gradient is exercised).

    ``init_std(path, shape)`` is a family's own rule (``reference/<family>.py``):
    the standard deviation of a leaf's draw (around 1 for a scale, around 0
    for any other leaf), or ``None`` for a leaf that keeps the rule above."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def draw(key):
        # one draw for the whole tree, cut into leaves: one small program
        sizes = [math.prod(s.shape) for _, s in flat]
        flat_noise = jax.random.normal(key, (sum(sizes),), jnp.float32)
        leaves, lo = [], 0
        for (path, s), size in zip(flat, sizes):
            kind = jax.tree_util.keystr(path)
            noise = flat_noise[lo:lo + size].reshape(s.shape)
            lo += size
            std = init_std(kind, s.shape) if init_std else None
            if std is not None:
                leaves.append((1.0 if "scale" in kind else 0.0) + std * noise)
            elif "kernel" in kind:
                fan_in = max(math.prod(s.shape[:-1]), 1)
                gain = CLASSIFIER_GAIN if len(s.shape) == 2 else 1.0
                leaves.append(noise * gain * math.sqrt(2.0 / fan_in))
            elif "scale" in kind:
                leaves.append(1.0 + 0.1 * noise)
            else:
                leaves.append(0.1 * noise)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31)
    return jax.jit(draw, out_shardings=shardings)(key)


# ---------------------------------------------------------------- counters


class Counters:
    """Process-wide backend-compile and persistent-cache counters
    (``jax.monitoring``): thread-summed compile seconds, compiles, cache hits
    and misses. After ``chip_smoke._Counters`` (PR 21)."""

    def __init__(self):
        from jax import monitoring

        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.compiles = self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float = 0.0, **_kw) -> None:
        if event.startswith(COMPILE_EVENT):
            with self._lock:
                self.compile_s += float(duration)
                self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"compile_s": self.compile_s, "compiles": self.compiles,
                    "cache_hits": self.hits, "cache_misses": self.misses}


# ------------------------------------------------------- per-layer readers


def read_layer_metric(name: str, ctx: dict, roots: Sequence[str] = (HERE,)) -> Optional[float]:
    """Run ``layer_metrics/<name>.py``'s ``read(ctx)``. A reader that finds
    nothing to read returns ``None`` and the metric is left out."""
    path = find_file(roots, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    value = module.read(ctx)
    return None if value is None else float(value)


def window_spans(ctx: dict, *names: str):
    """The graftscope spans of the given names that lie inside the window."""
    t0, t1 = ctx["window"]["t0"], ctx["window"]["t1"]
    return [s for s in ctx["spans"] if s[0] in names and s[2] >= t0 and s[2] + s[3] <= t1 + 1e-3]


# ------------------------------------------------------------- correct


def decide(compared: Dict[str, float], limits: Dict[str, float]) -> dict:
    """Each number beside its limit, and whether all hold. A number that is
    not finite, or a limit that is missing, fails."""
    table, ok = {}, True
    for name, limit in limits.items():
        value = compared.get(name)
        held = value is not None and math.isfinite(value) and value <= limit
        ok = ok and held
        table[name] = {"value": value, "limit": limit}
    return {"correct": bool(ok), "compared": table}
