"""Process-parallel XLA compile workers feeding the persistent cache.

The in-process AOT pool (runtime/compiler.py) overlaps compile jobs on
threads, but on XLA:CPU concurrent program compiles contend almost fully on
a shared resource in the emitter — jobs overlap 2x and stretch 2x, so
multi-program compile throughput never scales with cores (ROADMAP open
item, measured in PR 3). This module moves the backend compile itself into
subprocesses:

* The parent still traces and lowers (jitted callables close over live
  models and cannot cross a process boundary), then extracts a
  **self-contained lowering payload**: the StableHLO module as MLIR
  bytecode plus the exact serialized ``CompileOptions`` jax itself would
  build at ``lowered.compile()`` time (``pxla.create_compile_options`` with
  the arguments ``UnloadedMeshExecutable.from_hlo`` derives from
  ``compile_args`` — device assignment, SPMD flags, sharding-propagation
  masks, compiler-option kvs).
* A worker process deserializes the payload and compiles it through
  ``jax._src.compiler.compile_or_get_cached`` — the same entry point the
  parent's ``compile()`` uses — against the run's single pinned persistent
  compilation cache. The cache key is a pure function of (module bytes,
  serialized options, backend/version, XLA flags), all of which are
  byte-identical across the boundary (verified by the replay test), so the
  worker's compile lands in the cache under the key the parent will look
  up.
* The parent then replays ``lowered.compile()`` in-process: a **guaranteed
  persistent-cache hit** — deserialization, not compilation. Every
  process-level concern (executable registration, donation, dispatch)
  stays exactly the in-process path; the subprocess only pre-pays the
  expensive XLA emitter work, on its own core, with its own GIL.

A worker that dies or rejects a payload degrades that job to the
in-process path: the replay IS a full compile when the cache has no entry
(counted as ``worker_fallback`` and logged). Workers are spawned (never
forked — forking a live XLA runtime is undefined behavior) with the
parent's environment, so ``JAX_PLATFORMS`` / ``XLA_FLAGS`` (device counts!)
and the compile-cache placement carry over. Each worker opens the default
platform's backend, so the pool is a host-platform (CPU) facility: on a TPU
backend the chip belongs to the one process that holds it, and
``AOTCompileService`` refuses ``backend="process"`` at construction.

Each worker keeps its own graftscope span buffer (one ``worker_compile``
span per job, pid-tagged by the exporter) and writes it as a Chrome-trace
JSON next to the run trace on shutdown; ``graftscope summarize`` and the
engine's end-of-run save stitch those files into the run trace so compile
walls attribute across processes.
"""

from __future__ import annotations

import os
import pickle
import queue
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

_READY = "__ready__"
_POISON = None


def default_worker_count() -> int:
    """Process workers when the config leaves it at 0 (auto). Adaptive on
    many-core hosts (PR 5 follow-up): each worker is a full XLA runtime
    (~100 MB, ~2-4 s spawn), so small hosts keep the old one-per-core cap
    of 4, while hosts with cores to spare scale to half the cores capped at
    8 — the regime the process backend exists for (per-program compiles
    stop sharing an emitter once cores > concurrent programs)."""
    cpus = os.cpu_count() or 2
    if cpus <= 8:
        return max(1, min(4, cpus))
    return min(8, cpus // 2)


def ensure_persistent_cache() -> str:
    """Pin the run's persistent compilation cache (the channel worker
    compiles travel through) where ``compile_cache.enable_compile_cache``
    places it — the same directory every other entry point uses, never a
    temporary one. Floors are zeroed so small programs persist too.
    Returns the directory."""
    from jax._src import compilation_cache as _cc

    from dynamic_load_balance_distributeddnn_tpu.compile_cache import (
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache(min_compile_secs=0.0)
    os.makedirs(cache_dir, exist_ok=True)
    # jax memoizes BOTH the cache-used decision (_cache_checked) and the
    # cache object itself (_cache_initialized, possibly None when no dir
    # was configured yet) on the FIRST compile of the process; any
    # compile that ran before this configuration freezes the cache off
    # and every replay would silently recompile. Reset so the next
    # compile re-evaluates with the dir in place.
    stale_decision = _cc._cache_checked and not _cc._cache_used
    stale_cache = _cc._cache_initialized and _cc._cache is None
    if stale_decision or stale_cache:
        _cc.reset_cache()
    return cache_dir


def extract_lowering_payload(lowered) -> Optional[Dict[str, Any]]:
    """Self-contained compile job from a ``jax.stages.Lowered``: MLIR
    bytecode + the exact serialized ``CompileOptions`` the parent's own
    ``lowered.compile()`` will use (what ``pxla._cached_compilation`` builds
    from ``compile_args`` and the lowering's device list), so the worker's
    cache write and the parent's replay share one cache key. Returns None
    when the program cannot be offloaded (host callbacks, AUTO shardings,
    pmap-style replication, no concrete device list) — the caller then
    compiles in-process. Written against the one installed jax: a missing
    key or a changed private signature RAISES (the job fails and the AOT
    service counts it) instead of degrading in silence."""
    import numpy as np
    from jax._src.interpreters import mlir, pxla
    from jax._src.sharding_impls import AUTO

    lowering = lowered._lowering
    ca = lowering.compile_args
    if ca["host_callbacks"] or ca["ordered_effects"]:
        return None
    if int(ca["pmap_nreps"]) != 1:
        return None
    in_sh, out_sh = ca["in_shardings"], ca["out_shardings"]
    if any(isinstance(s, AUTO) for s in tuple(in_sh) + tuple(out_sh)):
        return None  # auto-SPMD keys depend on the solver's mesh choice
    da = lowering._device_list
    if da is None:
        return None  # fully abstract lowering: compile() needs a device list
    allow_in, allow_out = pxla.get_prop_to_input_output(in_sh, out_sh, 0)
    dev = np.vectorize(lambda i: da[i], otypes=[object])(np.arange(len(da)))
    module = lowering.stablehlo()
    options = pxla.create_compile_options(
        module,
        None,
        ca["spmd_lowering"],
        ca["tuple_args"],
        ca["auto_spmd_lowering"],
        allow_in,
        allow_out,
        ca["backend"],
        dev,
        ca["pmap_nreps"],
        dict(lowering._compiler_options_kvs),
    )
    return {
        "module": mlir.module_to_bytecode(module),
        "options": options.SerializeAsString(),
        "device_ids": [int(d.id) for d in da],
        "platform": ca["backend"].platform,
    }


def _worker_main(
    worker_id: int,
    job_q,
    ack_q,
    trace_path: Optional[str],
) -> None:
    """Worker process body. Spawned (fresh interpreter, the parent's
    environment): place the shared cache through the same helper the parent
    used BEFORE jax touches any backend — same environment, same directory
    — ack readiness once the (expensive) jax import is done, then drain
    jobs until the poison pill."""
    t_import = time.perf_counter()
    import numpy as np

    import jax

    from dynamic_load_balance_distributeddnn_tpu.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache(min_compile_secs=0.0)
    from jax._src import compiler as jax_compiler
    from jax._src import xla_bridge
    from jax._src.interpreters import mlir
    from jax._src.lib import xla_client as xc
    from jax._src.lib.mlir import ir

    from dynamic_load_balance_distributeddnn_tpu.obs.trace import Tracer

    tracer = Tracer(mode="on" if trace_path else "off")
    backend = xla_bridge.get_backend()
    by_id = {d.id: d for d in backend.local_devices()}
    # Pre-warm the compile stack BEFORE acking ready: a process's FIRST
    # compile pays one-time LLVM/autotune initialization (several seconds on
    # the CPU tier — comparable to a real program's compile). Folding it
    # into the spawn window means ready == "full-speed worker", and the
    # engine overlaps spawn with its own warm-up anyway. The dummy program
    # is unique per worker (worker_id in a constant) so it cannot shortcut
    # through a sibling's cache entry.
    try:
        import jax.numpy as jnp

        jax.jit(lambda x: (x * (2.0 + worker_id)).sum()).lower(  # graftlint: disable=G001
            jax.ShapeDtypeStruct((4, 4), jnp.float32)
        ).compile()
    except Exception:  # pragma: no cover - warm is best-effort
        pass
    ack_q.put((_READY, worker_id, time.perf_counter() - t_import, ""))
    try:
        while True:
            item = job_q.get()
            if item is _POISON:
                break
            job_id, name, blob = item
            t0 = time.perf_counter()
            err = ""
            try:
                payload = pickle.loads(blob)
                devs = tuple(by_id[i] for i in payload["device_ids"])
                dev = np.empty(len(devs), dtype=object)
                dev[:] = devs
                options = xc.CompileOptions.ParseFromString(payload["options"])
                with tracer.span(
                    "worker_compile", cat="compile", args={"key": name}
                ):
                    with mlir.make_ir_context() as ctx:
                        module = ir.Module.parse(payload["module"], context=ctx)
                        jax_compiler.compile_or_get_cached(
                            backend, module, dev, options, (),
                            xc.DeviceList(devs),
                        )
            except BaseException as e:  # noqa: BLE001 - reported via the ack
                err = repr(e)
            ack_q.put((job_id, worker_id, time.perf_counter() - t0, err))
    finally:
        if trace_path:
            try:
                tracer.save(trace_path)
            except OSError:
                pass


class CompileWorkerPool:
    """N spawn-based compile worker processes sharing one job queue.

    ``submit`` enqueues a job and returns a handle; ``wait`` blocks until
    that job's ack (or the pool is declared dead). The pool NEVER raises on
    worker failure — a job whose worker died resolves as failed and the
    caller's in-process replay compiles for real (the designed fallback).
    """

    def __init__(
        self,
        workers: int,
        trace_dir: Optional[str] = None,
        logger=None,
    ):
        import multiprocessing as mp

        self._workers = max(int(workers), 1)
        self._logger = logger
        self._ctx = mp.get_context("spawn")
        self._job_q = self._ctx.Queue()
        self._ack_q = self._ctx.Queue()
        self._procs: List = []
        self._trace_paths: List[str] = []
        self._lock = threading.Lock()
        self._events: Dict[str, threading.Event] = {}
        self._errors: Dict[str, str] = {}
        self._walls: Dict[str, float] = {}
        self._ready = threading.Event()
        self._all_ready = threading.Event()
        self._ready_count = 0
        self._dead = False
        self._startup_s: Optional[float] = None
        self._t_spawn = time.perf_counter()
        for i in range(self._workers):
            trace_path = None
            if trace_dir:
                # parent-pid tag: concurrent runs (multi-host, parallel
                # benches) sharing a trace_dir must not clobber each other's
                # worker files; the glob in scope_cli still matches
                trace_path = os.path.join(
                    trace_dir, f"compile_worker_{os.getpid()}_{i}.trace.json"
                )
                self._trace_paths.append(trace_path)
            p = self._ctx.Process(
                target=_worker_main,
                args=(i, self._job_q, self._ack_q, trace_path),
                daemon=True,
                name=f"aot-compile-worker-{i}",
            )
            p.start()
            self._procs.append(p)
        self._drain_thread = threading.Thread(
            target=self._drain_acks, name="aot-worker-acks", daemon=True
        )
        self._drain_thread.start()

    # ------------------------------------------------------------- internals

    def _drain_acks(self) -> None:
        last_alive = self._workers
        while True:
            try:
                job_id, worker_id, wall, err = self._ack_q.get(timeout=0.5)
            except queue.Empty:
                with self._lock:
                    if self._dead:
                        return
                alive = self.alive()
                if 0 < alive < last_alive:
                    # SOME worker died mid-job (OOM kill, segfault). The
                    # shared job queue cannot say which job it was holding,
                    # so resolve every outstanding job as failed — waiters
                    # fall back to in-process compiles instead of blocking
                    # forever on an ack that will never come. Jobs a live
                    # sibling is still compiling get compiled twice (worker
                    # + fallback): wasted background work, never a hang.
                    with self._lock:
                        pending = [
                            (jid, ev)
                            for jid, ev in self._events.items()
                            if not ev.is_set()
                        ]
                        for jid, ev in pending:
                            self._errors[jid] = "a worker died mid-job"
                            ev.set()
                    if self._logger is not None:
                        self._logger.warning(
                            f"compile worker died ({alive}/{self._workers} "
                            f"still alive); {len(pending)} outstanding "
                            "job(s) fall back to in-process compiles"
                        )
                    last_alive = alive
                    continue
                if not any(p.is_alive() for p in self._procs):
                    # every worker gone: resolve all outstanding jobs as
                    # failed so waiters fall back instead of hanging, and
                    # release wait_ready blockers NOW — a pool whose workers
                    # died at spawn (e.g. a __main__ that cannot be
                    # re-imported) must cost ~0, not one ready-timeout per
                    # job (wait_ready re-checks _dead and returns False)
                    with self._lock:
                        for jid, ev in self._events.items():
                            if not ev.is_set():
                                self._errors[jid] = "worker pool died"
                                ev.set()
                        self._dead = True
                        self._ready.set()
                        self._all_ready.set()
                        ready_count = self._ready_count
                    if self._logger is not None:
                        self._logger.warning(
                            f"compile worker pool died before serving any "
                            f"acks ({ready_count}/{self._workers} "
                            "workers reached ready); every job compiles "
                            "in-process — common cause: a __main__ the "
                            "spawned interpreter cannot re-import"
                        )
                    return
                continue
            except (EOFError, OSError):  # queue torn down at shutdown
                return
            if job_id == _READY:
                with self._lock:
                    self._ready_count += 1
                    if self._ready_count == 1:
                        # one live worker is enough to route jobs
                        self._startup_s = time.perf_counter() - self._t_spawn
                        self._ready.set()
                    if self._ready_count >= self._workers:
                        self._all_ready.set()
                continue
            with self._lock:
                ev = self._events.get(job_id)
                if ev is None:
                    # late ack for a job already resolved (e.g. failed over
                    # after a sibling worker died) — drop it, don't grow the
                    # error/wall maps unboundedly
                    continue
                self._errors[job_id] = err
                self._walls[job_id] = wall
                ev.set()

    # ------------------------------------------------------------ public API

    def wait_ready(self, timeout: float = 120.0, all_workers: bool = False) -> bool:
        """Block until at least one worker finished its jax import (spawn +
        import is the pool's fixed cost, ~3-8 s/worker on the CPU tier).
        ``all_workers=True`` waits for the FULL pool, so that late-importing
        workers don't contend with the jobs that follow.
        Returns False (immediately, not after the timeout) when the pool
        died before enough workers acked ready."""
        ev = self._all_ready if all_workers else self._ready
        ok = ev.wait(timeout)
        with self._lock:
            need = self._workers if all_workers else 1
            if self._dead and self._ready_count < need:
                return False
        return ok

    @property
    def startup_s(self) -> Optional[float]:
        with self._lock:
            return self._startup_s

    def alive(self) -> int:
        return sum(1 for p in self._procs if p.is_alive())

    def submit(self, name: str, payload: Dict[str, Any]) -> str:
        """Queue one compile job; returns its id (pass to :meth:`wait`)."""
        job_id = uuid.uuid4().hex
        ev = threading.Event()
        with self._lock:
            if self._dead:
                self._errors[job_id] = "worker pool died"
                ev.set()
                self._events[job_id] = ev
                return job_id
            self._events[job_id] = ev
        self._job_q.put((job_id, name, pickle.dumps(payload)))
        return job_id

    def wait(self, job_id: str, timeout: Optional[float] = None) -> Tuple[bool, str]:
        """(ok, error) for one job. ``ok=False`` means the caller's replay
        must compile in-process (worker failed/died/timed out)."""
        with self._lock:
            ev = self._events.get(job_id)
        if ev is None:
            return False, "unknown job"
        if not ev.wait(timeout):
            return False, "timeout"
        with self._lock:
            err = self._errors.pop(job_id, "")
            self._events.pop(job_id, None)
            self._walls.pop(job_id, None)
        return (err == ""), err

    def trace_paths(self) -> List[str]:
        """Worker trace files that exist on disk (written at shutdown)."""
        return [p for p in self._trace_paths if os.path.exists(p)]

    def shutdown(self, timeout: float = 5.0) -> None:
        with self._lock:
            if self._dead:
                # resolve stragglers; processes may already be gone
                for ev in self._events.values():
                    ev.set()
            dead = self._dead
            self._dead = True
            # release any wait_ready blockers (they re-check _dead)
            self._ready.set()
            self._all_ready.set()
        if not dead:
            for _ in self._procs:
                try:
                    self._job_q.put(_POISON)
                except (ValueError, OSError):
                    break
        for p in self._procs:
            p.join(timeout)
        for p in self._procs:
            if p.is_alive():
                p.terminate()
                p.join(1.0)
        # unblock any waiters that raced the shutdown
        with self._lock:
            for jid, ev in self._events.items():
                if not ev.is_set():
                    self._errors[jid] = "worker pool shut down"
                    ev.set()
