"""Async AOT compile service: the warm path off the critical path.

The engine's old warm-start compiled its bucket-ladder executables by
*executing dummy steps* — allocate a zero batch, ``device_put`` five arrays,
dispatch, ``block_until_ready`` — serially, one rung at a time
(engine._warm_shapes, kept behind ``--aot_warm off`` as the A/B reference and
flagged by graftlint G007). On short benchmark runs that warm wall dominated.

This service compiles the same executables ahead of time:

* ``jit(fn).lower(abstract_args).compile()`` — **no dummy execution, no
  host→device traffic**. Data arguments are :class:`jax.ShapeDtypeStruct`
  specs (shape + dtype + committed sharding); parameter/state trees are
  passed as the live arrays (zero-copy — ``lower`` only reads avals, and a
  concrete leaf carries its exact weak-type/committed-ness, which a spec
  cannot express).
* compile jobs run **concurrently** on a small thread pool — XLA releases
  the GIL during backend compile — with a **single-flight lowering lock**:
  tracing/lowering is GIL-bound Python, so at most one job traces while the
  others sit in backend compile. The pool becomes a software pipeline
  (trace job k+1 under job k's compile) instead of a GIL convoy.
* jobs are **deduped by key**: submitting an already-submitted key returns
  the existing future, so N workers sharing a device (or a warm pass racing
  a speculative compile) never trigger N backend compiles of one program.

In jax 0.4.x an AOT ``Compiled`` does *not* populate the lazy ``jit``
call cache, so the service is also the **executable registry**: the engine
resolves its hot dispatch through :meth:`get` and calls the ``Compiled``
object directly (same HLO, same donation semantics — bitwise-identical to
the lazy path; dispatch overhead is within a few microseconds of the C++
jit cache). A key the service doesn't hold falls back to the lazy wrapper.

Compile events raised by pool threads carry the :data:`AOT_THREAD_PREFIX`
thread name, which analysis/guards.py uses to keep background compiles out
of the engine's recompile sentinel (they are deliberate, overlapped work,
not a shape falling off the ladder) while still counting them in budgets
opened with ``include_background=True``.

``backend="process"`` additionally routes the backend-compile phase of
each job to subprocess workers (runtime/compile_worker.py): the pool
thread lowers, ships the serialized (StableHLO, CompileOptions) payload to
a worker, and — once the worker has compiled it into the run's pinned
persistent cache — replays ``lowered.compile()`` in-process as a
guaranteed cache hit. In-process concurrent compiles contend ~fully on a
shared resource in the XLA:CPU emitter (jobs overlap 2x but stretch 2x);
worker processes each own an emitter, so multi-program compile throughput
finally scales with cores (a CPU-tier reading; no chip run). A worker that
dies or rejects a payload costs nothing: the replay compiles in-process,
exactly the ``backend="thread"`` behavior.
"""

from __future__ import annotations

import collections
import concurrent.futures
import os
import threading
import time
import weakref
from typing import Callable, Deque, Dict, Hashable, List, Optional, Sequence, Tuple

# Thread-name prefix for the compile pool — defined in analysis/guards.py
# (the consumer that matches it to attribute backend-compile events to
# background AOT work) and imported here so the two can never drift.
from dynamic_load_balance_distributeddnn_tpu.analysis.guards import (
    AOT_THREAD_PREFIX,
)
from dynamic_load_balance_distributeddnn_tpu.obs.scopes import record_program
from dynamic_load_balance_distributeddnn_tpu.obs.trace import get_tracer


def default_pool_size() -> int:
    """Pool width when the config leaves it at 0 (auto): enough to keep the
    backend compiler busy without convoying tracing threads on the GIL.

    Adaptive on many-core hosts (PR 5 follow-up): the old fixed ``min(8,
    cpus)`` left a 56-core TPU host's compile throughput capped at 8 while
    the warm universe holds dozens of programs. Scale with ~3/4 of the
    cores (the rest keep the controller thread, transfer pipeline and
    allocator responsive), capped at 16 — beyond that, concurrent XLA:CPU
    program compiles contend on shared emitter state instead of speeding
    up (seen as a plateau on the CPU tier; no chip run)."""
    cpus = os.cpu_count() or 2
    return max(2, min(16, (cpus * 3) // 4))


# Ceiling on one worker job's wall (submit -> ack). Generous: the slowest
# single program observed (DenseNet-121 on the CPU tier) compiles in
# minutes, not tens of minutes — hitting this means a wedged worker, and
# the job falls back to an in-process compile instead of hanging the pool
# thread (and the engine's drain barrier) forever.
WORKER_JOB_TIMEOUT_S = float(os.environ.get("GRAFT_WORKER_JOB_TIMEOUT_S", 1800))

# Live pools, drained at interpreter shutdown. The hook registers with
# threading._register_atexit — the same internal mechanism
# concurrent.futures uses — which runs BEFORE the interpreter joins
# non-daemon threads, so it can still cancel queued jobs.
_live_pools: "weakref.WeakSet[_CompilePool]" = weakref.WeakSet()
_exit_hook_installed = False


def _drain_pools_at_exit() -> None:
    for pool in list(_live_pools):
        pool.shutdown(drop_pending=True)


def _install_exit_hook() -> None:
    global _exit_hook_installed
    if _exit_hook_installed:
        return
    _exit_hook_installed = True
    try:
        threading._register_atexit(_drain_pools_at_exit)  # 3.9+
    except AttributeError:  # pragma: no cover - very old Python
        import atexit

        atexit.register(_drain_pools_at_exit)


class _CompilePool:
    """Minimal fixed-size worker pool tuned for XLA compile jobs.

    Threads are NON-daemon: a thread killed mid-backend-compile at
    interpreter exit segfaults or std::terminates inside XLA (measured), so
    in-flight compiles must be allowed to finish. The exit hook above
    cancels everything still QUEUED, so process exit waits for at most one
    in-flight compile per worker instead of the whole backlog (the failure
    mode ThreadPoolExecutor's exit join has: it drains the entire queue)."""

    def __init__(self, workers: int, name_prefix: str):
        self._cv = threading.Condition()
        self._items: Deque = collections.deque()
        self._stop = False
        _install_exit_hook()
        _live_pools.add(self)
        self._threads = [
            threading.Thread(
                target=self._run, name=f"{name_prefix}-{i}", daemon=False
            )
            for i in range(workers)
        ]
        for t in self._threads:
            t.start()

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._items and not self._stop:
                    self._cv.wait()
                if self._items:
                    fut, fn, args = self._items.popleft()
                elif self._stop:
                    return
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(fn(*args))
            except BaseException as e:  # noqa: BLE001 - delivered via the future
                fut.set_exception(e)

    def submit(self, fn, *args) -> concurrent.futures.Future:
        fut: concurrent.futures.Future = concurrent.futures.Future()
        with self._cv:
            if self._stop:
                fut.cancel()
                return fut
            self._items.append((fut, fn, args))
            self._cv.notify()
        return fut

    def shutdown(self, drop_pending: bool = False) -> None:
        with self._cv:
            self._stop = True
            if drop_pending:
                for fut, _fn, _args in self._items:
                    fut.cancel()
                self._items.clear()
            self._cv.notify_all()


class AOTCompileService:
    """Concurrent ahead-of-time compiler + compiled-executable registry.

    ``workers``: pool width (0 = :func:`default_pool_size`). The pool is
    created lazily on the first ``submit`` — a service used only for
    ``compile_now`` never spawns a thread.

    ``backend``: ``"thread"`` (in-process backend compiles, the default) or
    ``"process"`` (backend compiles run in subprocess workers feeding the
    persistent cache; the in-process step becomes a cache-hit replay — see
    runtime/compile_worker.py). ``process_workers``: subprocess count
    (0 = auto). The worker pool spawns lazily with the thread pool and is
    shared by every job; any worker-side failure degrades that one job to
    an in-process compile.

    ``tick``: optional callback invoked after every finished compile job
    (the engine passes the watchdog heartbeat, so a long TPU compile ladder
    keeps answering the stall watchdog the way the execute-to-compile warm
    loop used to).
    """

    def __init__(
        self,
        workers: int = 0,
        logger=None,
        tick: Optional[Callable[[], None]] = None,
        backend: str = "thread",
        process_workers: int = 0,
        trace_dir: Optional[str] = None,
        release_caches: bool = False,
    ):
        if backend not in ("thread", "process"):
            raise ValueError(f"backend must be 'thread' or 'process', got {backend!r}")
        self._release_caches = bool(release_caches)
        if backend == "process":
            import jax

            if jax.default_backend() == "tpu":
                # a chip belongs to one process: each compile worker opens
                # the default platform's backend, i.e. the chip THIS process
                # holds, and would fail or hang there. Say so at start-up
                # rather than degrade to in-process compiles in silence.
                raise RuntimeError(
                    "aot_backend='process' is not available on a TPU "
                    "backend: the chip belongs to this process and a compile "
                    "worker that opens it fails or hangs; use the default "
                    "aot_backend='thread'"
                )
        self._backend = backend
        self._process_workers = int(process_workers)
        self._trace_dir = trace_dir
        self._worker_pool = None  # CompileWorkerPool, spawned lazily
        self._worker_pool_closed = False
        if backend == "process" and not int(workers):
            # keep the workers fed: while worker k compiles job i, thread
            # k should already be lowering job i+1
            from dynamic_load_balance_distributeddnn_tpu.runtime.compile_worker import (
                default_worker_count,
            )

            workers = (self._process_workers or default_worker_count()) + 1
        self._workers = int(workers) or default_pool_size()
        self._logger = logger
        self._tick = tick
        self._pool: Optional[_CompilePool] = None
        self._lock = threading.Lock()
        # Single-flight lowering: tracing is GIL-bound Python; serializing it
        # across jobs turns the pool into a lower/compile pipeline instead of
        # a GIL convoy (measured 2x on the 2-core CPU tier vs naive pooling).
        self._lower_lock = threading.Lock()
        self._jobs: Dict[Hashable, concurrent.futures.Future] = {}
        self._done: Dict[Hashable, object] = {}  # key -> jax.stages.Compiled
        self._stats = {
            "submitted": 0,
            "deduped": 0,
            "compiled": 0,
            "failed": 0,
            "speculative": 0,
            "compile_wall_s": 0.0,
            # process-backend accounting: jobs whose backend compile ran in
            # a worker (replay hit the persistent cache) vs jobs that fell
            # back to a real in-process compile
            "worker_compiled": 0,
            "worker_fallback": 0,
        }

    # ------------------------------------------------------------- internals

    def _ensure_pool_locked(self) -> _CompilePool:
        if self._pool is None:
            self._pool = _CompilePool(self._workers, AOT_THREAD_PREFIX)
        return self._pool

    def _ensure_worker_pool(self):
        """Spawn the subprocess worker pool on first use (process backend).
        Returns the pool, or None on the thread backend and after
        :meth:`close` (which forbids a respawn). A spawn that fails raises
        into the job that asked for it — counted in ``stats()["failed"]``."""
        if self._backend != "process":
            return None
        with self._lock:
            if self._worker_pool is not None or self._worker_pool_closed:
                return self._worker_pool
        from dynamic_load_balance_distributeddnn_tpu.runtime.compile_worker import (
            CompileWorkerPool,
            default_worker_count,
            ensure_persistent_cache,
        )

        ensure_persistent_cache()
        pool = CompileWorkerPool(
            self._process_workers or default_worker_count(),
            trace_dir=self._trace_dir,
            logger=self._logger,
        )
        with self._lock:
            if self._worker_pool is None:
                self._worker_pool = pool
                return pool
        pool.shutdown()  # lost the race to a concurrent spawner
        with self._lock:
            return self._worker_pool

    def _offload_to_worker(self, key: Hashable, lowered, tr, key_args) -> None:
        """Process backend: ship the lowered program to a worker and wait
        for its cache write. A worker that dies, times out or rejects the
        payload costs only that job's offload: the caller's replay compiles
        in-process, logged and counted as ``worker_fallback``."""
        from dynamic_load_balance_distributeddnn_tpu.runtime.compile_worker import (
            extract_lowering_payload,
        )

        pool = self._ensure_worker_pool()
        ok = False
        if pool is not None and pool.wait_ready():
            payload = extract_lowering_payload(lowered)
            if payload is not None:
                with tr.span("aot_worker_wait", cat="compile", args=key_args):
                    job_id = pool.submit(repr(key), payload)
                    # bounded wait: the pool resolves lost jobs when it sees
                    # a worker die, but a wedged (not dead) worker would
                    # otherwise hang this pool thread — and with it the
                    # engine's pre-wall drain barrier — forever
                    ok, err = pool.wait(job_id, timeout=WORKER_JOB_TIMEOUT_S)
                if not ok and self._logger is not None:
                    self._logger.warning(
                        f"compile worker failed for {key}: {err} — "
                        "compiling in-process"
                    )
        with self._lock:
            self._stats["worker_compiled" if ok else "worker_fallback"] += 1

    def _compile_job(self, key: Hashable, fn, args: Sequence):
        t0 = time.perf_counter()
        # graftscope compile track: lower vs backend-compile spans, tagged
        # by pool thread (thread name) and dedup key — the view the PR-3
        # compile-worker-contention question needs. The key is stringified
        # lazily only when tracing is on (span args stay JSON-safe).
        tr = get_tracer()
        key_args = {"key": repr(key)} if tr.enabled else None
        try:
            with self._lower_lock:
                with tr.span("aot_lower", cat="compile", args=key_args):
                    lowered = fn.lower(*args)
            if self._backend == "process":
                # worker pre-pays the XLA emitter work into the persistent
                # cache; the compile() below is then a deserialization
                self._offload_to_worker(key, lowered, tr, key_args)
            with tr.span("aot_compile", cat="compile", args=key_args):
                compiled = lowered.compile()
            # graftscope's scope map (obs/scopes.py): one line per program,
            # written here so that it is on disk before the program first
            # runs; nothing is parsed or written with the tracer off
            record_program(key, compiled)
        except BaseException:
            with self._lock:
                self._stats["failed"] += 1
            raise
        finally:
            if self._tick is not None:
                try:
                    self._tick()
                except Exception:  # pragma: no cover - heartbeat must not kill jobs
                    pass
        with self._lock:
            self._done[key] = compiled
            self._stats["compiled"] += 1
            self._stats["compile_wall_s"] += time.perf_counter() - t0
        return compiled

    # ------------------------------------------------------------ public API

    def submit(
        self, key: Hashable, fn, args: Sequence, speculative: bool = False
    ) -> concurrent.futures.Future:
        """Queue one AOT compile; dedup by ``key``.

        ``fn`` is a jitted callable, ``args`` its lowering arguments
        (ShapeDtypeStruct specs and/or live arrays). Returns the job's
        future; a key submitted before (in flight, done, or failed) returns
        the existing future without queueing anything.
        """
        with self._lock:
            fut = self._jobs.get(key)
            if fut is not None:
                self._stats["deduped"] += 1
                return fut
            pool = self._ensure_pool_locked()
            self._stats["submitted"] += 1
            if speculative:
                self._stats["speculative"] += 1
            fut = pool.submit(self._compile_job, key, fn, args)
            self._jobs[key] = fut
            return fut

    def compile_now(self, key: Hashable, fn, args: Sequence):
        """Blocking compile with the same dedup table as :meth:`submit`.

        A fresh key compiles INLINE on the caller thread (no pool, no queue
        delay — this is the path for one-off executables like the fused
        sync/FLOPs probes); a key already in flight joins that job instead.
        """
        with self._lock:
            fut = self._jobs.get(key)
            if fut is None:
                fut = concurrent.futures.Future()
                self._jobs[key] = fut
                self._stats["submitted"] += 1
                inline = True
            else:
                self._stats["deduped"] += 1
                inline = False
        if not inline:
            return fut.result()
        # Borrow the AOT thread-name prefix for the inline job so guards
        # attributes its backend-compile events as deliberate AOT work —
        # same classification as pool jobs (one compile must not read as a
        # foreground recompile to the sentinel just because it ran inline).
        me = threading.current_thread()
        saved = me.name
        me.name = AOT_THREAD_PREFIX + "-inline"
        try:
            compiled = self._compile_job(key, fn, args)
        except BaseException as e:
            fut.set_exception(e)
            raise
        finally:
            me.name = saved
        fut.set_result(compiled)
        return compiled

    def has(self, key: Hashable) -> bool:
        """Key known (queued, compiling, done, or failed)?"""
        with self._lock:
            return key in self._jobs

    def get(self, key: Hashable):
        """Finished ``Compiled`` for ``key``, or None (absent / in flight /
        failed). Non-blocking — the dispatch-time resolution path."""
        # deliberately lock-free: this sits on the per-step dispatch path;
        # dict.get is GIL-atomic and a racy miss only means one lazy-jit
        # fallback dispatch (bitwise-identical), never a wrong executable
        return self._done.get(key)  # graftlint: disable=G012

    def wait(
        self,
        keys: Optional[Sequence[Hashable]] = None,
        timeout: Optional[float] = None,
    ) -> List[Tuple[Hashable, BaseException]]:
        """Barrier: block until the given keys (default: every submitted job)
        finish. Returns ``(key, exception)`` pairs for failed jobs — the
        caller logs them and falls back to lazy dispatch; the failed key
        stays in the dedup table so it is not endlessly retried."""
        with self._lock:
            if keys is None:
                pending = list(self._jobs.items())
            else:
                pending = [(k, self._jobs[k]) for k in keys if k in self._jobs]
        deadline = None if timeout is None else time.monotonic() + timeout
        failures: List[Tuple[Hashable, BaseException]] = []
        for key, fut in pending:
            left = None if deadline is None else max(deadline - time.monotonic(), 0.0)
            try:
                fut.result(timeout=left)
            except concurrent.futures.TimeoutError:
                raise
            except BaseException as e:
                failures.append((key, e))
        return failures

    def failed(self, key: Hashable) -> bool:
        """Did ``key``'s job finish with an exception? Failed keys stay in
        the dedup table (never retried) and ``get`` returns None for them
        forever — callers that gate on readiness (the online controller's
        warm gate) must distinguish 'still compiling' from 'will never
        arrive', or one failed candidate compile would defer every switch
        for the rest of the run."""
        with self._lock:
            fut = self._jobs.get(key)
        if fut is None or not fut.done():
            return False
        return fut.exception() is not None

    def pending(self) -> int:
        with self._lock:
            return sum(1 for f in self._jobs.values() if not f.done())

    def keys(self) -> List[Hashable]:
        with self._lock:
            return list(self._jobs)

    def count_keys(self, name_prefixes: Tuple[str, ...]) -> int:
        """Compiled executables whose key[0] starts with one of the given
        names — e.g. the superstep variants for the engine's compile-once
        cross-check."""
        with self._lock:
            return sum(
                1
                for k in self._done
                if isinstance(k, tuple)
                and k
                and isinstance(k[0], str)
                and k[0].startswith(name_prefixes)
            )

    def stats(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._stats)

    def worker_trace_paths(self) -> List[str]:
        """Chrome-trace files written by compile workers (process backend;
        available after close/shutdown — workers save at exit). The engine
        stitches these into the run trace (obs/trace.py merge_trace_files)."""
        with self._lock:
            pool = self._worker_pool
        return pool.trace_paths() if pool is not None else []

    def flush_workers(self) -> List[str]:
        """Shut down the subprocess workers so they write their graftscope
        trace files (saved at worker exit), and return the written paths.
        The service stays usable: later jobs degrade to in-process compiles
        (the thread-backend behavior) — intended only at end of run, before
        the engine saves and stitches the run trace."""
        with self._lock:
            pool = self._worker_pool
        if pool is None:
            return []
        pool.shutdown()
        return pool.trace_paths()

    def close(self, wait: bool = True) -> None:
        with self._lock:
            pool, self._pool = self._pool, None
            # keep _worker_pool set while pending jobs drain (they still
            # offload to live workers), but forbid a respawn: a drain-time
            # job racing _ensure_worker_pool must not spin up a fresh pool
            # that close() would then leak
            self._worker_pool_closed = True
            wpool = self._worker_pool
        if pool is not None:
            pool.shutdown(drop_pending=not wait)
            if wait:
                self.wait()
        if wpool is not None:
            # after the drain: workers idle, shut them down (writes their
            # trace files); the handle stays for trace-path collection
            wpool.shutdown()
        # a closed service serves no executable: let go of them
        with self._lock:
            self._done.clear()
            self._jobs.clear()
            # once: a trainer's finalizer closes its service a second time,
            # whenever the collector gets to it
            release, self._release_caches = self._release_caches, False
        if release:
            # --release_on_close: the process goes on to use the device after
            # this trainer. The TPU runtime keeps a program's temporaries
            # reserved for as long as any of JAX's in-memory caches holds its
            # executable, and what is reserved is lost to every later buffer
            # (a 504 M-parameter model's one superstep: 9.7 of the chip's
            # 16.9 GB still reserved after the trainer was gone, given back
            # by `jax.clear_caches()` alone; my chip runs, PR 27)
            import jax

            jax.clear_caches()
