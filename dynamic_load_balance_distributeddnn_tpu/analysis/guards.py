"""Runtime compile/sync guards built on ``jax.monitoring``.

JAX records a ``/jax/core/compile/backend_compile_duration`` event for every
actual XLA backend compile (cache hits don't fire it). One process-wide
listener fans those events out to:

* a global monotone counter (:func:`compile_count`) — cheap deltas anywhere;
* :func:`compile_budget` — a context manager asserting "this region compiles
  at most N programs", which lets the bucket-ladder contract of
  tests/test_compile_discipline.py be checked in the fast tier instead of
  only by the @slow e2e run;
* :class:`CompileTracker` — a drainable per-consumer counter the engine uses
  to log unexpected steady-state recompiles in production runs (an off-ladder
  shape sneaking into a timed epoch is invisible in the wall on a fast chip
  but poisons the DBS time signal; see graftlint G003).

The listener registers lazily on first use and is never unregistered
(jax.monitoring has no public unregister; an idle listener costs one function
call per compile, i.e. nothing).

The same listener is graftscope's source for JAX's own share of a set-up:
the four duration events of :data:`COMPILE_SPANS` become ``compile`` spans
(each ending when the event fires, on the thread that fired it, so the AOT
pool's threads keep their own tracks). With the tracer off that costs one
dict lookup and one attribute check per event.

**Background (AOT) compiles.** The async compile service
(runtime/compiler.py) deliberately compiles on pool threads while epochs
execute; its threads are named with :data:`AOT_THREAD_PREFIX`, and the
listener runs on the compiling thread, so events can be attributed. Budgets
and trackers default to counting only *foreground* compiles — the ones on
the execution path, which is what the recompile sentinel and the
steady-epoch zero-budgets police — and opt into background events with
``include_background=True`` (the warm-ladder CI guard, which must see the
background compiles too).
"""

from __future__ import annotations

import threading
import weakref
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Iterator, List, Optional

from dynamic_load_balance_distributeddnn_tpu.obs.trace import get_tracer

_COMPILE_EVENT_PREFIX = "/jax/core/compile/backend_compile"

# jax.monitoring duration event -> graftscope span (cat "compile"). A
# `backend_compile` holds the `cache_read` that served it, where one did.
COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}

# Compile-pool threads are named with this prefix; runtime/compiler.py
# imports it from here (single definition — a drift would silently count
# background compiles as foreground and trip every steady-epoch budget).
AOT_THREAD_PREFIX = "jax-aot-compile"

_lock = threading.Lock()
_installed = False
_total_compiles = 0
_total_bg_compiles = 0
_total_compile_s = 0.0
_active_budgets: List["CompileBudget"] = []
# Weak registry: consumers (one tracker per Trainer) drop out when their
# owner is garbage-collected, so a process that builds many engines (bench
# arms, the test suite) never accumulates stale fan-out targets.
_trackers: "weakref.WeakSet[CompileTracker]" = weakref.WeakSet()


def _on_event(event: str, duration: float = 0.0, **_kw) -> None:
    global _total_compiles, _total_bg_compiles, _total_compile_s
    span = COMPILE_SPANS.get(event)
    if span is not None:
        tracer = get_tracer()
        if tracer.enabled:
            tracer.span_ending_now(span, "compile", float(duration))
    if not event.startswith(_COMPILE_EVENT_PREFIX):
        return
    # the listener runs ON the compiling thread, so the thread name tells
    # foreground (execution path) from background (AOT service pool) apart
    background = threading.current_thread().name.startswith(AOT_THREAD_PREFIX)
    with _lock:
        _total_compiles += 1
        _total_compile_s += float(duration)
        if background:
            _total_bg_compiles += 1
        for budget in _active_budgets:
            if not background or budget.include_background:
                budget.count += 1
        for tracker in _trackers:
            if not background or tracker.include_background:
                tracker._pending += 1


def _ensure_listener() -> None:
    global _installed
    with _lock:
        if _installed:
            return
        # register under the lock and mark installed only on success: a
        # guard that silently failed to hook the listener would report
        # green (0 compiles) forever after. _on_event cannot fire (and
        # re-take the lock) until registration completes.
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_event)
        _installed = True


def compile_count() -> int:
    """Total XLA backend compiles observed since the listener was installed
    (foreground AND background). Call once early (e.g. at trainer init) if
    you intend to diff against it — compiles before installation are not
    counted."""
    _ensure_listener()
    with _lock:
        return _total_compiles


def compile_seconds() -> float:
    """Summed duration of every XLA backend compile observed so far
    (foreground AND background; persistent-cache hits fire no event, so a
    warm run reads near zero). Diff it around a region for its compile
    seconds — set-up time, reported apart from the timed work."""
    _ensure_listener()
    with _lock:
        return _total_compile_s


def background_compile_count() -> int:
    """Compiles observed on AOT-service pool threads (a subset of
    :func:`compile_count`)."""
    _ensure_listener()
    with _lock:
        return _total_bg_compiles


class CompileBudgetExceeded(RuntimeError):
    def __init__(self, label: str, count: int, max_compiles: int):
        self.label = label
        self.count = count
        self.max_compiles = max_compiles
        super().__init__(
            f"compile budget exceeded in {label!r}: {count} XLA backend "
            f"compiles > budget {max_compiles} — an input shape fell off the "
            "bucket ladder or a jit wrapper was rebuilt (graftlint G001/G003)"
        )


@dataclass(eq=False)  # identity semantics: _active_budgets.remove must never
class CompileBudget:   # match a different-but-equal nested budget
    """Live view handed out by :func:`compile_budget`; ``count`` updates as
    compiles land inside the region."""

    label: str
    max_compiles: Optional[int]
    count: int = 0
    include_background: bool = False


@contextmanager
def compile_budget(
    max_compiles: Optional[int] = None,
    label: str = "compile_budget",
    on_excess: str = "raise",
    logger=None,
    include_background: bool = False,
) -> Iterator[CompileBudget]:
    """Count XLA backend compiles over a region; enforce a bound on exit.

    ``max_compiles=None`` counts without enforcing (measurement mode).
    ``on_excess``: ``"raise"`` (default) raises :class:`CompileBudgetExceeded`;
    ``"warn"`` logs a warning on ``logger`` (or stderr) and continues.
    Regions may nest; each counts independently. The count includes EVERY
    backend compile in the region — internal helper ops (jnp constant
    uploads etc.) too — so budgets should carry a few entries of slack
    rather than an exact executable count.

    ``include_background``: also count compiles from the AOT compile
    service's pool threads (runtime/compiler.py). Off by default — a
    steady-epoch zero-budget polices the *execution path*, and deliberate
    overlapped background compiles (speculation) would fail it spuriously.
    """
    if on_excess not in ("raise", "warn"):
        raise ValueError(f"on_excess must be 'raise' or 'warn', got {on_excess!r}")
    _ensure_listener()
    budget = CompileBudget(
        label=label,
        max_compiles=max_compiles,
        include_background=include_background,
    )
    with _lock:
        _active_budgets.append(budget)
    clean_exit = False
    try:
        yield budget
        clean_exit = True
    finally:
        with _lock:
            _active_budgets.remove(budget)
        # enforce ONLY on clean exit: an exception from the region must
        # propagate as itself, not be replaced by a budget violation its
        # aborted run may well have caused
        if (
            clean_exit
            and budget.max_compiles is not None
            and budget.count > budget.max_compiles
        ):
            exc = CompileBudgetExceeded(label, budget.count, budget.max_compiles)
            if on_excess == "raise":
                raise exc
            if logger is not None:
                logger.warning(str(exc))
            else:  # pragma: no cover - fallback path
                import sys

                print(f"WARNING: {exc}", file=sys.stderr)


@dataclass(eq=False)  # identity semantics: hashable for the weak registry
class CompileTracker:
    """Drainable compile counter for long-lived consumers (one per engine).

    ``take()`` returns the number of backend compiles since the previous
    ``take()`` and resets the pending count — the engine calls it at each
    epoch boundary and logs a warning when steady-state epochs (probes
    anchored, ladder warm) still compile. Background AOT-service compiles
    are excluded by default (``include_background``): they are deliberate
    overlapped work, not a shape falling off the ladder."""

    _pending: int = field(default=0, repr=False)
    include_background: bool = field(default=False)

    def __post_init__(self) -> None:
        _ensure_listener()
        with _lock:
            _trackers.add(self)

    def take(self) -> int:
        with _lock:
            n = self._pending
            self._pending = 0
        return n

    def close(self) -> None:
        """Optional eager deregistration; the weak registry also drops the
        tracker automatically when its owner is collected."""
        with _lock:
            _trackers.discard(self)
