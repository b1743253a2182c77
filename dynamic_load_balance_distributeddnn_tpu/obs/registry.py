"""Unified metrics registry: one handle over the run's observability surfaces.

Before graftscope, a caller wanting "where did this run's time go" had to
know four unrelated objects: the :class:`~..obs.recorder.MetricsRecorder`
(nine per-epoch series + extras), the
:class:`~..balance.timing.HostOverheadMeter` (dispatch/put walls), the
compile guards (:mod:`..analysis.guards` counters + per-engine
``CompileTracker``), and the AOT compile service's stats. The registry binds
them behind one object the engine owns:

* ``registry.last(name)`` / ``registry.series(name)`` — recorder access with
  the None-for-absent contract (optional series like ``examples_per_s``
  exist only on some paths);
* ``registry.snapshot()`` — one JSON-safe dict of everything measurable
  *right now*: recorder last-values, host-meter walls, compile counts
  (foreground/background), AOT service stats, tracer state. The engine logs
  it at end of run; tests read single keys out of it;
* meters registered once (``attach(...)``) so future surfaces (a new meter,
  a new service) join the snapshot without new plumbing at every call site.

The registry holds *references*, not copies — it is a view, never a second
source of truth, so it can never drift from the objects it unifies.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from dynamic_load_balance_distributeddnn_tpu.obs.recorder import MetricsRecorder
from dynamic_load_balance_distributeddnn_tpu.obs.trace import Tracer, get_tracer


def device_peak_memory() -> Dict:
    """Per-device peak-memory series (ISSUE 13 satellite) — the datum the
    zero1 A/B reports. Where the backend provides ``device.memory_stats()``
    (TPU/GPU runtimes), one row per local device with ``bytes_in_use``,
    ``peak_bytes_in_use`` (live buffers), ``peak_bytes_reserved`` (programs'
    temporaries: the TPU runtime counts them apart, and a chip half full of
    them read 3 % by the first alone) and their sum ``peak_bytes``, as
    ``benchmark/harness.peak_bytes`` has it; CPU backends expose no per-device allocator, so
    the fallback reports the process's peak RSS (and tracemalloc's peak
    when tracing is active) — a coarser but honest host-side ceiling.

    Mid-rendezvous safe: while the distributed runtime is torn down
    (retire_runtime -> establish), ``jax.local_devices()`` can raise — a
    snapshot taken then degrades to ``{"source": "unavailable"}`` instead of
    propagating and killing the caller's whole snapshot."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception as e:  # noqa: BLE001 — torn-down runtime mid-rendezvous
        return {"source": "unavailable", "error": str(e)[:200]}

    out: Dict = {"source": "memory_stats", "per_device": []}
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — backend without an allocator API
            stats = None
        if stats:
            in_use = int(stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0)))
            reserved = int(stats.get("peak_bytes_reserved", 0))
            out["per_device"].append(
                {
                    "device": str(d),
                    "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                    "peak_bytes_in_use": in_use,
                    "peak_bytes_reserved": reserved,
                    "peak_bytes": in_use + reserved,
                }
            )
    if not out["per_device"]:
        import resource
        import sys
        import tracemalloc

        out["source"] = "host_rss"
        ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # ru_maxrss is KiB on Linux, bytes on macOS
        out["host_peak_rss_bytes"] = int(
            ru if sys.platform == "darwin" else ru * 1024
        )
        if tracemalloc.is_tracing():
            _cur, peak = tracemalloc.get_traced_memory()
            out["tracemalloc_peak_bytes"] = int(peak)
    return out


class MetricsRegistry:
    def __init__(
        self,
        recorder: Optional[MetricsRecorder] = None,
        tracer: Optional[Tracer] = None,
    ):
        self.recorder = recorder if recorder is not None else MetricsRecorder()
        self.tracer = tracer if tracer is not None else get_tracer()
        self.host_meter = None  # balance.timing.HostOverheadMeter
        self.compile_tracker = None  # analysis.guards.CompileTracker
        self.aot_service = None  # runtime.compiler.AOTCompileService
        self.health = None  # runtime.health.WorkerHealth
        self.controller = None  # balance.controller.OnlineRebalanceController
        self.scheduler = None  # runtime.scheduler.MultiStreamEngine

    def attach(self, **surfaces) -> "MetricsRegistry":
        """Register observability surfaces by their well-known slot name
        (``host_meter``, ``compile_tracker``, ``aot_service``, ``health``,
        ``controller``, ``scheduler``). Unknown names raise — a typo'd
        attach would silently hollow the snapshot."""
        for name, obj in surfaces.items():
            if name not in (
                "host_meter", "compile_tracker", "aot_service", "health",
                "controller", "scheduler",
            ):
                raise ValueError(f"unknown registry surface {name!r}")
            setattr(self, name, obj)
        return self

    # ------------------------------------------------------- recorder facade

    def series(self, name: str) -> List:
        """A recorder series by name ([] for a series never recorded)."""
        return self.recorder.data.get(name, [])

    def last(self, name: str):
        """Last recorded value of a series (None when absent/empty)."""
        return self.recorder.last(name)

    # ------------------------------------------------------------- snapshot

    def snapshot(self) -> Dict:
        """JSON-safe point-in-time view across every attached surface."""
        out: Dict = {
            "recorder": {
                k: self.recorder.last(k)
                for k, v in self.recorder.data.items()
                if v
            },
            "trace": {
                "mode": self.tracer.mode,
                # O(1): events() would COPY the whole deque (up to 1M
                # tuples) just to take a length
                "events": self.tracer.event_count() if self.tracer.enabled else 0,
            },
        }
        # gradient-collective wire accounting (ISSUE 12): per-epoch bytes
        # each link class carried, plus the combine structure they were
        # measured under (tests/test_grad_comm.py reads them)
        comm = {
            k: self.recorder.last(k)
            for k in ("comm_bytes_ici", "comm_bytes_dcn")
            if self.recorder.last(k) is not None
        }
        if comm:
            comm["grad_comm"] = self.recorder.meta.get("grad_comm", "flat")
            for k in ("grad_comm_levels", "grad_comm_wires"):
                if k in self.recorder.meta:
                    comm[k] = self.recorder.meta[k]
            # bandwidth-probe verdict (ISSUE 17): the measured hier/flat
            # wall ratio, the gate it was judged against, and the per-level
            # link rates the codec choice was made from — queryable live,
            # not only a log line
            bw = self.recorder.meta.get("link_bandwidth")
            if isinstance(bw, dict):
                comm["probe"] = {
                    k: bw[k]
                    for k in (
                        "wall_ratio", "gate_ratio", "hier_wins",
                        "level_bytes_per_s", "levels",
                    )
                    if k in bw
                }
            out["comm"] = comm
        # per-device peak-memory series (ISSUE 13): backend allocator stats
        # where available, host-RSS fallback on CPU — what the zero1 A/B
        # cites for the optimizer-state shrink
        out["memory"] = device_peak_memory()
        if self.host_meter is not None:
            m = self.host_meter
            out["host"] = {
                "dispatch_s": round(m.dispatch_s, 6),
                "put_s": round(m.put_s, 6),
                "dispatches": m.dispatches,
            }
        # process-wide compile counters are always available (guards installs
        # its jax.monitoring listener lazily)
        from dynamic_load_balance_distributeddnn_tpu.analysis.guards import (
            background_compile_count,
            compile_count,
        )

        total = compile_count()
        bg = background_compile_count()
        out["compiles"] = {"total": total, "background": bg, "foreground": total - bg}
        if self.aot_service is not None:
            out["aot"] = {
                k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in self.aot_service.stats().items()
            }
        if self.health is not None:
            out["health"] = self.health.snapshot()
        if self.controller is not None:
            # the online-DBS decision journal's live surface (ISSUE 15):
            # ledgers, decision count, and the most recent verdict with the
            # inputs it was decided on
            out["controller"] = self.controller.snapshot()
        if self.scheduler is not None:
            # the OUTER loop's decision journal (ISSUE 19): the many-stream
            # engine's per-window device-allocation verdicts in the same
            # journal shape as the inner controller's
            out["scheduler"] = self.scheduler.snapshot()
        return out
