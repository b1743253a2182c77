"""Device seconds by scope, from the profiled epoch's trace and the scope map
the program wrote while it compiled.

The program wraps the parts of a step in ``jax.named_scope`` and, with its
tracer on, leaves ``<trace_dir>/hlo_scopes.jsonl``: per compiled program its
HLO module's name and the scope of every instruction
(``dynamic_load_balance_distributeddnn_tpu/obs/scopes.py``). A device event of
the profiler names an instruction and its module, so the two join without
either side knowing the other's names for ``fusion.825``. What is computed
here: self seconds by scope on the busiest device (an event outside the map
counts as ``unscoped``), and the device's idle time outside the host's probe
spans. ``run.py`` hands every reader its run directory (``ctx["run_dir"]``,
deleted only after the readers have run), where the profile and the
program's trace directory with the map lie. A program that writes no map (the
parent of the PR that brought this file) gives no scope shares, and the
readers return nothing.

The arithmetic is checked on hand-made events in ``tests/benchmark``.
"""

from __future__ import annotations

import bisect
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark import trace_reduce

MAP_FILE = "hlo_scopes.jsonl"
UNSCOPED = "unscoped"
PROBES = ("probe", "sync_probe")          # host spans that stand for the probes' own waits
WAITS = ("device_wait",) + PROBES         # the controller thread blocked on the device
# module, instruction, start and duration in the profiler's whole nanoseconds:
# scaled to seconds first, an event that starts where another ends comes out
# 7e-18 s inside it, is taken for its child, and the enclosing `while` is
# counted twice (0.04-0.2 s of a profiled epoch, my chip runs, PR 24)
OpEvent = Tuple[str, str, float, float]
NS = trace_reduce.NS


# ------------------------------------------------------------------ the map


def load_map(path: str) -> Dict[str, Dict[str, Optional[str]]]:
    """``{module: {instruction: scope}}`` of a ``hlo_scopes.jsonl``. A key
    written twice counts once, as last written. Where two programs share a
    module's name and give an instruction different scopes it maps to
    ``None`` (it cannot be told which program an event belongs to)."""
    last: Dict[str, dict] = {}
    with open(path) as f:
        for line in f:
            if line.strip():
                row = json.loads(line)
                last[row["key"]] = row
    out: Dict[str, Dict[str, Optional[str]]] = {}
    for row in last.values():
        scopes = out.setdefault(row["module"], {})
        for name, scope in row["scopes"].items():
            scopes[name] = scope if scopes.get(name, scope) == scope else None
    return out


# ---------------------------------------------------------------- arithmetic


def seconds_by_scope(
    lines: Iterable[Sequence[OpEvent]], scope_map: Dict[str, Dict[str, Optional[str]]]
) -> Tuple[Dict[str, float], Dict[str, float], float]:
    """``(seconds by scope, seconds by program, ambiguous seconds)`` of one
    device's lines of events (times in nanoseconds), each counted without what is nested in it
    on its line (``trace_reduce.self_seconds_by_name``), so that the scopes
    add up to the busy time. A program or instruction that is not in the map
    is ``unscoped``, as is an instruction in no scope."""
    by_scope: Dict[str, float] = {}
    by_program: Dict[str, float] = {}
    ambiguous = 0.0
    for events in lines:
        keyed = [((module, name), start, dur) for module, name, start, dur in events]
        for (module, name), ns in trace_reduce.self_seconds_by_name(keyed).items():
            seconds = ns * NS
            scope = scope_map.get(module, {}).get(name, "")
            if scope is None:
                ambiguous += seconds
            by_scope[scope or UNSCOPED] = by_scope.get(scope or UNSCOPED, 0.0) + seconds
            by_program[module] = by_program.get(module, 0.0) + seconds
    return by_scope, by_program, ambiguous


def merged(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The union of ``(start, end)`` intervals as disjoint ones, in order."""
    out: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        elif end > start:
            out.append((start, end))
    return out


def idle_outside(ops: Sequence[trace_reduce.Event], spans: Iterable[trace_reduce.Event],
                 t0: float, t1: float) -> Optional[dict]:
    """Idle seconds of a device inside ``[t0, t1]`` but outside the given host
    spans, and the length of that steady part. ``None`` where nothing of the
    span is left."""
    cut = merged((max(s, t0), min(s + d, t1)) for _, s, d in spans)
    steady_s = (t1 - t0) - sum(e - s for s, e in cut)
    if steady_s <= 0:
        return None
    idle_s = 0.0
    for start, length in trace_reduce.gaps(ops, t0, t1):
        idle_s += length - sum(max(0.0, min(e, start + length) - max(s, start)) for s, e in cut)
    return {"idle_s": idle_s, "steady_s": steady_s, "probe_s": (t1 - t0) - steady_s}


# -------------------------------------------------------------------- loader


def instruction_name(text: str) -> str:
    """An event's instruction: on the TPU an event is named by the whole
    instruction (``%fusion.8 = bf16[..] fusion(..)``), on the CPU by its name."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def load_events(path: str):
    """``(devices, host_spans)`` of one ``.xplane.pb``. ``devices``: per
    device its lines of :data:`OpEvent`. On a TPU plane that is the ``XLA
    Ops`` line, the module taken from the ``XLA Modules`` event that contains
    the operation or, failing that, from its ``hlo_module`` stat. With no
    TPU plane (the CPU rehearsal: never a device number) it is the XLA:CPU
    client's threads under one name, events told by their ``hlo_op`` stat.
    ``host_spans``: every event of the host planes, in seconds as ``trace_reduce``'s."""
    from jax.profiler import ProfileData

    devices: Dict[str, List[List[OpEvent]]] = {}
    cpu_lines: List[List[OpEvent]] = []
    host_spans: List[trace_reduce.Event] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            # by hand (my chip run, PR 24): an operation's event carries no
            # module, the `XLA Modules` line names the program that holds it
            # (`jit_fused_epoch_idx(6613387310377536670)`)
            modules = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name.split("(", 1)[0])
                for e in (lines["XLA Modules"].events if "XLA Modules" in lines else ())
            )
            starts = [m[0] for m in modules]
            ops: List[OpEvent] = []
            for e in lines["XLA Ops"].events:
                if e.duration_ns <= 0:
                    continue
                i = bisect.bisect_right(starts, e.start_ns) - 1
                if i >= 0 and e.start_ns <= modules[i][1]:
                    module = modules[i][2]
                else:
                    module = str(dict(e.stats).get("hlo_module", ""))
                ops.append((module, instruction_name(e.name), e.start_ns, e.duration_ns))
            devices[plane.name] = [ops]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ops = []
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    host_spans.append((e.name, e.start_ns * NS, e.duration_ns * NS))
                    if "XLA" in line.name:
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            ops.append((str(stats.get("hlo_module", "")), str(stats["hlo_op"]),
                                        e.start_ns, e.duration_ns))
                if ops:
                    cpu_lines.append(ops)
    if not devices and cpu_lines:
        devices["host-xla"] = cpu_lines
    return devices, host_spans


# ----------------------------------------------------------------- the table


def reduce_run(devices, host_spans, scope_map) -> Optional[dict]:
    """The table of one profiled epoch: busiest device, its busy seconds, its
    self seconds by scope and by program (``None`` without a map), and its
    idle seconds outside the probes. ``None`` when no operation ran."""
    flat = {k: [(n, s * NS, d * NS) for line in v for _, n, s, d in line]
            for k, v in devices.items()}
    flat = {k: v for k, v in flat.items() if v}
    if not flat:
        return None
    busy = {k: trace_reduce.union_seconds(v) for k, v in flat.items()}
    busiest = max(busy, key=busy.get)
    t0 = min(trace_reduce.span_of(v)[0] for v in flat.values())
    t1 = max(trace_reduce.span_of(v)[1] for v in flat.values())
    table = {
        "device": busiest, "busy_s": busy[busiest], "window_s": t1 - t0,
        "steady": idle_outside(flat[busiest], [e for e in host_spans if e[0] in PROBES], t0, t1),
        "seconds": None,
    }
    if scope_map:
        by_scope, by_program, ambiguous = seconds_by_scope(devices[busiest], scope_map)
        table.update(seconds=by_scope, self_s=sum(by_scope.values()), programs=by_program,
                     ambiguous_s=ambiguous,
                     unmapped_programs=sorted(m for m in by_program if m not in scope_map))
    return table


def table(ctx: dict) -> Optional[dict]:
    """:func:`reduce_run` of the run ``ctx`` describes, read once and kept in
    ``ctx``; printed whole as one ``{"scopes": ...}`` line when first read.
    ``None`` for a run with no profile."""
    if "scope_table" not in ctx:
        ctx["scope_table"] = None
        where = ctx.get("run_dir") if ctx.get("profile") else None
        xplane = trace_reduce.find_xplane(os.path.join(where, "profile")) if where else None
        if xplane:
            map_path = os.path.join(where, "traces", MAP_FILE)
            scope_map = load_map(map_path) if os.path.isfile(map_path) else {}
            ctx["scope_table"] = reduce_run(*load_events(xplane), scope_map)
            print(json.dumps({"scopes": ctx["scope_table"]}), flush=True)
    return ctx["scope_table"]


def share(ctx: dict, *scopes: str) -> Optional[float]:
    """Percent of the busiest device's self seconds under the given scopes."""
    t = table(ctx)
    if not t or not t["seconds"] or t["self_s"] <= 0:
        return None
    return 100.0 * sum(t["seconds"].get(s, 0.0) for s in scopes) / t["self_s"]


# ------------------------------------------------------ host spans (window)


def wait_seconds(spans, start: float, end: float) -> float:
    """Length of the union of the wait spans that lie inside ``[start, end]``."""
    return trace_reduce.union_seconds(
        (s[0], s[2], s[3]) for s in spans if s[0] in WAITS and s[2] >= start and s[2] + s[3] <= end)
