"""Reduction of a profiler trace to the few numbers the benchmark reports.

Pure functions over lists of ``(name, start, duration)`` — interval union
for busy and idle time, self time by operation name, the longest gaps with
the host phase that covers each, the share of collectives — and one loader
that reads a ``.xplane.pb`` with nothing but JAX
(``jax.profiler.ProfileData``). The arithmetic is checked on a hand-made
event list in ``tests/benchmark``.

Device events keep the profiler's whole nanoseconds until self time has been
added up (``reduce_profile`` scales what it returns): scaled to seconds
first, an event that starts where another ends comes out 7e-18 s inside it,
is taken for its child, and the enclosing ``while`` is counted twice
(PERF.md, PR 24 Findings 2). An epoch of a language-model job is one
``while`` over its windows.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start, duration (one unit: s, or whole ns)
NS = 1e-9

COLLECTIVE_MARKS = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
                    "collective-permute")


def union_seconds(events: Iterable[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def span_of(events: Sequence[Event]) -> Tuple[float, float]:
    """First start and last end of the events."""
    return min(e[1] for e in events), max(e[1] + e[2] for e in events)


def self_seconds_by_name(events: Iterable[Event]) -> Dict[str, float]:
    """Seconds per operation name on one device line, each event counted
    without the events nested inside it (a ``while`` holds its body's
    operations on the same line), so that the names add up to the busy time."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [name, end, self_seconds]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until:
            name, _, self_s = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_s, 0.0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, max(stack[-1][1] - start, 0.0))
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def gaps(events: Iterable[Event], t0: float, t1: float) -> List[Tuple[float, float]]:
    """Idle intervals ``(start, length)`` inside ``[t0, t1]``, longest first."""
    out, end = [], t0
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if start > end:
            out.append((end, min(start, t1) - end))
        end = max(end, start + dur)
        if end >= t1:
            break
    if end < t1:
        out.append((end, t1 - end))
    return sorted((g for g in out if g[1] > 0), key=lambda g: -g[1])


def cover(host_spans: Iterable[Event], start: float, length: float) -> str:
    """Name of the host span that overlaps ``[start, start + length]`` most;
    ``"(no host span)"`` where none does."""
    best, best_s = "(no host span)", 0.0
    for name, s, d in host_spans:
        ov = min(s + d, start + length) - max(s, start)
        if ov > best_s:
            best, best_s = name, ov
    return best


def collective_seconds(by_name: Dict[str, float]) -> float:
    return sum(s for n, s in by_name.items() if any(m in n for m in COLLECTIVE_MARKS))


def short_name(name: str, width: int = 96) -> str:
    """An HLO instruction's text cut to its name, result shape and kind:
    layouts (``{0,3,2,1:T(8,128)}``) and operands go."""
    head = re.sub(r"\{[^{}]*\}", "", name.split("(%", 1)[0])
    return re.sub(r"\s+", " ", head).strip()[:width]


def top(by_name: Dict[str, float], n: int = 10) -> List[List]:
    return [[short_name(k), v] for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])[:n]]


def reduce_profile(
    device_ops: Dict[str, List[Event]],
    host_spans: List[Event],
    phase_names: Sequence[str],
    unit: float = 1.0,
) -> Optional[dict]:
    """Per-device busy seconds and the window they are taken over, the
    operations with most self time on the busiest device, its five longest
    gaps with the phase that covers each, and its collective seconds.
    ``None`` when no operation ran on any device. ``device_ops`` are in
    ``unit`` seconds (``NS`` as ``load_xplane`` gives them), ``host_spans``
    in seconds; self time is added up before anything is scaled."""
    device_ops = {k: v for k, v in device_ops.items() if v}
    if not device_ops:
        return None
    t0 = min(span_of(v)[0] for v in device_ops.values())
    t1 = max(span_of(v)[1] for v in device_ops.values())
    busy = {k: union_seconds(v) * unit for k, v in device_ops.items()}
    busiest = max(busy, key=busy.get)
    by_name = {n: s * unit for n, s in self_seconds_by_name(device_ops[busiest]).items()}
    phases = [e for e in host_spans if e[0] in phase_names]
    idle = [
        [cover(phases, s * unit, length * unit), length * unit]
        for s, length in gaps(device_ops[busiest], t0, t1)[:5]
    ]
    return {
        "window_s": (t1 - t0) * unit,
        "busy_s_by_device": busy,
        "busy_s": sum(busy.values()) / len(busy),
        "busiest": busiest,
        "busiest_busy_s": busy[busiest],
        "collective_s": collective_seconds(by_name),
        "device_ops": top(by_name, 10),
        "idle_gaps": idle,
        "host_phase_spans_found": len(phases),
    }


# ------------------------------------------------------------------ loader


def find_xplane(profile_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load_xplane(path: str, ops_line: str = "XLA Ops"):
    """``(device_ops, host_spans, layout)`` of one ``.xplane.pb``.

    ``device_ops``: per TPU plane, the events of its ``XLA Ops`` line.
    ``host_spans``: every event of the host planes' lines (the program's
    phases appear there when its spans are bridged to
    ``jax.profiler.TraceAnnotation``). ``layout``: plane and line names with
    event counts, for a look by hand. Device events are in the profiler's
    whole nanoseconds (``reduce_profile(..., unit=NS)``), host spans in
    seconds, both on the trace's own clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    host_spans: List[Event] = []
    layout = []
    names: Dict[str, str] = {}  # one string per distinct (long) instruction text
    for plane in data.planes:
        is_tpu = plane.name.startswith("/device:TPU:")
        is_host = plane.name.startswith("/host:")
        for line in plane.lines:
            on_device = is_tpu and line.name == ops_line
            keep = device_ops.setdefault(plane.name, []) if on_device \
                else host_spans if is_host else None
            scale = 1 if on_device else NS
            count = 0
            for e in line.events:
                count += 1
                if keep is not None and e.duration_ns > 0:
                    keep.append((names.setdefault(e.name, e.name), e.start_ns * scale,
                                 e.duration_ns * scale))
            layout.append([plane.name, line.name, count])
    return device_ops, host_spans, layout


def host_ops_as_device(path: str) -> Dict[str, List[Event]]:
    """Rehearsal only (no TPU plane exists on the CPU): the XLA:CPU client's
    operation events, so that the same reduction runs end to end. What it
    yields is never a device number."""
    from jax.profiler import ProfileData

    out: Dict[str, List[Event]] = {"host-xla": []}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if "XLA" not in line.name:
                continue
            out["host-xla"].extend(
                (e.name, e.start_ns, e.duration_ns)  # whole nanoseconds, as load_xplane's
                for e in line.events
                if e.duration_ns > 0
            )
    return out
