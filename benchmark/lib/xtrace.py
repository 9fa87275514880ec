"""From a `jax.profiler` trace of the window to device numbers: busy and
idle seconds, program (kernel) time, the longest device operations, and the
idle gaps attributed to what the feeding host thread was doing.

What a TPU trace holds (looked at by hand, PR 24): a plane `/device:TPU:<n>`
for each chip with the lines `XLA Modules` (one event for each program run)
and `XLA Ops` (one for each operation inside it), and a plane `/host:CPU`
with one line for each host thread, where `TraceAnnotation`s appear by name,
on the same clock. Those lines all carry the process's name (`python3`), so
`load` keeps them apart by position. The window is the annotation
WINDOW_SPAN; its line is the feeding thread, and the only one whose spans
name an idle gap: pool threads' spans run beside it, not inside it.

`reduce()` works on plain lists, so that tests can hand it a synthetic trace.
"""

from __future__ import annotations

import glob
import os

HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "jfs.window"
SPAN_PREFIX = "jfs."
UNATTRIBUTED = "host:no_benchmark_span_on_the_feeding_thread"
Event = tuple  # (name, start_s, duration_s)


def start(trace_dir: str) -> None:
    import jax.profiler

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # every Python call, for 30 s, is too much
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop_and_reduce(trace_dir: str, n_tpus: int) -> dict:
    """`n_tpus` is 0 off the chip (a rehearsal): no device plane is asked for."""
    import jax.profiler

    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}: {files}")
    summary = reduce(load(files[0]))
    if n_tpus and len(summary["busy_by_device"]) != n_tpus:
        raise RuntimeError(
            f"the trace shows {sorted(summary['busy_by_device'])}, the run "
            f"used {n_tpus} TPU chip(s)")
    return summary


def load(path: str) -> dict:
    """{plane name: {line: [(name, start_s, duration_s), ...]}}. A device's
    lines are keyed by name; the host's, one a thread and all of one name,
    by `<name>#<position>`, so that no two threads fold into one."""
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    planes: dict[str, dict[str, list[Event]]] = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for position, line in enumerate(plane.lines):
            key = (f"{line.name}#{position}" if plane.name == HOST_PLANE
                   else line.name)
            lines.setdefault(key, []).extend(
                (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                for e in line.events)
    return planes


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in merge(intervals))


def merge(intervals) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(events: list[Event], lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(s + d, hi)) for _, s, d in events
            if s < hi and s + d > lo]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    """The parts of [lo, hi] that no merged busy interval covers."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def innermost_segments(events: list[Event]) -> list[tuple[float, float, str]]:
    """Nested spans of one thread -> non-overlapping (start, end, name)
    pieces, each named by the innermost span open there."""
    marks = []
    for name, s, d in events:
        marks.append((s, 1, -d, name))       # opens: longer (outer) first
        marks.append((s + d, 0, 0.0, name))  # closes sort before opens
    marks.sort()
    out, at = [], None
    stack: list[list] = []              # [name, still open], outermost first
    open_by_name: dict[str, list] = {}  # name -> its entries of `stack`
    for t, opens, _, name in marks:
        if stack and t > at:
            out.append((at, t, stack[-1][0]))
        if opens:
            entry = [name, True]
            stack.append(entry)
            open_by_name.setdefault(name, []).append(entry)
        elif open_by_name.get(name):
            open_by_name[name].pop()[1] = False  # the innermost of that name
            while stack and not stack[-1][1]:
                stack.pop()
        at = t
    return out


def gap_seconds_by_segment(idle, segments) -> dict[str, float]:
    """Seconds of the idle gaps under each segment's name, and under
    UNATTRIBUTED what no segment covers. Both lists are sorted and disjoint,
    so one sweep with a cursor in each does it."""
    out: dict[str, float] = {}
    first = 0  # the first segment that ends after the gap at hand starts
    for gs, ge in idle:
        while first < len(segments) and segments[first][1] <= gs:
            first += 1
        covered = 0.0
        i = first
        while i < len(segments) and segments[i][0] < ge:
            ss, se, name = segments[i]
            overlap = min(ge, se) - max(gs, ss)
            if overlap > 0:
                out[name] = out.get(name, 0.0) + overlap
                covered += overlap
            i += 1
        if ge - gs - covered > 0:
            out[UNATTRIBUTED] = out.get(UNATTRIBUTED, 0.0) + ge - gs - covered
    return out


def short_op_name(name: str) -> str:
    """`%fusion.5 = u32[...] fusion(...)` -> `fusion.5`"""
    return name.split(" = ", 1)[0].lstrip("%")


def top(seconds_by_name: dict[str, float], n: int = 10) -> list[list]:
    ranked = sorted(seconds_by_name.items(), key=lambda kv: -kv[1])
    return [[name, seconds] for name, seconds in ranked[:n]]


def reduce(planes: dict) -> dict:
    host_lines = planes.get(HOST_PLANE, {})
    feeding = [(line, e) for line, events in host_lines.items()
               for e in events if e[0] == WINDOW_SPAN]
    if len(feeding) != 1:
        raise RuntimeError(f"{len(feeding)} {WINDOW_SPAN!r} spans in the trace")
    line, (_, lo, length) = feeding[0]
    hi = lo + length
    devices = {name: lines for name, lines in planes.items()
               if name.startswith("/device:TPU:") and lines.get("XLA Modules")}

    busy_by_device, program_s, programs = {}, {}, {}
    op_seconds: dict[str, float] = {}
    busy_intervals = {}  # merged, clipped to the window
    for name, lines in devices.items():
        ops = lines.get("XLA Ops") or lines["XLA Modules"]
        busy_intervals[name] = merge(clip(ops, lo, hi))
        busy_by_device[name] = union_seconds(busy_intervals[name])
        modules = [m for m in lines["XLA Modules"] if lo <= m[1] < hi]
        program_s[name] = sum(d for _, _, d in modules)
        programs[name] = len(modules)
        for op, s, d in lines.get("XLA Ops", []):
            if lo <= s < hi:
                key = short_op_name(op)
                op_seconds[key] = op_seconds.get(key, 0.0) + d
    n = max(1, len(devices))

    # idle gaps of the busiest device, by what the feeding thread was in
    gap_seconds: dict[str, float] = {}
    if devices:
        busiest = max(busy_by_device, key=busy_by_device.get)
        idle = gaps(busy_intervals[busiest], lo, hi)
        spans = [e for e in host_lines[line]
                 if e[0].startswith(SPAN_PREFIX) and e[0] != WINDOW_SPAN]
        gap_seconds = gap_seconds_by_segment(idle, innermost_segments(spans))

    return {
        "window_s": length,
        "busy_by_device": busy_by_device,
        "busy_s": sum(busy_by_device.values()) / n,
        "busiest_busy_s": max(busy_by_device.values(), default=0.0),
        # programs run concurrently across chips: their time is one chip's
        "program_s": sum(program_s.values()) / n,
        "programs": max(programs.values(), default=0),
        "device_ops": top({k: v / n for k, v in op_seconds.items()}),
        "idle_gaps": top(gap_seconds),
    }
