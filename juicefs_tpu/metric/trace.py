"""Request-scoped tracing across fuse/gateway → vfs → chunk → object → tpu.

A dependency-free span subsystem mirroring the accesslog's active-reader
gate (vfs/accesslog.py, reference pkg/vfs/accesslog.go:64-140): span
*events* (JSON lines) are only materialized while at least one consumer
holds the virtual `.trace` file open — otherwise `span()` returns a shared
no-op (zero allocation) or a timing-only shim that feeds the stage-latency
histograms. Three exposures:

  - `.trace` internal file: a live stream of JSON span events, one per
    line, with `trace`/`id`/`parent` linking each request into a tree
    (fuse → vfs → chunk → object → tpu);
  - `juicefs profile --trace DIR`: samples the stream and writes a Chrome
    `trace_event` JSON loadable in chrome://tracing / Perfetto;
    `juicefs gc --trace DIR` does the same for the process that has no
    mount, through an in-process reader (cmd/gc.py);
  - `juicefs_tpu_stage_seconds{layer,op,stage}`: always-on histogram
    rollup in the global registry, the per-stage attribution substrate
    for perf work (ROADMAP north star; round-4 cold-scan postmortem).

Cross-thread propagation: span context rides a per-thread stack, so the
synchronous read path links automatically; pool crossings (upload pool,
download fan-out, slice fan-out) capture `current_ref()` at submit time
and pass it as `parent=`, or run the worker under `carried(ref)`.

`Tracer.annotate` is a hook for whoever runs a profiler session beside a
reader (cmd/gc.py `--trace` on a device backend): while it is set, every
`Span` also opens `annotate("jfs.<layer>.<op>[.<stage>]")`, which puts the
program's spans on the device trace's clock. This module knows no profiler.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import threading
import time
from collections import deque
from typing import Optional

from . import global_registry

__all__ = ["NULL_SPAN", "Tracer", "global_tracer", "span_name",
           "span_summary", "stage_hist"]

MAX_BUFFERED_EVENTS = 10240

_STAGE_SECONDS = global_registry().histogram(
    "juicefs_tpu_stage_seconds",
    "Per-stage operation latency across layers (chunk/object/tpu rollup)",
    ("layer", "op", "stage"),
)


def stage_hist(layer: str, op: str, stage: str = "total"):
    """Pre-resolve one (layer, op, stage) histogram child for hot paths
    (labels() does a locked dict lookup; call sites bind once)."""
    return _STAGE_SECONDS.labels(layer, op, stage)


def span_name(layer, op, stage="") -> str:
    """`jfs.<layer>.<op>[.<stage>]`: a span's name on the profiler's clock
    (the benchmark's hook on `Tracer.span` gives the same)."""
    return "jfs." + ".".join(str(x) for x in (layer, op, stage) if x)


def span_summary(events: list[dict]) -> dict[str, dict]:
    """Span events -> {name: {"n", "total_s", "self_s"}}. A span's self
    time is its duration less the union of its children's intervals
    (children by `parent`, whatever thread they ran on, clipped to the
    parent: a pool thread's child may outlive its parent's wait)."""
    children: dict[int, list[tuple[float, float]]] = {}
    for ev in events:
        children.setdefault(ev.get("parent", 0), []).append(
            (ev["ts"], ev["ts"] + ev["dur"]))
    out: dict[str, dict] = {}
    for ev in events:
        lo, hi = ev["ts"], ev["ts"] + ev["dur"]
        covered, at = 0.0, lo
        for s, e in sorted(children.get(ev["id"], ())):
            s, e = max(s, at), min(e, hi)
            if e > s:
                covered += e - s
                at = e
        row = out.setdefault(
            span_name(ev.get("layer"), ev.get("op"), ev.get("stage", "")),
            {"n": 0, "total_s": 0.0, "self_s": 0.0})
        row["n"] += 1
        row["total_s"] += ev["dur"]
        row["self_s"] += ev["dur"] - covered
    for row in out.values():
        row["total_s"] = round(row["total_s"], 6)
        row["self_s"] = round(row["self_s"], 6)
    return out


class _NullSpan:
    """Shared no-op span: the zero-cost path when no consumer is attached
    and the call site carries no stage histogram."""

    __slots__ = ()
    active = False

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

    def set(self, **kw) -> None:
        pass

    def ref(self) -> None:
        return None


NULL_SPAN = _NullSpan()


class _CarriedRef:
    """Stack marker adopting a foreign (trace_id, span_id) as parent
    (Tracer.carried); never emitted, only resolved against."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, ref: tuple[int, int]):
        self.trace_id, self.span_id = ref


class _TimedSpan:
    """No consumer attached but a stage histogram bound: time the region
    and observe — nothing else (the <5% no-reader overhead budget)."""

    __slots__ = ("_hist", "_t0", "dur")
    active = False

    def __init__(self, hist):
        self._hist = hist

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        # kept for the caller that reports its stages (gc --dedup's
        # stage_seconds): the same number the histogram took
        self.dur = time.perf_counter() - self._t0
        self._hist.observe(self.dur)
        return False

    def set(self, **kw) -> None:
        pass

    def ref(self) -> None:
        return None


class Span:
    """One traced region; emitted as a JSON event line on exit."""

    __slots__ = ("tracer", "layer", "op", "stage", "hist", "attrs",
                 "trace_id", "span_id", "parent_id", "_t0", "_ts", "_ann",
                 "dur")
    active = True

    def __init__(self, tracer: "Tracer", layer: str, op: str, stage: str,
                 hist, parent, attrs: dict):
        self.tracer = tracer
        self.layer = layer
        self.op = op
        self.stage = stage
        self.hist = hist
        self.attrs = attrs
        if parent is not None:  # explicit (trace_id, span_id) ref
            self.trace_id, self.parent_id = parent
        else:
            self.trace_id = self.parent_id = -1  # resolve from stack on enter

    def __enter__(self):
        tr = self.tracer
        self.span_id = next(tr._ids)
        stack = tr._local.__dict__.setdefault("stack", [])
        if self.parent_id < 0:
            if stack:
                top = stack[-1]
                self.trace_id, self.parent_id = top.trace_id, top.span_id
            else:  # root: the trace is named after its root span
                self.trace_id, self.parent_id = self.span_id, 0
        stack.append(self)
        annotate = tr.annotate
        if annotate is None:
            self._ann = None
        else:
            self._ann = annotate(span_name(self.layer, self.op, self.stage))
            self._ann.__enter__()
        self._ts = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb):
        self.dur = dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        if self.hist is not None:
            self.hist.observe(dur)
        stack = self.tracer._local.__dict__.get("stack")
        if stack:
            if stack[-1] is self:
                stack.pop()
            elif self in stack:  # unbalanced exit: drop self only
                stack.remove(self)
        if et is not None and "errno" not in self.attrs:
            self.attrs["error"] = et.__name__
        self.tracer._emit(self, dur)
        return False

    def set(self, **kw) -> None:
        self.attrs.update(kw)

    def ref(self) -> tuple[int, int]:
        return (self.trace_id, self.span_id)


class Tracer:
    """Global span hub; reader bookkeeping mirrors AccessLogger."""

    def __init__(self):
        self._lock = threading.Lock()
        self._readers: dict[int, deque[bytes]] = {}
        self._active = False
        self._local = threading.local()
        self._ids = itertools.count(1)
        # name -> context manager, or None: set by the owner of a
        # profiler session for as long as it runs (module docstring)
        self.annotate = None

    @property
    def active(self) -> bool:
        return self._active

    # -- span construction -------------------------------------------------
    def span(self, layer: str, op: str, stage: str = "", hist=None,
             parent: Optional[tuple[int, int]] = None, **attrs):
        if not self._active:
            return _TimedSpan(hist) if hist is not None else NULL_SPAN
        return Span(self, layer, op, stage, hist, parent, attrs)

    def current_ref(self) -> Optional[tuple[int, int]]:
        """(trace_id, span_id) of the innermost open span on this thread,
        for crossing into worker pools; None when inactive/no span."""
        stack = self._local.__dict__.get("stack")
        if stack:
            top = stack[-1]
            return (top.trace_id, top.span_id)
        return None

    @contextlib.contextmanager
    def carried(self, ref: Optional[tuple[int, int]]):
        """Adopt a captured (trace_id, span_id) as this thread's current
        parent — the pool-crossing adapter for code that opens spans
        *internally* (the metered object wrapper under the resilience
        layer's worker pool).  Emits nothing itself; spans opened inside
        resolve their parent from the carried marker."""
        if ref is None or not self._active:
            yield
            return
        stack = self._local.__dict__.setdefault("stack", [])
        marker = _CarriedRef(ref)
        stack.append(marker)
        try:
            yield
        finally:
            if stack and stack[-1] is marker:
                stack.pop()
            elif marker in stack:  # unbalanced inner exits: drop self only
                stack.remove(marker)

    # -- event stream ------------------------------------------------------
    def _emit(self, span: Span, dur: float) -> None:
        ev = {
            "ts": round(span._ts, 6),
            "dur": round(dur, 6),
            "trace": span.trace_id,
            "id": span.span_id,
            "parent": span.parent_id,
            "tid": threading.get_native_id(),
            "layer": span.layer,
            "op": span.op,
        }
        if span.stage:
            ev["stage"] = span.stage
        if span.attrs:
            ev.update(span.attrs)
        try:
            line = (json.dumps(ev, default=str) + "\n").encode()
        except (TypeError, ValueError):
            return  # a bad attr must never break the traced operation
        with self._lock:
            for buf in self._readers.values():
                buf.append(line)

    # -- reader lifecycle (one ring buffer per .trace open) ----------------
    def open_reader(self, fh,
                    max_events: Optional[int] = MAX_BUFFERED_EVENTS) -> None:
        """Attach a consumer. `max_events=None` keeps every event until it
        is read: for an in-process reader that drains once, at the end of
        a command (gc --trace); a ring drops its oldest."""
        with self._lock:
            self._readers[fh] = deque(maxlen=max_events)
            self._active = True

    def close_reader(self, fh) -> None:
        with self._lock:
            self._readers.pop(fh, None)
            self._active = bool(self._readers)

    def read(self, fh, max_bytes: int = 1 << 16) -> bytes:
        """Drain buffered events for one reader (blocking up to 1s so
        `tail -f` style consumers don't spin; same shape as accesslog)."""
        deadline = time.time() + 1.0
        while True:
            with self._lock:
                buf = self._readers.get(fh)
                if buf is None:
                    return b""
                out = bytearray()
                while buf:
                    line = buf[0]
                    if len(out) + len(line) > max_bytes:
                        if not out:  # a single oversized line: split it
                            out += line[:max_bytes]
                            buf[0] = line[max_bytes:]
                        break
                    out += buf.popleft()
            if out or time.time() >= deadline:
                return bytes(out)
            time.sleep(0.02)


_tracer = Tracer()


def global_tracer() -> Tracer:
    return _tracer
