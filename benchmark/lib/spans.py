"""The benchmark's own spans, put around names of the program from outside
and only in a traced run: each records its duration on the host clock and
opens a `jax.profiler.TraceAnnotation`, so that it lies on the profiler's
clock beside the device's operations. Spans inside the program are a later
(`tracing`) PR's; an untraced run patches nothing.
"""

from __future__ import annotations

import contextlib
import functools
import importlib
import time


class Spans:
    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.durations: dict[str, list[float]] = {}
        self._undo: list[tuple[object, str, object]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.enabled:
            yield
            return
        import jax.profiler

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.durations.setdefault(name, []).append(
                    time.perf_counter() - t0)

    def _patch(self, target: str, make):
        """`pkg.mod:attr` or `pkg.mod:Class.attr` -> replaced by make(old)."""
        if not self.enabled:
            return
        module, _, path = target.partition(":")
        owner = importlib.import_module(module)
        *parents, attr = path.split(".")
        for p in parents:
            owner = getattr(owner, p)
        old = getattr(owner, attr)
        setattr(owner, attr, make(old))
        self._undo.append((owner, attr, old))

    def wrap_call(self, target: str, name: str) -> None:
        def make(old):
            @functools.wraps(old)
            def wrapped(*a, **kw):
                with self.span(name):
                    return old(*a, **kw)
            return wrapped
        self._patch(target, make)

    def wrap_iterator(self, target: str, name: str) -> None:
        """A generator function: the span covers each wait for the next item."""
        def make(old):
            @functools.wraps(old)
            def wrapped(*a, **kw):
                it = iter(old(*a, **kw))
                while True:
                    with self.span(name):
                        try:
                            item = next(it)
                        except StopIteration:
                            return
                    yield item
            return wrapped
        self._patch(target, make)

    def wrap_tracer(self, target: str, prefix: str) -> None:
        """The program's `Tracer.span(layer, op, stage, ...)`: every span
        site it already has also opens `<prefix><layer>.<op>[.<stage>]`."""
        def make(old):
            @functools.wraps(old)
            def span(tracer, layer, op, stage="", *a, **kw):
                name = prefix + ".".join(x for x in (layer, op, stage) if x)
                return _Both(self.span(name), old(tracer, layer, op, stage, *a, **kw))
            return span
        self._patch(target, make)

    def restore(self) -> None:
        while self._undo:
            owner, attr, old = self._undo.pop()
            setattr(owner, attr, old)


class _Both:
    def __init__(self, outer, inner):
        self.outer, self.inner = outer, inner

    def __enter__(self):
        self.outer.__enter__()
        return self.inner.__enter__()

    def __exit__(self, *exc):
        try:
            return self.inner.__exit__(*exc)
        finally:
            self.outer.__exit__(None, None, None)
