"""Ordered, bounded-window parallel fetch stage (ISSUE 2 tentpole).

The serial bulk block paths (gc --dedup scan, fill_cache, remove, chunk
compaction) all walked blocks one GET at a time while the reference design
runs every bulk path through async worker pools
(pkg/chunk/cached_store.go:415-472).  `fetch_ordered` is the shared stage
that fixes this: it keeps up to `window` calls running on a caller-owned
executor and yields results **in input order**, so downstream consumers
(the TPU hash pipeline, compact's sequential writer, tests) stay
deterministic while storage I/O overlaps device compute.

Bounds, by construction (two numbers since ISSUE 32; `ahead` defaults to
0, and then they are the one bound the stage always had):
  - at most `window` calls are submitted and unfinished at any moment, so
    no more than `window` concurrent GETs whatever the executor's width:
    the cap is the stage's own, not the lane's;
  - at most `window + ahead` items are fetched-or-fetching and not yet
    consumed, so no more than `(window + ahead) x block_size` bytes wait
    for the consumer.  `ahead` is what the consumer takes in one gulp
    (the dedup scan: one hash batch), so that while it works on that gulp
    the pool fetches the next.  What exceeds the running cap waits inside
    the stage, unsubmitted; a finished call starts the next from its own
    pool thread, so the fetch runs on while the consumer is busy;
  - yielding blocks on the *oldest* item, so a slow head stalls the
    output but never grows the buffer.

Deadlock rule (see docs/ARCHITECTURE.md "Concurrency model"): the worker
callable must never submit-and-wait on the same bounded pool it runs on.
`_load_block` / object `delete` do no pool submits, so the store's
download pool is safe for scans and bulk ops; compaction reads go through
`RSlice.read`, which fans out on the download pool, so compact passes a
transient pool of its own.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import CancelledError, Future
from typing import Callable, Iterable, Iterator, Optional, TypeVar

from ..metric import global_registry
from ..metric.trace import global_tracer, stage_hist
from ..object.interface import NotFoundError
from ..object.resilient import BreakerOpenError
from ..utils import get_logger

logger = get_logger("chunk.parallel")

T = TypeVar("T")
R = TypeVar("R")

# Gauge (not counter): in-flight GETs of every live fetch stage — the
# direct observable for "is storage I/O actually overlapping compute".
_INFLIGHT = global_registry().gauge(
    "juicefs_fetch_inflight",
    "Block fetches currently in flight in ordered parallel-fetch stages",
)
# Is the fetch hidden behind the consumer's own work? Each time the
# consumer takes the oldest block: was it there already (ready="1") or did
# the consumer have to wait for its GET (ready="0").
_WAITS = global_registry().counter(
    "juicefs_fetch_waits",
    "Consumer waits of ordered parallel-fetch stages, by whether the "
    "block had already arrived",
    ("ready",),
)
_WAIT_READY = _WAITS.labels("1")
_WAIT_BLOCKED = _WAITS.labels("0")
_TR = global_tracer()
_H_WAIT = stage_hist("chunk", "fetch", "wait")


class FetchStats:
    """Wall vs aggregate time of one fetch stage.

    `seconds` sums per-call durations across worker threads (aggregate
    thread time); `wall` is BUSY wall — time during which at least one
    call was in flight.  Busy, not first-start-to-last-end: a
    consumer-paced stage (one GET issued per block the hash pipeline
    drains) would otherwise count its idle gaps as GET time and report a
    hash-bound scan as GET-bound.  With a window of W and the stage
    saturated, seconds/wall ~= W — the overlap factor the bench reports
    (ISSUE 2 acceptance).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.seconds = 0.0  # aggregate per-thread GET seconds
        self.items = 0
        self.errors = 0
        self._active = 0
        self._busy = 0.0
        self._active_since: Optional[float] = None

    @property
    def wall(self) -> float:
        with self._lock:
            busy = self._busy
            if self._active_since is not None:
                busy += time.perf_counter() - self._active_since
        return busy

    def _begin(self, start: float) -> None:
        with self._lock:
            if self._active == 0:
                self._active_since = start
            self._active += 1

    def _record(self, start: float, end: float) -> None:
        with self._lock:
            self.seconds += end - start
            self.items += 1
            self._active -= 1
            if self._active == 0 and self._active_since is not None:
                self._busy += end - self._active_since
                self._active_since = None

    def _record_error(self) -> None:
        with self._lock:
            self.errors += 1


class _Unsubmitted(Exception):
    """`pool.submit` failed on a pool thread: the stage's failure, not an
    item's, so the consumer re-raises what it carries whatever `on_error`
    says, as it would had it submitted the item itself."""


class _Slot:
    """One item from the moment the consumer pulled it until it is
    consumed.  `fut` is the stage's own: the consumer can wait on an item
    that no thread has submitted yet."""

    __slots__ = ("item", "ref", "fut", "job")

    def __init__(self, item, ref) -> None:
        self.item = item
        self.ref = ref  # the consumer's span, for the pool thread
        self.fut: Future = Future()
        self.job: Optional[Future] = None  # the pool's, once submitted


def fetch_ordered(
    items: Iterable[T],
    fn: Callable[[T], R],
    pool,
    window: int,
    on_error: str = "raise",
    stats: Optional[FetchStats] = None,
    ahead: int = 0,
) -> Iterator[tuple[T, R]]:
    """Run `fn(item)` over `items` on `pool`, up to `window` running at
    once, yielding `(item, result)` strictly in input order.

    `ahead` lets the stage fetch past the window: up to `window + ahead`
    items are fetched or fetching before the consumer takes the oldest
    (that many results buffered at most), still only `window` calls
    running.  Pass what the consumer takes between two stretches of its
    own work; 0 (the default) submits an item only as the consumer pulls.

    on_error="raise": the first failing item re-raises (in input order) and
    the stage cancels everything still queued — for paths where a missing
    block is corruption (compact).
    on_error="skip": failing items are logged and dropped from the output —
    for scans that must cover everything else (gc --dedup).  A
    NotFoundError under "skip" is logged at debug only: bulk scans racing
    deletions are expected.

    A BreakerOpenError re-raises even under "skip": an open circuit is not
    a per-item failure — every remaining item would fast-fail identically,
    so the stage aborts instead of burning the whole input on EIO churn.

    A pool shut down under a live stage (`CachedStore.close()` cancels
    what its pools have queued): a cancelled call is a failed item, a
    CancelledError under the policy above, and the next submit, from
    whichever thread, raises the pool's own refusal to the consumer.
    """
    if on_error not in ("raise", "skip"):
        raise ValueError(f"on_error: {on_error!r}")
    window = max(1, int(window))
    depth = window + max(0, int(ahead))

    def timed(item: T, ref) -> R:
        _INFLIGHT.inc()
        start = time.perf_counter()
        if stats is not None:
            stats._begin(start)
        try:
            # spans `fn` opens on the pool thread hang off the span the
            # consumer was in when it pulled the item
            with _TR.carried(ref):
                out = fn(item)
        except BaseException:
            if stats is not None:
                stats._record_error()
            raise
        finally:
            end = time.perf_counter()
            _INFLIGHT.dec()
            if stats is not None:
                stats._record(start, end)
        return out

    # The consumer alone touches `items` and `inflight`.  `todo` (pulled,
    # not yet submitted), `running` and `closed` are shared with the pool
    # threads, each of which starts the next call as its own ends.
    inflight: deque[_Slot] = deque()
    todo: deque[_Slot] = deque()
    lock = threading.Lock()
    running = 0
    closed = False
    it = iter(items)

    def claim(new: Optional[_Slot] = None, finished: int = 0) -> list[_Slot]:
        """Account for one pulled item or one finished call, and take what
        may start now."""
        nonlocal running
        with lock:
            running -= finished
            if new is not None:
                todo.append(new)
            out = []
            while todo and running < window and not closed:
                out.append(todo.popleft())
                running += 1
        return out

    def submit(slot: _Slot) -> None:
        # outside the lock: a BACKGROUND submit may wait for queue space
        job = pool.submit(run, slot)
        slot.job = job
        # the callback holds the slot's future, not the slot: slot -> job ->
        # callback -> slot would be a cycle, and a consumed 4 MiB block
        # would wait for the collector instead of being freed at once
        job.add_done_callback(
            lambda j, fut=slot.fut: j.cancelled() and dropped(fut))
        if closed:  # abandoned meanwhile, after its sweep read `job`
            job.cancel()

    def dropped(fut: Future) -> None:
        """The pool cancelled a call it had queued (`CachedStore.close()`
        under a live stage): `run` never runs for it, so its end is counted
        here and the consumer, who waits on the stage's own future, is
        told.  Nothing is started from here — this is the thread that
        shuts the pool down, and it may be inside the pool's lock — so
        what waited unsubmitted goes the same way; what the consumer or a
        running call submits afterwards, the closed pool refuses."""
        nonlocal running
        with lock:
            running -= 1
            stranded = [s.fut for s in todo]
            todo.clear()
        for f in (fut, *stranded):
            f.set_exception(CancelledError())

    def run(slot: _Slot) -> None:
        try:
            out, err = timed(slot.item, slot.ref), None
        except BaseException as e:
            out, err = None, e
        # the call's end is counted before the consumer can see its result:
        # whoever pulls an item because of it finds the cap already open
        start = claim(finished=1)
        if err is None:
            slot.fut.set_result(out)
        else:
            slot.fut.set_exception(err)
        for nxt in start:
            try:
                submit(nxt)
            except Exception as e:
                nxt.fut.set_exception(_Unsubmitted(e))

    def drain_one() -> Iterator[tuple[T, R]]:
        slot = inflight.popleft()
        item, fut = slot.item, slot.fut
        ready = fut.done()
        (_WAIT_READY if ready else _WAIT_BLOCKED).inc()
        try:
            with _TR.span("chunk", "fetch", stage="wait", hist=_H_WAIT) as sp:
                if sp.active:
                    sp.set(ready=ready)
                out = fut.result()
        except _Unsubmitted as e:
            raise e.args[0]
        except Exception as e:
            if on_error == "raise" or isinstance(e, BreakerOpenError):
                raise
            if isinstance(e, NotFoundError):
                logger.debug("fetch %s: %s", item, e)
            else:
                logger.warning("fetch %s: %s", item, e)
            return
        yield item, out

    def pull(item: T) -> None:
        # a function of its own: no name of this generator's frame keeps a
        # slot, and the block it will hold, past its consumption
        slot = _Slot(item, _TR.current_ref())
        inflight.append(slot)
        # with no look-ahead the running cap is never full here, and
        # every call is submitted from this thread as pulled
        for nxt in claim(new=slot):
            submit(nxt)

    try:
        while True:
            for item in it:
                pull(item)
                if len(inflight) >= depth:
                    break
            if not inflight:
                break
            yield from drain_one()
    finally:
        # error or abandoned generator: don't leave queued work behind
        with lock:
            closed = True
        for slot in inflight:
            if slot.job is not None:
                slot.job.cancel()
