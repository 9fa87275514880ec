"""Ordered bounded-window parallel fetch stage (chunk/parallel.py).

ISSUE 2 acceptance: results yield in input order under out-of-order
completion, the in-flight window is a hard bound (gating fake store), the
per-item error policy behaves (skip vs raise), and concurrent fetches of
one key collapse onto the store's singleflight leader.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from juicefs_tpu.chunk import CachedStore, ChunkConfig, block_key
from juicefs_tpu.chunk.parallel import FetchStats, fetch_ordered
from juicefs_tpu.object import MemStorage
from juicefs_tpu.object.interface import NotFoundError


@pytest.fixture
def pool():
    p = ThreadPoolExecutor(max_workers=8, thread_name_prefix="t-fetch")
    yield p
    p.shutdown(wait=True)


def test_yields_in_input_order_under_out_of_order_completion(pool):
    # later items complete FIRST (reverse delays): output must not reorder
    def fn(i):
        time.sleep((9 - i) * 0.01)
        return i * 10

    out = list(fetch_ordered(range(10), fn, pool, window=8))
    assert out == [(i, i * 10) for i in range(10)]


def test_window_bounds_concurrent_gets(pool):
    # gating fake store: every GET records concurrency; the stage must
    # never have more than `window` in flight even though the pool has 8
    # workers and 40 items are offered
    lock = threading.Lock()
    state = {"cur": 0, "max": 0}

    def gated_get(i):
        with lock:
            state["cur"] += 1
            state["max"] = max(state["max"], state["cur"])
        time.sleep(0.005)
        with lock:
            state["cur"] -= 1
        return i

    list(fetch_ordered(range(40), gated_get, pool, window=3))
    assert state["max"] <= 3
    assert state["max"] >= 2  # it DID overlap (not accidentally serial)


def test_buffered_results_never_exceed_window(pool):
    # item 0 is the slow head: everything else completes and must wait,
    # but completed-minus-consumed can never exceed the window
    done = {"n": 0}
    lock = threading.Lock()
    max_buffered = {"n": 0}

    def fn(i):
        if i == 0:
            time.sleep(0.05)
        with lock:
            done["n"] += 1
        return i

    consumed = 0
    for _ in fetch_ordered(range(20), fn, pool, window=4):
        with lock:
            max_buffered["n"] = max(max_buffered["n"], done["n"] - consumed)
        consumed += 1
    assert max_buffered["n"] <= 4


def test_error_policy_skip_drops_item_and_counts(pool):
    stats = FetchStats()

    def fn(i):
        if i in (2, 5):
            raise IOError("backend hiccup")
        if i == 7:
            raise NotFoundError("gone")
        return i

    out = list(fetch_ordered(range(10), fn, pool, window=4,
                             on_error="skip", stats=stats))
    assert [i for i, _ in out] == [0, 1, 3, 4, 6, 8, 9]
    assert stats.errors == 3
    assert stats.items == 10  # every call recorded, errored or not


def test_error_policy_raise_propagates_in_input_order(pool):
    seen = []

    def fn(i):
        if i == 3:
            raise ValueError("block 3 corrupt")
        return i

    gen = fetch_ordered(range(10), fn, pool, window=4, on_error="raise")
    with pytest.raises(ValueError, match="block 3"):
        for i, _ in gen:
            seen.append(i)
    assert seen == [0, 1, 2]  # everything before the bad item arrived


def test_invalid_error_policy_rejected(pool):
    with pytest.raises(ValueError):
        next(fetch_ordered([1], lambda x: x, pool, 1, on_error="ignore"))


def test_stats_wall_is_busy_time_not_span(pool):
    # consumer-paced stage (hash-bound scan shape): GETs are instant but a
    # new one is only issued as the consumer drains.  Busy wall must stay
    # near zero — first-start-to-last-end would count the consumer's time
    # as GET time and misreport the bottleneck.
    stats = FetchStats()
    t0 = time.perf_counter()
    for _ in fetch_ordered(range(10), lambda i: i, pool, window=2,
                           stats=stats):
        time.sleep(0.02)  # the "hash" stage
    elapsed = time.perf_counter() - t0
    assert elapsed >= 0.15
    assert stats.wall < elapsed / 3  # idle gaps are NOT attributed to GET


def test_stats_wall_vs_aggregate_show_overlap(pool):
    # 8 sleeps of 30ms through a window of 8: aggregate thread time is
    # ~240ms but wall is ~30ms — the overlap factor the bench reports
    stats = FetchStats()
    list(fetch_ordered(range(8), lambda i: time.sleep(0.03), pool,
                       window=8, stats=stats))
    assert stats.seconds >= 8 * 0.025
    assert stats.wall < stats.seconds / 2  # genuinely overlapped


class _GatedStorage(MemStorage):
    """get() parks until released; counts raw GETs per key."""

    def __init__(self):
        super().__init__()
        self.release = threading.Event()
        self.get_calls = 0
        self._glock = threading.Lock()

    def get(self, key, off=0, size=-1):
        with self._glock:
            self.get_calls += 1
        self.release.wait(timeout=5)
        return super().get(key, off, size)


def test_singleflight_dedups_scan_and_reader(pool):
    # a dedup-scan fetch and a reader load of the SAME block in flight
    # concurrently must collapse to one storage GET (singleflight leader)
    storage = _GatedStorage()
    store = CachedStore(storage, ChunkConfig(block_size=1 << 16,
                                             cache_size=1))
    try:
        data = b"z" * (1 << 16)
        w = store.new_writer(5)
        w.write_at(data, 0)
        w.finish(len(data))
        key = block_key(5, 0, 1 << 16)
        storage.get_calls = 0

        results = []

        def scan():
            results.extend(fetch_ordered(
                [key],
                lambda k: store._load_block(k, 1 << 16, cache_after=False),
                store._rpool, window=2,
            ))

        # the leader is parked on the gate, so the flight stays open until
        # we release it — wait for BOTH the leader's GET and the follower's
        # singleflight join (a fixed sleep flakes under full-suite load)
        from juicefs_tpu.metric import global_registry

        shared = global_registry()._metrics["juicefs_singleflight_shared"]
        s0 = shared.value  # one follower join is the target delta
        t_scan = threading.Thread(target=scan)
        t_scan.start()
        reader_out = []
        t_read = threading.Thread(
            target=lambda: reader_out.append(store._load_block(key, 1 << 16))
        )
        t_read.start()
        deadline = time.time() + 5
        while (storage.get_calls == 0 or shared.value < s0 + 1) \
                and time.time() < deadline:
            time.sleep(0.005)
        storage.release.set()
        t_scan.join(timeout=5)
        t_read.join(timeout=5)
        assert results == [(key, data)]
        assert reader_out == [data]
        assert storage.get_calls == 1  # the follower shared the leader's GET
    finally:
        store.close()


def test_store_remove_counts_only_real_errors():
    class FlakyDelete(MemStorage):
        """MemStorage.delete silently ignores missing keys; real backends
        raise NotFoundError — model that so the idempotent branch runs."""

        def __init__(self):
            super().__init__()
            self.fail_keys = set()

        def delete(self, key):
            if key in self.fail_keys:
                raise IOError("backend down")
            with self._lock:
                if key not in self._data:
                    raise NotFoundError(key)
            return super().delete(key)

    storage = FlakyDelete()
    store = CachedStore(storage, ChunkConfig(block_size=1 << 16,
                                             max_retries=1))
    try:
        data = b"y" * (3 << 16)
        w = store.new_writer(9)
        w.write_at(data, 0)
        w.finish(len(data))
        # one real failure; the others delete fine
        storage.fail_keys.add(block_key(9, 1, 1 << 16))
        assert store.remove(9, len(data)) == 1
        # second pass: the two deleted blocks are NotFound (idempotent,
        # not errors), the flaky one still fails
        assert store.remove(9, len(data)) == 1
        storage.fail_keys.clear()
        assert store.remove(9, len(data)) == 0  # all NotFound now: clean
    finally:
        store.close()


def test_fill_cache_parallel_and_raises():
    store = CachedStore(MemStorage(), ChunkConfig(block_size=1 << 16))
    try:
        data = b"w" * (4 << 16)
        w = store.new_writer(11)
        w.write_at(data, 0)
        w.finish(len(data))
        store.evict_cache(11, len(data))
        store.fill_cache(11, len(data))
        assert store.check_cache(11, len(data)) == 4
        # a missing slice raises (fill is an integrity-sensitive path)
        with pytest.raises(NotFoundError):
            store.fill_cache(404, 1 << 16)
    finally:
        store.close()


def test_prefetcher_close_stops_workers():
    """Since ISSUE 6 the prefetcher owns no threads — it submits to the
    unified scheduler at PREFETCH class.  close() drains its own work and
    refuses new fetches; the shared scheduler workers keep running."""
    from juicefs_tpu.chunk.prefetch import Prefetcher

    fetched = []
    p = Prefetcher(lambda k: fetched.append(k) or True, workers=2)
    p.fetch(("k", 1))
    deadline = time.time() + 2
    while not fetched and time.time() < deadline:
        time.sleep(0.01)
    assert fetched == [("k", 1)]
    p.close()
    # a fetch after close is dropped, never submitted
    p.fetch(("k2", 1))
    time.sleep(0.05)
    assert fetched == [("k", 1)]
    # the scheduler the prefetcher rode is still alive for other users
    from juicefs_tpu.qos import IOClass, global_scheduler

    ex = global_scheduler().executor("download", IOClass.FOREGROUND)
    assert ex.submit(lambda: 7).result(timeout=5) == 7
    ex.shutdown()


def test_pipeline_inflight_depth_preserves_results():
    from juicefs_tpu.tpu.pipeline import HashPipeline, PipelineConfig
    from juicefs_tpu.tpu.jth256 import jth256

    blocks = [bytes([i]) * 4096 for i in range(10)]
    for depth in (1, 2, 4):
        pipe = HashPipeline(PipelineConfig(
            backend="cpu", batch_blocks=3, pad_lanes=1,
            max_inflight_batches=depth,
        ))
        out = pipe.hash_blocks(blocks)
        assert out == [jth256(b) for b in blocks]


# -- look-ahead: `ahead` items fetched past the window (ISSUE 32) ------------

class _GatedFn:
    """The file's gating fake store as a callable: counts calls running at
    once, calls begun and calls ended; a call may be held on an event."""

    def __init__(self, hold=(), delay=0.0):
        self.lock = threading.Lock()
        self.cur = self.max_running = self.begun = self.ended = 0
        self.started: list = []
        self.hold = set(hold)
        self.release = threading.Event()
        self.delay = delay

    def __call__(self, i):
        with self.lock:
            self.cur += 1
            self.begun += 1
            self.started.append(i)
            self.max_running = max(self.max_running, self.cur)
        try:
            if i in self.hold:
                assert self.release.wait(timeout=10)
            elif self.delay:
                time.sleep(self.delay)
            return i * 10
        finally:
            with self.lock:
                self.cur -= 1
                self.ended += 1


def _until(cond, timeout=10.0):
    deadline = time.time() + timeout
    while not cond() and time.time() < deadline:
        time.sleep(0.002)
    return cond()


def test_ahead_runs_window_calls_and_fetches_window_plus_ahead(pool):
    # 8 pool workers, window 3, ahead 5: never more than 3 calls run, and a
    # consumer that stands still finds window + ahead = 8 items fetched or
    # fetching, not one more
    fn = _GatedFn(delay=0.002)
    gen = fetch_ordered(range(40), fn, pool, window=3, ahead=5)
    assert next(gen) == (0, 0)
    assert _until(lambda: fn.ended == 8)  # nothing pulled them: look-ahead
    time.sleep(0.05)
    assert fn.begun == 8
    consumed, most = 1, 0
    for i, out in gen:
        assert (i, out) == (consumed, consumed * 10)
        consumed += 1
        most = max(most, fn.begun - consumed)
    assert consumed == 40
    assert most <= 8
    assert 2 <= fn.max_running <= 3


def test_ahead_buffers_at_most_window_plus_ahead(pool):
    # item 0 is held: everything behind it completes and waits, but
    # completed-minus-consumed never passes window + ahead
    fn = _GatedFn(hold={0})
    gen = fetch_ordered(range(30), fn, pool, window=4, ahead=6)
    got = []
    t = threading.Thread(target=lambda: got.extend(gen), daemon=True)
    t.start()
    assert _until(lambda: fn.ended == 9)  # all but the held head
    time.sleep(0.05)
    assert fn.begun == 10 and fn.ended == 9
    fn.release.set()
    t.join(timeout=10)
    assert not t.is_alive()
    assert got == [(i, i * 10) for i in range(30)]
    assert fn.max_running <= 4


def test_ahead_yields_in_input_order_under_out_of_order_completion(pool):
    def fn(i):
        time.sleep((11 - i) * 0.004)
        return i * 10

    out = list(fetch_ordered(range(12), fn, pool, window=4, ahead=8))
    assert out == [(i, i * 10) for i in range(12)]


@pytest.mark.parametrize("ahead", [0, 5])
def test_error_policies_hold_with_and_without_ahead(pool, ahead):
    from juicefs_tpu.object.resilient import BreakerOpenError

    def fn(i):
        if i in (2, 5):
            raise IOError("backend hiccup")
        if i == 7:
            raise NotFoundError("gone")
        return i

    stats = FetchStats()
    out = list(fetch_ordered(range(12), fn, pool, window=3, on_error="skip",
                             stats=stats, ahead=ahead))
    assert [i for i, _ in out] == [0, 1, 3, 4, 6, 8, 9, 10, 11]
    assert (stats.errors, stats.items) == (3, 12)

    seen = []
    with pytest.raises(IOError, match="hiccup"):
        for i, _ in fetch_ordered(range(12), fn, pool, window=3,
                                  on_error="raise", ahead=ahead):
            seen.append(i)
    assert seen == [0, 1]

    def tripped(i):
        if i == 4:
            raise BreakerOpenError("circuit open")
        return i

    seen = []
    with pytest.raises(BreakerOpenError):  # even under "skip"
        for i, _ in fetch_ordered(range(12), tripped, pool, window=3,
                                  on_error="skip", ahead=ahead):
            seen.append(i)
    assert seen == [0, 1, 2, 3]


@pytest.mark.parametrize("workers,may_start", [(1, {0, 1}), (8, {0, 1, 2, 3})])
def test_abandoned_generator_with_ahead_leaves_nothing_queued(workers,
                                                              may_start):
    # one worker: items 2 and 3 are queued in the pool when the consumer
    # walks away; eight: 1-3 run (the window) and 4.. wait in the stage.
    # Nothing that had not begun by then ever runs.
    p = ThreadPoolExecutor(max_workers=workers, thread_name_prefix="t-aband")
    try:
        fn = _GatedFn(hold=set(range(1, 20)))
        gen = fetch_ordered(range(20), fn, p, window=3, ahead=6)
        assert next(gen) == (0, 0)
        if workers > 1:
            assert _until(lambda: fn.begun == 4)
        gen.close()
        fn.release.set()
    finally:
        p.shutdown(wait=True)
    assert set(fn.started) <= may_start
    assert fn.begun == fn.ended


def test_stalling_consumer_finds_every_later_batch_ready(pool):
    # the scan's shape: pull a batch, then stand in the "pack" long enough
    # for the pool to fetch the next. From the second batch on every pull
    # finds its block there — by the stage's own counter, not by a timing.
    from juicefs_tpu.chunk.parallel import _WAIT_BLOCKED, _WAIT_READY

    class Counting:
        """Counts pool jobs that have returned: by then the stage has the
        result (a call's own end comes a moment before that)."""

        def __init__(self, inner):
            self.inner, self.finished = inner, 0
            self.lock = threading.Lock()

        def submit(self, job, *a):
            def counted():
                try:
                    return job(*a)
                finally:
                    with self.lock:
                        self.finished += 1
            return self.inner.submit(counted)

    batch, n = 8, 40
    counting = Counting(pool)
    gen = fetch_ordered(range(n), lambda i: time.sleep(0.001), counting,
                        window=3, ahead=batch)
    blocked_after_first = None
    ready0 = _WAIT_READY.value
    for k in range(n // batch):
        for j in range(batch):
            assert next(gen)[0] == k * batch + j
        if k == 0:
            blocked_after_first = _WAIT_BLOCKED.value
        # the "pack": until every call the stage may start has returned —
        # the generator stands with window + ahead - 1 items pulled past
        # the one it yielded (a count of returns alone could be met by
        # later items while one of the next batch still runs)
        want = min(n, (k + 1) * batch + 3 + batch - 1)
        assert _until(lambda: counting.finished >= want)
    assert list(gen) == []
    assert _WAIT_BLOCKED.value == blocked_after_first
    assert _WAIT_READY.value - ready0 >= n - batch


def test_failed_submit_on_a_pool_thread_reaches_the_consumer():
    # the stage submits from pool threads too: a pool that refuses there
    # (shut down under a live stage) must fail the scan in the consumer,
    # under "skip" as well, not hang it
    class Refusing:
        def __init__(self, inner, after):
            self.inner, self.left = inner, after

        def submit(self, *a, **kw):
            self.left -= 1
            if self.left < 0:
                raise RuntimeError("cannot schedule new futures")
            return self.inner.submit(*a, **kw)

    p = ThreadPoolExecutor(max_workers=4, thread_name_prefix="t-refuse")
    refusing = Refusing(p, 9)
    got, err = [], []

    def consume():
        try:
            for pair in fetch_ordered(range(30), lambda i: i, refusing,
                                      window=2, on_error="skip", ahead=10):
                got.append(pair)
                # stand still until a pool thread has met the refusal
                assert _until(lambda: refusing.left < 0)
        except RuntimeError as e:
            err.append(e)

    try:
        t = threading.Thread(target=consume, daemon=True)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    finally:
        p.shutdown(wait=True)
    assert got == [(i, i) for i in range(9)]  # what was submitted, in order
    assert len(err) == 1 and "cannot schedule" in str(err[0])


class _Pools:
    """The two kinds of pool the stage runs on, two workers each: compact's
    own ThreadPoolExecutor, and a BACKGROUND executor of a scheduler lane
    (`CachedStore._bulk_pool`: the scan, `fill_cache`, `remove`)."""

    def __init__(self, kind):
        from juicefs_tpu.qos.scheduler import IOClass, Scheduler

        self.sched = None
        if kind == "lane":
            self.sched = Scheduler()
            self.pool = self.sched.executor("t-cancel", IOClass.BACKGROUND,
                                            width=2)
        else:
            self.pool = ThreadPoolExecutor(max_workers=2,
                                           thread_name_prefix="t-cancel")

    def close(self):
        self.pool.shutdown(wait=True)
        if self.sched is not None:
            self.sched.close()


@pytest.mark.parametrize("kind", ["threads", "lane"])
@pytest.mark.parametrize("ahead", [0, 6])
@pytest.mark.parametrize("on_error", ["raise", "skip"])
def test_a_pool_shut_down_under_a_live_stage_ends_the_consumer(kind, ahead,
                                                               on_error):
    # `CachedStore.close()` cancels what its pools have queued. Two calls
    # run (held), two are queued in the pool, `ahead` more wait in the
    # stage; the pool is shut down with cancel_futures and the gate opens.
    # The consumer gets what ran, meets the cancelled calls as failed items
    # (raise: CancelledError; skip: skipped) and ends: it never waits on a
    # call that nobody will run.
    from concurrent.futures import CancelledError

    pools = _Pools(kind)
    fn = _GatedFn(hold={0, 1})
    got, err = [], []

    def consume():
        try:
            got.extend(fetch_ordered(range(4 + ahead), fn, pools.pool,
                                     window=4, on_error=on_error,
                                     ahead=ahead))
        except Exception as e:
            err.append(e)

    t = threading.Thread(target=consume, daemon=True)
    try:
        t.start()
        assert _until(lambda: fn.begun == 2)
        time.sleep(0.02)  # the consumer stands on the held head
        pools.pool.shutdown(wait=False, cancel_futures=True)
        fn.release.set()
        t.join(timeout=10)
        assert not t.is_alive()
    finally:
        fn.release.set()
        pools.close()
    assert got == [(0, 0), (1, 10)]
    assert fn.started == [0, 1] or fn.started == [1, 0]
    if on_error == "raise":
        assert len(err) == 1 and isinstance(err[0], CancelledError)
    else:
        assert err == []


@pytest.mark.parametrize("kind", ["threads", "lane"])
@pytest.mark.parametrize("ahead", [0, 6])
def test_a_pool_shut_down_refuses_what_a_skipping_consumer_pulls_next(kind,
                                                                     ahead):
    # as above with input left to pull: under "skip" the consumer's next
    # submit (or a running call's) meets the closed pool and the stage
    # fails with the pool's own refusal, as it did before it had a depth
    pools = _Pools(kind)
    fn = _GatedFn(hold={0, 1})
    got, err = [], []

    def consume():
        try:
            got.extend(fetch_ordered(range(200), fn, pools.pool, window=4,
                                     on_error="skip", ahead=ahead))
        except RuntimeError as e:
            err.append(e)

    t = threading.Thread(target=consume, daemon=True)
    try:
        t.start()
        assert _until(lambda: fn.begun == 2)
        time.sleep(0.02)
        pools.pool.shutdown(wait=False, cancel_futures=True)
        fn.release.set()
        t.join(timeout=10)
        assert not t.is_alive()
    finally:
        fn.release.set()
        pools.close()
    assert got == [(0, 0), (1, 10)][:len(got)]
    assert set(fn.started) == {0, 1}
    assert len(err) == 1 and "shutdown" in str(err[0])


@pytest.mark.parametrize("ahead,submitters", [(0, "consumer"), (6, "both")])
def test_without_ahead_every_call_is_submitted_by_the_consumer(pool, ahead,
                                                               submitters):
    # compact, remove and fill_cache pass no `ahead`: the stage then submits
    # from the consumer's thread alone, as it did before it had a depth (a
    # call's end is counted before its result shows, so the cap is open
    # whenever the consumer pulls). With `ahead`, pool threads submit too.
    class Recording:
        def __init__(self, inner):
            self.inner, self.by = inner, set()

        def submit(self, *a, **kw):
            self.by.add(threading.get_ident())
            return self.inner.submit(*a, **kw)

    import sys

    rec = Recording(pool)
    n = 2000
    out = []
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)  # a thread loses the lock between any two steps
    try:
        for pair in fetch_ordered(range(n), lambda i: i, rec, window=3,
                                  ahead=ahead):
            out.append(pair)
            if ahead and len(out) == 1:
                time.sleep(0.02)  # a "pack": the pool runs on without a pull
    finally:
        sys.setswitchinterval(old)
    assert out == [(i, i) for i in range(n)]
    me = threading.get_ident()
    if submitters == "consumer":
        assert rec.by == {me}
    else:
        assert me in rec.by and len(rec.by) > 1


@pytest.mark.parametrize("ahead", [0, 5])
def test_a_consumed_result_is_freed_at_once(pool, ahead):
    # a fetched block is 4 MiB: once the consumer lets go of it nothing of
    # the stage may keep it alive, and no reference cycle either — with
    # the collector off, it is gone by the next pull (the scan's GET
    # buffers are recycled by malloc only if they are freed, PERF.md PR 26)
    import gc
    import weakref

    class Block:
        pass

    refs = []

    def fn(i):
        b = Block()
        refs.append((i, weakref.ref(b)))
        return b

    gc.collect()
    gc.disable()
    try:
        gen = fetch_ordered(range(40), fn, pool, window=3, ahead=ahead)
        for n, (i, b) in enumerate(gen):
            del b
            # (a pool thread may still be on its way out of the call)
            assert _until(lambda: not [j for j, r in refs
                                       if j < i and r() is not None], 2.0)
        assert n == 39
        del gen
        assert _until(lambda: not [j for j, r in refs if r() is not None],
                      2.0)
    finally:
        gc.enable()


def test_ahead_stress_keeps_order_and_the_running_cap():
    # more workers than cores, a short switch interval: a lost update of
    # the running count would pass the cap or strand the tail
    import sys

    p = ThreadPoolExecutor(max_workers=32, thread_name_prefix="t-stress")
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    fn = _GatedFn()
    out = []
    try:
        t = threading.Thread(
            target=lambda: out.extend(
                fetch_ordered(range(3000), fn, p, window=5, ahead=7)),
            daemon=True)
        t.start()
        t.join(timeout=60)
        assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
        p.shutdown(wait=True)
    assert out == [(i, i * 10) for i in range(3000)]
    assert fn.max_running <= 5


def test_dedup_scan_fetches_ahead_inside_threads(tmp_path, capsys,
                                                 monkeypatch):
    """`gc --dedup --threads 3` over 80 blocks (three hash batches): never
    more than 3 GETs at once, more than 3 blocks fetched before the hash
    takes them, and digests and index rows as with `--threads 1`."""
    import json

    from juicefs_tpu.cmd import main, open_meta
    from test_trace import _scan_volume

    meta_url = _scan_volume(tmp_path, blocks=80, block_kib=64)
    state = {"cur": 0, "max": 0}
    lock = threading.Lock()
    real = CachedStore._load_block

    def counted(self, key, size, **kw):
        with lock:
            state["cur"] += 1
            state["max"] = max(state["max"], state["cur"])
        try:
            time.sleep(0.002)
            return real(self, key, size, **kw)
        finally:
            with lock:
                state["cur"] -= 1

    monkeypatch.setattr(CachedStore, "_load_block", counted)

    def scan(threads):
        index = str(tmp_path / f"index{threads}.json")
        capsys.readouterr()
        assert main(["gc", meta_url, "--dedup", "--hash-backend", "xla",
                     "--threads", str(threads), "--dedup-index", index]) == 0
        stats = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        m, _ = open_meta(meta_url)
        rows = sorted(m.scan_block_digests())
        with open(index) as f:
            return stats, rows, json.load(f), m

    stats, rows, digests, m = scan(3)
    assert stats["hashed_now"] == 80 and len(rows) == 80
    assert stats["fetch_window"] == 3
    assert stats["fetch_ahead"] == 32  # the pipeline's batch_blocks
    assert 2 <= state["max"] <= 3
    m.delete_block_digests([(sid, indx) for sid, indx, _, _ in rows])
    state["max"] = 0
    stats1, rows1, digests1, _ = scan(1)
    assert stats1["hashed_now"] == 80 and stats1["fetch_window"] == 1
    assert state["max"] == 1
    assert rows1 == rows and digests1 == digests
