"""Object-plane resilience layer (ISSUE 3): error classification,
deadline-aware retries with abandonment, per-backend circuit breaker with
half-open probes, hedged GETs, throttle shed, and the no-bare-store lint.
"""

from __future__ import annotations

import threading
import time

import pytest

from juicefs_tpu.metric import global_registry
from juicefs_tpu.object import (
    FileStorage,
    create_storage,
    new_checksummed,
    sharded,
    with_prefix,
)
from juicefs_tpu.object.fault import FaultyStore, InjectedThrottle
from juicefs_tpu.object.interface import (
    NotFoundError,
    PermanentError,
    ThrottleError,
)
from juicefs_tpu.object.metered import metered
from juicefs_tpu.object.resilient import (
    BreakerOpenError,
    BreakerState,
    CircuitBreaker,
    DeadlineExceeded,
    ErrorClass,
    ResilientStorage,
    RetryPolicy,
    classify,
    resilience_snapshot,
    resilient,
)


def counter(name, *labels):
    m = global_registry()._metrics[name]
    return m.labels(*labels) if labels else m


class CountingMem:
    """Minimal inner store counting every backend call — the blackout
    drills assert ZERO of these while the breaker is open."""

    def __init__(self):
        self._s = create_storage("mem://")
        self.calls = 0
        self._mu = threading.Lock()

    def _count(self):
        with self._mu:
            self.calls += 1

    def string(self):
        return "mem://counting"

    def get(self, key, off=0, limit=-1):
        self._count()
        return self._s.get(key, off, limit)

    def put(self, key, data):
        self._count()
        self._s.put(key, data)

    def delete(self, key):
        self._count()
        self._s.delete(key)

    def head(self, key):
        self._count()
        return self._s.head(key)

    def list_all(self, prefix="", marker=""):
        self._count()
        return self._s.list_all(prefix, marker)


# -- classification ----------------------------------------------------------

def test_classify_error_classes():
    assert classify(NotFoundError("k")) is ErrorClass.PERMANENT
    assert classify(PermanentError("denied")) is ErrorClass.PERMANENT
    assert classify(ThrottleError("slow down")) is ErrorClass.THROTTLE
    assert classify(InjectedThrottle("x")) is ErrorClass.THROTTLE
    assert classify(IOError("conn reset")) is ErrorClass.TRANSIENT
    # generic errors carrying a driver status code classify by status
    e = IOError("rejected")
    e.status = 403
    assert classify(e) is ErrorClass.PERMANENT
    e.status = 429
    assert classify(e) is ErrorClass.THROTTLE
    e.status = 503
    assert classify(e) is ErrorClass.THROTTLE
    e.status = 500
    assert classify(e) is ErrorClass.TRANSIENT
    e.status = 408  # request timeout is retryable
    assert classify(e) is ErrorClass.TRANSIENT


def test_throttle_backs_off_longer_than_transient():
    p = RetryPolicy(jitter=0.0)
    for attempt in range(6):
        assert (p.backoff(attempt, ErrorClass.THROTTLE)
                > p.backoff(attempt, ErrorClass.TRANSIENT))
    # and both grow exponentially until their caps
    assert p.backoff(1, ErrorClass.TRANSIENT) == 2 * p.backoff(0, ErrorClass.TRANSIENT)
    assert p.backoff(10, ErrorClass.TRANSIENT) == p.cap
    assert p.backoff(10, ErrorClass.THROTTLE) == p.throttle_cap


# -- retries per class -------------------------------------------------------

def test_permanent_errors_are_never_retried():
    inner = CountingMem()
    rs = resilient(inner, policy=RetryPolicy(max_attempts=8, jitter=0.0),
                   hedge=False)
    try:
        with pytest.raises(NotFoundError):
            rs.get("missing")
        assert inner.calls == 1  # exactly one backend attempt
        # auth-analog: a PermanentError from the driver is terminal too
        def denied(key, off=0, limit=-1):
            inner._count()
            raise PermanentError("403")
        inner.get = denied
        with pytest.raises(PermanentError):
            rs.get("denied-key")
        assert inner.calls == 2
    finally:
        rs.close()


def test_transient_and_throttle_retry_counters_per_class():
    t0 = counter("juicefs_object_retries_by_class", "transient").value
    h0 = counter("juicefs_object_retries_by_class", "throttle").value
    inner = CountingMem()
    inner._s.put("k", b"v")
    fails = {"n": 2}

    real_get = inner.get

    def flaky(key, off=0, limit=-1):
        if fails["n"] > 0:
            fails["n"] -= 1
            inner._count()
            raise IOError("transient blip")
        return real_get(key, off, limit)

    inner.get = flaky
    rs = resilient(inner, policy=RetryPolicy(
        max_attempts=8, base=0.001, throttle_base=0.002, jitter=0.0),
        hedge=False)
    try:
        assert rs.get("k") == b"v"
        assert counter("juicefs_object_retries_by_class",
                       "transient").value == t0 + 2
        # throttle: retried too, but counted in its own class
        fails2 = {"n": 1}

        def throttled(key, off=0, limit=-1):
            if fails2["n"] > 0:
                fails2["n"] -= 1
                inner._count()
                raise ThrottleError("429")
            return real_get(key, off, limit)

        inner.get = throttled
        assert rs.get("k") == b"v"
        assert counter("juicefs_object_retries_by_class",
                       "throttle").value == h0 + 1
        assert counter("juicefs_object_retries_by_class",
                       "transient").value == t0 + 2  # unchanged
    finally:
        rs.close()


def test_throttle_sheds_concurrency():
    inner = CountingMem()
    inner._s.put("k", b"v")
    rs = resilient(inner, policy=RetryPolicy(max_attempts=1),
                   hedge=False)
    try:
        limit0 = rs._shed.limit

        def throttled(key, off=0, limit=-1):
            raise ThrottleError("slow down")

        inner.get = throttled
        with pytest.raises(ThrottleError):
            rs.get("k")
        assert rs._shed.limit == max(1, limit0 // 2)
        # a success streak creeps the limit back up
        del inner.get
        for _ in range(10):
            assert rs.get("k") == b"v"
        assert rs._shed.limit == max(1, limit0 // 2) + 1
    finally:
        rs.close()


# -- deadlines / abandonment -------------------------------------------------

def test_hung_call_is_abandoned_at_attempt_timeout_and_retried():
    a0 = counter("juicefs_object_deadline_abandoned", "GET").value
    inner = CountingMem()
    inner._s.put("k", b"payload")
    hang = threading.Event()  # never set: the call truly never returns
    state = {"hung": 0}

    real_get = inner.get

    def hung_once(key, off=0, limit=-1):
        if state["hung"] < 1:
            state["hung"] += 1
            hang.wait(30.0)
            raise IOError("released late")
        return real_get(key, off, limit)

    inner.get = hung_once
    rs = resilient(inner, policy=RetryPolicy(
        deadline=5.0, max_attempts=4, attempt_timeout=0.15,
        base=0.001, jitter=0.0), hedge=False)
    try:
        t0 = time.perf_counter()
        assert rs.get("k") == b"payload"
        took = time.perf_counter() - t0
        assert took < 2.0, f"abandonment did not bound the hang ({took:.2f}s)"
        assert counter("juicefs_object_deadline_abandoned",
                       "GET").value == a0 + 1
    finally:
        hang.set()
        rs.close()


def test_deadline_exhaustion_raises_timeout():
    inner = CountingMem()

    def always_hangs(key, off=0, limit=-1):
        time.sleep(5.0)
        return b""

    inner.get = always_hangs
    rs = resilient(inner, policy=RetryPolicy(
        deadline=0.4, max_attempts=10, attempt_timeout=0.1,
        base=0.001, jitter=0.0), hedge=False)
    try:
        t0 = time.perf_counter()
        with pytest.raises(DeadlineExceeded):
            rs.get("k")
        assert time.perf_counter() - t0 < 1.5
    finally:
        rs.close()


# -- hedged GETs -------------------------------------------------------------

def test_hedged_get_first_response_wins():
    inner = CountingMem()
    inner._s.put("k", b"hedged!")
    state = {"calls": 0}
    gate = threading.Event()

    real_get = inner.get

    def slow_first(key, off=0, limit=-1):
        state["calls"] += 1
        if state["calls"] == 1:  # primary: stuck until released
            gate.wait(10.0)
        return real_get(key, off, limit)

    inner.get = slow_first
    rs = resilient(inner, policy=RetryPolicy(deadline=8.0, max_attempts=2),
                   hedge=True, hedge_delay=0.05)
    w0 = counter("juicefs_object_hedge_wins", rs.metric_backend).value
    try:
        t0 = time.perf_counter()
        assert rs.get("k") == b"hedged!"
        took = time.perf_counter() - t0
        assert took < 2.0, f"hedge did not rescue the slow primary ({took:.2f}s)"
        assert state["calls"] == 2  # a second GET was issued
        assert counter("juicefs_object_hedge_wins",
                       rs.metric_backend).value == w0 + 1
    finally:
        gate.set()
        rs.close()


def test_hedge_not_issued_for_fast_primary():
    inner = CountingMem()
    inner._s.put("k", b"v")
    rs = resilient(inner, hedge=True, hedge_delay=0.5)
    try:
        assert rs.get("k") == b"v"
        assert inner.calls == 1  # no wasted duplicate GET
    finally:
        rs.close()


# -- circuit breaker ---------------------------------------------------------

def test_breaker_trips_fails_fast_and_recovers_via_probes():
    inner = CountingMem()
    inner._s.put("k", b"v")
    br = CircuitBreaker(backend="trip-test", threshold=0.5, min_samples=4,
                        probe_interval=0.05)
    down = {"down": True}

    real_get = inner.get

    def flappy(key, off=0, limit=-1):
        if down["down"]:
            inner._count()
            raise IOError("backend down")
        return real_get(key, off, limit)

    inner.get = flappy
    rs = resilient(inner, policy=RetryPolicy(
        max_attempts=2, base=0.001, jitter=0.0), breaker=br, hedge=False)
    try:
        trips0 = counter("juicefs_object_breaker_trips", "trip-test").value
        for _ in range(3):
            with pytest.raises(IOError):
                rs.get("k")
        assert br.state == BreakerState.OPEN
        assert counter("juicefs_object_breaker_trips",
                       "trip-test").value == trips0 + 1
        assert counter("juicefs_object_breaker_state",
                       "trip-test").value == 1
        # open: fail fast, ZERO backend calls
        calls = inner.calls
        t0 = time.perf_counter()
        with pytest.raises(BreakerOpenError) as ei:
            rs.get("k")
        assert time.perf_counter() - t0 < 0.05
        assert ei.value.errno == 5  # EIO
        assert inner.calls == calls
        # heal: background probes walk open → half-open → closed
        down["down"] = False
        deadline = time.time() + 5.0
        while br.state != BreakerState.CLOSED and time.time() < deadline:
            time.sleep(0.02)
        assert br.state == BreakerState.CLOSED
        assert counter("juicefs_object_breaker_state", "trip-test").value == 0
        assert rs.get("k") == b"v"
    finally:
        rs.close()


def test_breaker_reset_fires_callbacks_and_half_open_refailure_retrips():
    br = CircuitBreaker(backend="cb-test", threshold=0.5, min_samples=2,
                        probe_interval=999.0)  # probes off: drive manually
    resets = []
    br.on_reset(lambda: resets.append(1))
    br.record_failure()
    br.record_failure()
    assert br.state == BreakerState.OPEN
    # manual half-open (as a probe success would)
    br._state = BreakerState.HALF_OPEN
    br.record_failure()  # trial traffic fails: re-trip
    assert br.state == BreakerState.OPEN
    br._state = BreakerState.HALF_OPEN
    br.record_success()
    br.record_success()  # half_open_successes=2 closes + fires reset
    assert br.state == BreakerState.CLOSED
    assert resets == [1]
    br.close()


def test_permanent_errors_do_not_trip_the_breaker():
    inner = CountingMem()
    br = CircuitBreaker(backend="perm-test", threshold=0.5, min_samples=2,
                        probe_interval=999.0)
    rs = resilient(inner, policy=RetryPolicy(max_attempts=1), breaker=br,
                   hedge=False)
    try:
        for _ in range(12):  # a storm of NotFound is a HEALTHY backend
            with pytest.raises(NotFoundError):
                rs.get("nope")
        assert br.state == BreakerState.CLOSED
    finally:
        rs.close()


# -- misc contract -----------------------------------------------------------

def test_resilient_wrap_is_idempotent_and_delegates():
    inner = create_storage("mem://")
    rs = resilient(inner)
    try:
        assert resilient(rs) is rs
        assert isinstance(rs, ResilientStorage)
        inner.put("a", b"1")
        assert rs.get("a") == b"1"
        assert [o.key for o in rs.list_all("")] == ["a"]
        assert rs.head("a").size == 1
        rs.delete("a")
        with pytest.raises(NotFoundError):
            rs.head("a")
        assert rs.limits()["max_part_count"] > 0
    finally:
        rs.close()


def test_breaker_open_gates_listings():
    inner = create_storage("mem://")
    br = CircuitBreaker(backend="gate-test", probe_interval=999.0)
    rs = resilient(inner, breaker=br, hedge=False)
    try:
        br.record_failure()  # force open regardless of rate
        br._trip_locked()
        with pytest.raises(BreakerOpenError):
            rs.list_all("")
        with pytest.raises(BreakerOpenError):
            rs.put("k", b"v")
    finally:
        rs.close()


def test_health_and_snapshot_shapes():
    rs = resilient(create_storage("mem://"))
    try:
        h = rs.health()
        assert h["breaker"]["state"] == "closed"
        assert h["degraded"] is False
        assert "deadline" in h["policy"]
        snap = resilience_snapshot()
        assert isinstance(snap, dict)  # only non-zero series are emitted
    finally:
        rs.close()


def test_lint_resilience_passes_and_catches_bare_stores(tmp_path):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "lint_metrics",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "lint_metrics.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.lint_resilience() == []
    # a consumer module with a bare store is flagged
    bad = tmp_path / "pkg"
    bad.mkdir()
    (bad / "rogue.py").write_text(
        "from juicefs_tpu.object import create_storage\n"
        "s = create_storage('mem://')\n"
        "s.put('k', b'v')\n"
    )
    problems = mod.lint_resilience(root=str(bad))
    assert len(problems) == 1 and "rogue.py" in problems[0]
    # a comment/docstring MENTIONING a wrapper must not satisfy the check
    (bad / "rogue.py").write_text(
        "from juicefs_tpu.object import create_storage\n"
        "# wrapped elsewhere via CachedStore( ... honest, promise\n"
        "s = create_storage('mem://')\n"
    )
    assert len(mod.lint_resilience(root=str(bad))) == 1
    # wrapping fixes it
    (bad / "rogue.py").write_text(
        "from juicefs_tpu.object import create_storage, resilient\n"
        "s = resilient(create_storage('mem://'))\n"
    )
    assert mod.lint_resilience(root=str(bad)) == []


# -- mutation-run survivors (docs/BENCHMARKS.md §6): each test below pins
# -- a behavior a first-order mutant of resilient.py escaped through -----

def test_classify_status_boundaries():
    """400 is the FIRST permanent status and 499 the last (mutant: the
    4xx window off by one)."""
    e = IOError("bad request")
    e.status = 400
    assert classify(e) is ErrorClass.PERMANENT
    e.status = 499
    assert classify(e) is ErrorClass.PERMANENT
    e.status = 399
    assert classify(e) is ErrorClass.TRANSIENT


def test_breaker_state_gauge_contract():
    """The gauge publishes 0/1/2 (closed/open/half-open) — dashboards and
    the drills depend on the exact values."""
    assert int(BreakerState.CLOSED) == 0
    assert int(BreakerState.OPEN) == 1
    assert int(BreakerState.HALF_OPEN) == 2


def test_breaker_trips_at_exact_threshold():
    """failure_rate == threshold must trip (mutant: strict >)."""
    br = CircuitBreaker(backend="exact-thresh", threshold=0.5, min_samples=4,
                        probe_interval=999.0)
    br.record_success()
    br.record_success()
    br.record_failure()
    assert br.state == BreakerState.CLOSED  # 1/3 < 0.5, and < min_samples
    br.record_failure()  # 2/4 == 0.5 at exactly min_samples: trips
    assert br.state == BreakerState.OPEN
    br.close()


def test_shed_limit_never_exceeds_max():
    """A success streak at the cap must not push the limit past max_limit
    (mutant: < vs <=)."""
    inner = CountingMem()
    inner._s.put("k", b"v")
    rs = resilient(inner, hedge=False)
    try:
        for _ in range(25):
            assert rs.get("k") == b"v"
        assert rs._shed.limit == rs._shed.max_limit
    finally:
        rs.close()


def test_hist_quantile_returns_covering_bucket():
    """The hedge delay reads a real quantile, not a degenerate target
    (mutant: q*total -> q//total selects the first bucket always)."""
    from juicefs_tpu.metric import Histogram
    from juicefs_tpu.object.resilient import _hist_quantile

    h = Histogram("q_test", "")
    for _ in range(100):
        h.observe(0.003)  # all mass in the (0.001, 0.005] bucket
    assert _hist_quantile(h, 0.95) == 0.005
    assert _hist_quantile(h, 0.5) == 0.005
    h2 = Histogram("q_test2", "")
    assert _hist_quantile(h2, 0.95) is None  # no samples: no bound


def test_deadline_budget_refuses_oversleeping_backoff():
    """When the next backoff cannot fit in the deadline, the op raises
    NOW instead of sleeping past its budget (mutant: elapsed - delay)."""
    inner = CountingMem()

    def always_fails(key, off=0, limit=-1):
        raise IOError("down")

    inner.get = always_fails
    rs = resilient(inner, policy=RetryPolicy(
        deadline=0.5, max_attempts=10, base=5.0, jitter=0.0), hedge=False)
    try:
        t0 = time.perf_counter()
        with pytest.raises(IOError):
            rs.get("k")
        assert time.perf_counter() - t0 < 1.0, "op slept past its deadline"
    finally:
        rs.close()


def test_put_is_never_hedged():
    """Hedging is GET-only: a slow PUT must not be duplicated even with
    hedging enabled and a zero hedge delay (mutant: `hedge and enabled`
    -> `hedge or enabled`)."""
    inner = CountingMem()

    real_put = inner.put

    def slow_put(key, data):
        time.sleep(0.15)
        real_put(key, data)

    inner.put = slow_put
    rs = resilient(inner, hedge=True, hedge_delay=0.0)
    h0 = counter("juicefs_object_hedged_requests", rs.metric_backend).value
    try:
        rs.put("k", b"v")
        assert counter("juicefs_object_hedged_requests",
                       rs.metric_backend).value == h0, "a PUT hedge was issued"
        time.sleep(0.4)  # any stray duplicate PUT would land here
        assert inner.calls == 1, "a PUT was hedged"
    finally:
        rs.close()


def test_hedge_delay_derived_from_histogram_at_min_samples():
    """Exactly _HEDGE_MIN_SAMPLES observations switch the delay from the
    default to the live p95 bucket bound (mutant: > vs >=)."""
    from juicefs_tpu.metric import global_registry
    from juicefs_tpu.object.resilient import _HEDGE_MIN_SAMPLES, _HIST_NAME

    class HistBackend(CountingMem):
        def string(self):
            return "histtest://x"

    rs = resilient(HistBackend(), hedge=True)  # backend label: "histtest"
    try:
        child = global_registry()._metrics[_HIST_NAME].labels(
            "GET", "histtest")
        for _ in range(_HEDGE_MIN_SAMPLES):
            child.observe(0.2)  # all mass in the (0.1, 0.5] bucket
        assert rs._hedge_after() == 0.5
    finally:
        rs.close()


def test_no_hedge_when_delay_equals_attempt_budget():
    """delay == timeout leaves no room to hedge: the attempt runs
    un-hedged and abandons at its bound (mutant: strict > lets a
    zero-budget hedge fire and count)."""
    inner = CountingMem()

    def hangs(key, off=0, limit=-1):
        time.sleep(10.0)
        return b""

    inner.get = hangs
    rs = resilient(inner, policy=RetryPolicy(
        deadline=5.0, max_attempts=1, attempt_timeout=0.3),
        hedge=True, hedge_delay=0.3)
    h0 = counter("juicefs_object_hedged_requests", rs.metric_backend).value
    try:
        with pytest.raises(DeadlineExceeded):
            rs.get("k")
        assert counter("juicefs_object_hedged_requests",
                       rs.metric_backend).value == h0, "pointless hedge issued"
    finally:
        rs.close()


# -- a host-local store: each attempt on the caller ------------------------------

class ThreadFile(FileStorage):
    """A `file://` store noting the thread each call runs on; `fail`
    raises a transient error on the next n GETs, `hang` blocks every GET
    until set."""

    def __init__(self, root):
        super().__init__(root)
        self.threads = []
        self.fail = 0
        self.hang = None

    def get(self, key, off=0, limit=-1):
        self.threads.append(threading.get_ident())
        if self.hang is not None:
            self.hang.wait(10.0)
        if self.fail > 0:
            self.fail -= 1
            raise OSError("transient blip")
        return super().get(key, off, limit)

    def put(self, key, data):
        self.threads.append(threading.get_ident())
        super().put(key, data)

    def head(self, key):
        self.threads.append(threading.get_ident())
        return super().head(key)

    def delete(self, key):
        self.threads.append(threading.get_ident())
        super().delete(key)


def local_store(tmp_path, **kw):
    drv = ThreadFile(str(tmp_path))
    drv.put("k", b"payload")
    drv.threads.clear()
    return drv, resilient(metered(drv), **kw)


def inline_count(method):
    return counter("juicefs_object_inline_requests", "file", method).value


OPS = {
    "GET": lambda rs: rs.get("k"),
    "PUT": lambda rs: rs.put("k2", b"v"),
    "HEAD": lambda rs: rs.head("k"),
    "DELETE": lambda rs: rs.delete("k"),
}


@pytest.mark.parametrize("method", sorted(OPS))
def test_a_local_stores_attempt_runs_on_the_calling_thread(tmp_path, method):
    drv, rs = local_store(tmp_path)  # hedging on, as the chunk store has it
    n0 = inline_count(method)
    try:
        assert rs.inline and rs.health()["inline"] is True
        OPS[method](rs)
        assert drv.threads == [threading.get_ident()]
        assert rs._pool._seq == 0  # the pool started no thread
        assert inline_count(method) == n0 + 1
    finally:
        rs.close()


def test_an_attempt_bound_keeps_a_local_store_on_the_pool(tmp_path):
    """`attempt_timeout` asks for abandonment: a local GET that outlives
    it is abandoned on the pool, as a remote one is."""
    drv, rs = local_store(tmp_path, policy=RetryPolicy(
        deadline=5.0, max_attempts=1, attempt_timeout=0.1), hedge=False)
    drv.hang = threading.Event()
    a0 = counter("juicefs_object_deadline_abandoned", "GET").value
    n0 = inline_count("GET")
    try:
        assert not rs.inline and rs.health()["inline"] is False
        t0 = time.perf_counter()
        with pytest.raises(DeadlineExceeded):
            rs.get("k")
        assert time.perf_counter() - t0 < 2.0
        assert counter("juicefs_object_deadline_abandoned",
                       "GET").value == a0 + 1
        assert rs._pool._seq == 1
        assert drv.threads and drv.threads[0] != threading.get_ident()
        assert inline_count("GET") == n0
    finally:
        drv.hang.set()
        rs.close()


# case -> (transient failures before the answer, what the GET gives,
# attempts, retries, breaker failures)
LOCAL_OUTCOMES = {
    "transient_twice_then_success": (2, b"payload", 3, 2, 2),
    "not_found_is_permanent": (0, NotFoundError, 1, 0, 0),
}


@pytest.mark.parametrize("case", sorted(LOCAL_OUTCOMES))
def test_a_local_stores_retries_and_breaker_are_the_pools(tmp_path, case):
    fails, answer, attempts, retries, failures = LOCAL_OUTCOMES[case]
    br = CircuitBreaker(backend=f"local-{case}", probe_interval=999.0)
    drv, rs = local_store(tmp_path, breaker=br, policy=RetryPolicy(
        max_attempts=8, base=0.001, jitter=0.0))
    drv.fail = fails
    r0 = counter("juicefs_object_request_retries", "GET").value
    t0 = counter("juicefs_object_retries_by_class", "transient").value
    n0 = inline_count("GET")
    try:
        if answer is NotFoundError:
            with pytest.raises(NotFoundError):
                rs.get("missing")
        else:
            assert rs.get("k") == answer
        assert drv.threads == [threading.get_ident()] * attempts
        assert rs._pool._seq == 0
        assert inline_count("GET") == n0 + attempts
        assert counter("juicefs_object_request_retries",
                       "GET").value == r0 + retries
        assert counter("juicefs_object_retries_by_class",
                       "transient").value == t0 + retries
        snap = br.snapshot()
        assert snap["window_samples"] == failures + 1
        assert snap["window_failure_rate"] == round(
            failures / (failures + 1), 3)
        assert br.state == BreakerState.CLOSED
    finally:
        rs.close()


def sync_store(url):
    from juicefs_tpu.cmd.sync import _open_store

    return _open_store(url)


# store -> whether the resilience layer runs its attempts on the caller
WRAPPED = {
    "file": (lambda d: FileStorage(d), True),
    "prefixed": (lambda d: with_prefix(FileStorage(d), "vol/"), True),
    "sharded": (lambda d: sharded([FileStorage(d + "/a"),
                                   FileStorage(d + "/b")]), True),
    "sharded_with_a_remote_shard": (
        lambda d: sharded([FileStorage(d), create_storage("mem://")]), False),
    "checksummed": (lambda d: new_checksummed(FileStorage(d)), True),
    "metered": (lambda d: metered(with_prefix(FileStorage(d), "vol/")), True),
    "faulty": (lambda d: FaultyStore(FileStorage(d)), False),
    "mem": (lambda d: create_storage("mem://"), False),
    # the mirror check's endpoint: already resilient(metered(...))
    "sync_endpoint": (lambda d: sync_store(f"file://{d}/"), True),
}


@pytest.mark.parametrize("name", sorted(WRAPPED))
def test_the_wrappers_report_their_inner_stores_answer(tmp_path, name):
    """The decision is the store's own declaration, carried up through
    each wrapper; FaultyStore keeps the pool path (its hangs are what the
    drills abandon), and so does every store that is not host-local."""
    make, local = WRAPPED[name]
    store = make(str(tmp_path))
    store.put("k", b"v")
    rs = resilient(store, hedge=False)
    try:
        assert rs.inline is local
        assert rs.get("k") == b"v"
        assert (rs._pool._seq == 0) is local
    finally:
        rs.close()
