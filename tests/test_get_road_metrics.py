"""A block's GET measured from inside, where each part happens: the fetch
stage's queue (chunk/parallel.py), the resilience layer's two hand-offs
(object/resilient.py; over `file://` each attempt runs on the caller and
the two are function calls), the store's own read and its thread CPU time
(object/metered.py) — one observation of each a GET, nested inside the
`chunk.load.fetch` span and the fetch stage's own time of the call — and
`main`'s argument parsing, one an invocation. Through `gc --dedup`,
`fsck --verify-data` and `sync --check-all` over `file://`, and a hedged GET
(on the pool, over a store that is not host-local) whose first leg answers
or does not."""

import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from juicefs_tpu.chunk.parallel import fetch_ordered
from juicefs_tpu.cmd import main
from juicefs_tpu.metric import global_registry
from juicefs_tpu.object import MemStorage
from juicefs_tpu.object.fault import FaultyStore
from juicefs_tpu.object.metered import metered
from juicefs_tpu.object.resilient import RetryPolicy, resilient

STAGE = "juicefs_tpu_stage_seconds"
DUR = "juicefs_object_request_durations_histogram_seconds"
CPU = "juicefs_object_request_cpu_seconds"
# part -> (histogram, label values), for a `file://` store
PARTS = {
    "fetch_queue": (STAGE, ("chunk", "fetch", "queue")),
    "load_fetch": (STAGE, ("chunk", "load", "fetch")),
    "resilient_queue": (STAGE, ("object", "get", "queue")),
    "resilient_wake": (STAGE, ("object", "get", "wake")),
    "store": (DUR, ("GET", "file")),
    "store_cpu": (CPU, ("GET", "file")),
    "parse": (STAGE, ("cmd", "main", "parse")),
}
EPS = 1e-6  # the stamps' own order: a microsecond


def inline_gets():
    """GET attempts a `file://` store's resilience layer ran on the caller."""
    child = global_registry()._metrics[
        "juicefs_object_inline_requests"]._children.get(("file", "GET"))
    return child.value if child is not None else 0


def hist(name, labels):
    """(sum, count) of one child of a registry histogram; (0, 0) before its
    first observation (reading creates nothing)."""
    child = global_registry()._metrics[name]._children.get(tuple(labels))
    return (child.sum, child.total) if child is not None else (0.0, 0)


def snapshot(parts=PARTS):
    return {p: hist(*series) for p, series in parts.items()}


def gained(before, parts=PARTS):
    """part -> (count gained, mean of what was gained, in seconds)."""
    out = {}
    for p, (s0, n0) in before.items():
        s1, n1 = hist(*parts[p])
        n = n1 - n0
        out[p] = (n, (s1 - s0) / n if n else None)
    return out


def _stats(capsys, argv):
    capsys.readouterr()
    assert main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("command", ["gc", "fsck"])
def test_every_part_is_one_observation_a_block_nested_in_the_load(
        tmp_path, capsys, command):
    from test_trace import _scan_volume

    meta_url = _scan_volume(tmp_path, blocks=9)
    gc_argv = ["gc", meta_url, "--dedup", "--hash-backend", "xla",
               "--threads", "4"]
    if command == "fsck":  # the scrub holds the blocks to the index gc fills
        _stats(capsys, gc_argv)
        argv = ["fsck", meta_url, "--verify-data", "--hash-backend", "xla",
                "--threads", "4"]
    else:
        argv = gc_argv
    before, inline0 = snapshot(), inline_gets()
    stats = _stats(capsys, argv)
    got = gained(before)
    blocks = stats["hashed_now"]
    assert blocks == 9
    # every GET one attempt on the fetch thread: no pool between, no hedge
    assert inline_gets() == inline0 + blocks
    assert got["parse"][0] == 1  # one `main`
    for part in PARTS:
        if part != "parse":
            assert got[part][0] == blocks, part
    mean = {p: m for p, (_, m) in got.items()}
    assert all(m >= 0 for m in mean.values())
    # each GET's parts are disjoint stretches of its `chunk.load.fetch`,
    # which lies inside the fetch stage's own time of the call
    assert (mean["resilient_queue"] + mean["store"] + mean["resilient_wake"]
            <= mean["load_fetch"] + EPS)
    assert mean["load_fetch"] <= (
        stats["stage_seconds"]["get_threads"] / blocks + EPS)
    assert mean["store_cpu"] <= mean["store"] + EPS


def test_the_mirror_checks_stores_are_metered(tmp_path, capsys):
    """`sync --check-all` meters both stores under the resilience layer, as
    every other stack does: each range read, on either side, is one GET of
    the store's histogram, with its CPU time and its two hand-offs."""
    from test_sync_hash import SIZES, Buckets

    b = Buckets(tmp_path).mirror()
    before, inline0 = snapshot(), inline_gets()
    stats = _stats(capsys, ["sync", b.src_url, b.dst_url, "--threads", "4",
                            "--check-all", "--hash-backend", "xla"])
    got = gained(before)
    # one range an object of up to 4 MiB (the empty one too), three for the
    # one of 9 MiB + 5; both sides
    ranges = 2 * (len(SIZES) + 2)
    assert stats["checked"] == len(SIZES) and stats["hashed_now"] == ranges
    assert inline_gets() == inline0 + ranges  # both stores on one series
    for part in ("fetch_queue", "resilient_queue", "resilient_wake", "store",
                 "store_cpu"):
        assert got[part][0] == ranges, part
    assert got["load_fetch"][0] == 0  # no chunk store on this path
    assert got["store_cpu"][1] <= got["store"][1] + EPS


# -- a hedged GET --------------------------------------------------------------

def _hedged(store, delay):
    return resilient(metered(store),
                     policy=RetryPolicy(deadline=10.0, max_attempts=2),
                     hedge=True, hedge_delay=delay)


def _hedge_parts(rs):
    return {
        "resilient_queue": PARTS["resilient_queue"],
        "resilient_wake": PARTS["resilient_wake"],
        "store": (DUR, ("GET", rs.backend)),
        "store_cpu": (CPU, ("GET", rs.backend)),
    }


def _until(cond, timeout=5.0):
    end = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > end:
            return False
        time.sleep(0.01)
    return True


def _counter(name, label):
    return global_registry()._metrics[name].labels(label).value


# case -> (the store's fault timeline, hedge delay, legs that run, hedges,
# whether the first leg's answer is the one taken)
HEDGES = {
    "answered_before_the_delay": ([(None, dict())], 5.0, 1, 0, True),
    # the first leg takes 0.3 s, the second, started 0.05 s later, 1 s
    "first_leg_wins_the_race": ([(0.04, dict(latency=0.3)),
                                 (None, dict(latency=1.0))], 0.05, 2, 1, True),
    # the first leg hangs; the second one's start heals the store, which
    # lets the first go with an error: the second leg answers
    "second_leg_wins_the_race": ([(0.1, dict(hang_rate=1.0)),
                                  (None, dict(hang_rate=0.0))], 0.2, 2, 1, False),
}


@pytest.mark.parametrize("case", sorted(HEDGES))
def test_a_hedged_get_counts_its_hand_offs_only_from_the_first_leg(case):
    timeline, delay, legs, hedges, first = HEDGES[case]
    mem = MemStorage()
    mem.put("k", b"hedged!")
    faulty = FaultyStore(mem, hang_seconds=10.0)
    faulty.fault_schedule(timeline)
    rs = _hedged(faulty, delay)
    parts = _hedge_parts(rs)
    h0 = _counter("juicefs_object_hedged_requests", rs.metric_backend)
    w0 = _counter("juicefs_object_hedge_wins", rs.metric_backend)
    before = snapshot(parts)
    try:
        assert rs.get("k") == b"hedged!"
        # the leg that lost may still be on its way out of the store
        assert _until(lambda: hist(*parts["store"])[1]
                      == before["store"][1] + legs)
        got = gained(before, parts)
    finally:
        rs.close()
    taken = 1 if first else 0
    assert got["resilient_queue"][0] == got["resilient_wake"][0] == taken
    assert got["store"][0] == got["store_cpu"][0] == legs
    assert _counter("juicefs_object_hedged_requests", rs.metric_backend) == h0 + hedges
    assert _counter("juicefs_object_hedge_wins",
                    rs.metric_backend) == w0 + (hedges - taken if hedges else 0)


def test_a_store_that_never_hedged_reads_zero_hedges():
    """The hedge counters are bound when the store is made: a window in
    which nothing hedged reads 0, not nothing."""
    from benchmark import run

    rs = _hedged(MemStorage(), 5.0)
    try:
        key = f'juicefs_object_hedged_requests{{backend="{rs.metric_backend}"}}'
        assert key in run.registry_snapshot()
    finally:
        rs.close()


# -- the fetch stage's queue ---------------------------------------------------

@pytest.mark.parametrize("ahead", [0, 5])
def test_the_fetch_queue_is_one_stamp_an_item_and_keeps_no_block(ahead):
    """Two pool threads under a window of four: items wait on the pool's
    queue, each observed once as its call starts; the stamp lives on the
    item's slot, and a consumed block is still freed at once (nothing of
    the stage may keep a fetched block alive: one freed late is an
    allocator change, PERF.md)."""
    import gc
    import weakref

    class Block:
        pass

    refs = []
    lock = threading.Lock()

    def fn(i):
        time.sleep(0.002)
        b = Block()
        with lock:
            refs.append((i, weakref.ref(b)))
        return b

    pool = ThreadPoolExecutor(max_workers=2, thread_name_prefix="t-queue")
    before = snapshot({"fetch_queue": PARTS["fetch_queue"]})
    gc.collect()
    gc.disable()
    try:
        n = -1
        for n, (i, b) in enumerate(fetch_ordered(range(30), fn, pool,
                                                 window=4, ahead=ahead)):
            del b
            assert _until(lambda: not [j for j, r in list(refs)
                                       if j < i and r() is not None], 2.0)
        assert n == 29
        assert _until(lambda: not [j for j, r in refs if r() is not None], 2.0)
    finally:
        gc.enable()
        pool.shutdown(wait=True)
    count, mean = gained(before, {"fetch_queue": PARTS["fetch_queue"]})["fetch_queue"]
    assert count == 30
    # four submitted, two running: some waited for a pool thread
    assert mean > 0
