"""A stream's pack buffers are ready before its first pack (ISSUE 34): a
`HashPipeline` that was told a stream is coming (`prepare()`) has helper
threads allocate the stream's `max_inflight_batches` pack buffers and fault
every page of them in, and `hash_stream` packs into those. Same bytes, same
order, same lifetimes as the buffers a stream makes for itself
(tests/test_pack_buffers.py); nothing for who did not ask; nothing left
behind — no buffer, no thread — once the stream is over or never came."""

import gc
import logging
import threading
import time
import weakref

import numpy as np
import pytest

from juicefs_tpu.metric import global_registry
from juicefs_tpu.metric.trace import global_tracer, stage_hist
from juicefs_tpu.tpu import LANE_BYTES, jth256
from juicefs_tpu.tpu import pipeline
from juicefs_tpu.tpu.jth256 import COLS, ROWS, hash_packed_np, pack_blocks
from juicefs_tpu.tpu.pipeline import HashPipeline, PipelineConfig

from test_pack_buffers import STALE, _ragged_stream

BATCH = 4 * 2 * LANE_BYTES  # packed bytes of a full batch of _pipe()


def _until(cond, timeout=10.0):
    deadline = time.time() + timeout
    while not cond() and time.time() < deadline:
        time.sleep(0.002)
    return cond()


def no_preparer_runs(timeout=10.0):
    """No thread of any preparer is alive (one that was told to stop is
    given a moment to see it: nobody joins it)."""
    return _until(lambda: not [t for t in threading.enumerate()
                               if t.name.startswith("jfs-pack-prepare")], timeout)


def wait_ready(pipe):
    """The announced stream's buffers, once every one of them is ready."""
    slots = list(pipe._prepared._slots)
    assert _until(lambda: all(s["state"] == "ready" for s in slots))
    return [s["buf"] for s in slots]


def _counter(name):
    return global_registry()._metrics[name].value


def _gains():
    return np.array([_counter("juicefs_tpu_pack_fresh_bytes"),
                     _counter("juicefs_tpu_pack_unready_bytes")])


def _pipe(backend="xla", depth=2):
    return HashPipeline(PipelineConfig(
        backend=backend, batch_blocks=4, pad_lanes=2,
        max_inflight_batches=depth))


def _hash(pipe, blocks):
    got = list(pipe.hash_stream((f"k{i}", b) for i, b in enumerate(blocks)))
    assert got == [(f"k{i}", jth256(b)) for i, b in enumerate(blocks)]


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_a_stream_packs_into_the_buffers_prepared_for_it(backend, depth):
    """Dirty on purpose: a prepared buffer holds whatever the touch left and
    the pack writes every row whole. The first-use bytes are what they are
    for a stream nobody announced; none of them was unready."""
    blocks = _ragged_stream()
    pipe = _pipe(backend, depth)
    pipe.prepare()
    bufs = wait_ready(pipe)
    assert len(bufs) == depth
    for buf in bufs:
        assert buf.shape == (4, 2, ROWS, COLS) and buf.dtype == np.dtype("<u4")
        assert buf.flags.c_contiguous and buf.flags.writeable
        buf[...] = STALE
    used = []
    real = pipeline.pack_blocks

    def noting(blocks, pad_lanes=None, out=None):
        used.append(out)
        return real(blocks, pad_lanes, out)

    before = _gains()
    pipeline.pack_blocks = noting
    try:
        _hash(pipe, blocks)
    finally:
        pipeline.pack_blocks = real
    assert list(_gains() - before) == [depth * BATCH, 0]
    # six batches, every one into a prepared buffer, none made by the pack
    assert len(used) == 6 and {id(b) for b in used} == {id(b) for b in bufs}
    assert pipe._prepared is None and no_preparer_runs()


@pytest.mark.parametrize("why", ["never-announced", "preparer-fails"])
def test_unready_bytes_are_the_fresh_bytes_where_nothing_was_ready(
        why, monkeypatch, caplog):
    pipe = _pipe()
    if why == "preparer-fails":
        def broken(part, stop):
            raise OSError("no pages today")
        monkeypatch.setattr(pipeline, "_touch", broken)
        with caplog.at_level(logging.ERROR):
            pipe.prepare()
            before = _gains()
            _hash(pipe, _ragged_stream())
        assert no_preparer_runs()
        assert "preparing pack buffers failed" in caplog.text
    else:
        before = _gains()
        _hash(pipe, _ragged_stream())
    # as today: max_inflight_batches batches packed into memory of their own
    assert list(_gains() - before) == [2 * BATCH, 2 * BATCH]


def test_a_stream_that_asks_before_the_preparer_is_done_waits_and_hashes_right(
        monkeypatch):
    """The first pack comes while its buffer is being touched: it waits for
    the remainder (the pages are faulted once, by the preparer), counts as
    unready, and every digest is right."""
    gate, started = threading.Event(), threading.Event()
    real = pipeline._touch

    def stalled(part, stop):
        started.set()
        assert gate.wait(10)
        return real(part, stop)

    monkeypatch.setattr(pipeline, "_touch", stalled)
    pipe = _pipe()
    pipe.prepare()
    preparer = pipe._prepared
    assert started.wait(10)
    first = preparer._slots[0]
    assert first["state"] == "touching"
    threading.Timer(0.2, gate.set).start()
    before = _gains()
    t0 = time.perf_counter()
    _hash(pipe, _ragged_stream())
    assert time.perf_counter() - t0 >= 0.15  # it did wait
    fresh, unready = _gains() - before
    assert fresh == 2 * BATCH and BATCH <= unready <= fresh
    assert first["state"] == "ready"  # and packed into that very buffer
    assert no_preparer_runs()


def test_a_stream_that_comes_before_the_preparer_began_packs_fresh(
        monkeypatch):
    """A slot nobody has begun is taken over: the stream packs into memory
    of its own, as if nothing had been prepared, and the preparer skips it."""
    monkeypatch.setattr(pipeline._PreparedBuffers, "_run",
                        lambda self, slots: None)  # a thread that never ran
    pipe = _pipe()
    pipe.prepare()
    preparer = pipe._prepared
    before = _gains()
    _hash(pipe, _ragged_stream())
    assert list(_gains() - before) == [2 * BATCH, 2 * BATCH]
    assert preparer._slots == [] and preparer._stop.value == 1


class _HostProgram:
    """A stand-in device program that keeps nothing of the words it is
    given (JAX's CPU backend aliases host words, in cycles of its own that
    only the collector frees: not what these tests are about)."""

    def __init__(self, words, counts, lengths):
        self.digests = hash_packed_np(words, counts, lengths)

    def __array__(self, dtype=None, copy=None):
        return self.digests


@pytest.mark.parametrize("how", ["closed-early", "run-to-its-end"])
def test_a_stream_that_is_over_holds_no_prepared_buffer(how):
    """Collector off: no reference cycle keeps a 128 MiB buffer alive past
    its stream, and no thread of the preparer runs on."""
    blocks = _ragged_stream()
    pipe = _pipe()
    pipe._fn = _HostProgram
    gc.collect()
    gc.disable()
    try:
        pipe.prepare()
        refs = [weakref.ref(b) for b in wait_ready(pipe)]
        stream = pipe.hash_stream((f"k{i}", b) for i, b in enumerate(blocks))
        if how == "closed-early":
            assert next(stream)[1] == jth256(blocks[0])
            stream.close()
        else:
            assert [d for _, d in stream] == [jth256(b) for b in blocks]
        del stream
        assert no_preparer_runs() and pipe._prepared is None
        assert len(refs) == 2 and all(ref() is None for ref in refs)
    finally:
        gc.enable()


@pytest.mark.parametrize("how", ["never-started", "released-twice",
                                 "stream-made-not-run"])
def test_a_stream_that_never_comes_leaves_nothing(how):
    pipe = _pipe()
    gc.collect()
    gc.disable()
    try:
        pipe.prepare()
        if how == "released-twice":
            refs = [weakref.ref(b) for b in wait_ready(pipe)]
        else:  # released while the preparer is at work
            refs = []
        if how == "stream-made-not-run":
            stream = pipe.hash_stream(iter([("k", b"x")]))
            del stream  # never started: the pipeline still holds what it made
            assert pipe._prepared is not None
        preparer = pipe._prepared
        pipe.release()
        pipe.release()
        assert pipe._prepared is None and preparer._slots == []
        assert no_preparer_runs(2.0)  # promptly: a MiB's touch, not a buffer's
        assert all(ref() is None for ref in refs)
    finally:
        gc.enable()
    # and the pipeline is as it was: an unannounced stream packs fresh
    before = _gains()
    _hash(pipe, _ragged_stream())
    assert list(_gains() - before) == [2 * BATCH, 2 * BATCH]


def test_a_stopped_preparer_ends_within_a_slice_of_its_touch(monkeypatch):
    """`stop()` does not wait for a buffer to be finished: the touch looks
    at the flag (the native call once a MiB, numpy once a slice)."""
    import ctypes

    from juicefs_tpu import native

    part = np.empty(8 << 20, dtype=np.uint8)
    stop = ctypes.c_int(1)
    if native.available():
        assert native.touch_pages(part, stop) == 0
        assert native.touch_pages(part, ctypes.c_int(0)) == part.nbytes
        assert native.touch_pages(part) == part.nbytes
    assert pipeline._touch(part, ctypes.c_int(0)) == (
        "native" if native.available() else "numpy")
    monkeypatch.setattr(native, "touch_pages", lambda part, stop=None: None)
    part[:] = 7
    assert pipeline._touch(part, stop) == "numpy" and part.min() == 7
    assert pipeline._touch(part, ctypes.c_int(0)) == "numpy"
    assert (part[::4096] == 0).all() and part[1] == 7


def test_streams_racing_their_preparers_hash_right_and_leave_nothing():
    """More threads than cores and a short switch interval: every stream
    comes while its preparer is somewhere between not begun and done, takes
    each buffer at most once (a buffer handed out twice would be rewritten
    under a pending batch: wrong digests) and leaves no thread behind."""
    import sys

    from juicefs_tpu import native

    blocks = _ragged_stream(batches=3)
    want = [(f"k{i}", jth256(b)) for i, b in enumerate(blocks)]
    failures = []

    def scan(n):
        pipe = _pipe(depth=1 + n % 3)
        pipe._fn = _HostProgram
        for _ in range(6):
            pipe.prepare()
            if n % 2:
                time.sleep(0.0005 * (n % 5))
            got = list(pipe.hash_stream(
                (f"k{i}", b) for i, b in enumerate(blocks)))
            if got != want or pipe._prepared is not None:
                failures.append(n)

    with pytest.raises(ValueError):
        native.touch_pages(np.empty((4, 4096), np.uint8)[:, ::2])
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        scans = [threading.Thread(target=scan, args=(n,), daemon=True)
                 for n in range(12)]
        for t in scans:
            t.start()
        for t in scans:
            t.join(timeout=120)
        assert not [t for t in scans if t.is_alive()]
    finally:
        sys.setswitchinterval(old)
    assert failures == [] and no_preparer_runs()


def test_nothing_is_prepared_for_who_did_not_ask(monkeypatch):
    """`hash_blocks`, a stream nobody announced, and the `cpu` backend even
    when asked: no preparer, no thread, the digests and counters of before."""
    started = []
    monkeypatch.setattr(pipeline._PreparedBuffers, "__init__",
                        lambda self, *a: started.append(a))
    blocks = _ragged_stream()
    pipe = _pipe()
    before = _gains()
    assert pipe.hash_blocks(blocks[:3]) == [jth256(b) for b in blocks[:3]]
    assert list(_gains() - before) == [3 * 2 * LANE_BYTES] * 2
    _hash(pipe, blocks)
    cpu = _pipe("cpu")
    cpu.prepare()  # packs nothing, so prepares nothing
    assert cpu._prepared is None
    before = _gains()
    _hash(cpu, blocks)
    assert list(_gains() - before) == [0, 0]
    cpu.release()
    assert started == []


def test_a_pack_stand_in_without_out_lets_the_prepared_buffers_go(monkeypatch):
    blocks = _ragged_stream()
    pipe = _pipe()
    monkeypatch.setattr(
        pipeline, "pack_blocks",
        lambda blocks, pad_lanes=None: pack_blocks(blocks, pad_lanes))
    pipe.prepare()
    preparer = pipe._prepared
    before = _gains()
    _hash(pipe, blocks)
    packed = sum(min(4, len(blocks) - i) for i in range(0, len(blocks), 4))
    assert list(_gains() - before) == [packed * 2 * LANE_BYTES] * 2
    assert preparer._slots == [] and no_preparer_runs()


def test_a_changed_config_lets_the_prepared_buffers_go():
    pipe = _pipe()
    pipe.prepare()
    preparer = pipe._prepared
    pipe.config.pad_lanes = 3  # buffers of another shape than were prepared
    blocks = _ragged_stream()
    got = list(pipe.hash_stream((f"k{i}", b) for i, b in enumerate(blocks)))
    assert [d for _, d in got] == [jth256(b) for b in blocks]
    assert preparer._slots == [] and no_preparer_runs()


def test_prepare_and_pack_say_what_they_did_in_the_trace():
    """One `tpu.pack.prepare` span a buffer, under the announcer's span and
    so in its trace; the `tpu.hash.pack` span says `ready`."""
    import json

    tr = global_tracer()
    hist = stage_hist("tpu", "pack", "prepare")
    key = ("test", "prepare")
    pipe = _pipe()
    n0 = hist.total
    tr.open_reader(key)
    try:
        with tr.span("cmd", "gc", stage="open") as announcer:
            pipe.prepare()
        wait_ready(pipe)
        _hash(pipe, _ragged_stream())
        assert no_preparer_runs()
        evs = [json.loads(line) for line in
               tr.read(key, 1 << 22).decode().splitlines()]
    finally:
        tr.close_reader(key)
    assert hist.total - n0 == 2
    prepares = [e for e in evs if (e["layer"], e["op"], e.get("stage"))
                == ("tpu", "pack", "prepare")]
    assert len(prepares) == 2
    for e in prepares:
        assert e["parent"] == announcer.span_id
        assert e["trace"] == announcer.trace_id
        assert e["bytes"] == BATCH and e["how"] in ("native", "numpy")
        assert e["stopped"] == 0
    packs = [e for e in evs if (e["layer"], e["op"], e.get("stage"))
             == ("tpu", "hash", "pack")]
    assert [e["ready"] for e in packs] == [1] * 6
    assert [e["fresh"] for e in packs] == [1, 1, 0, 0, 0, 0]


def _volume(tmp_path, blocks):
    from test_trace import _scan_volume

    return _scan_volume(tmp_path, blocks=blocks, block_kib=64)


def test_gc_dedup_announces_its_stream_and_leaves_nothing(tmp_path, capsys,
                                                          monkeypatch):
    """`gc --dedup` builds its pipeline in `open` and announces the stream
    there; when the op returns no preparer thread is alive and no pack
    buffer is referenced. A second scan finds every block indexed: it
    neither waits for a buffer nor keeps one."""
    import json

    from juicefs_tpu.cmd import gc as gc_cmd, main

    meta_url = _volume(tmp_path, blocks=40)
    made, pipes = [], []
    real_empty = np.empty

    def noted_empty(shape, *a, **kw):
        arr = real_empty(shape, *a, **kw)
        if np.shape(arr)[1:] == (1, ROWS, COLS):
            made.append(weakref.ref(arr))
        return arr

    real_scan = gc_cmd.dedup_scan

    def scan(*a, pipe=None, **kw):
        pipes.append(pipe)
        return real_scan(*a, pipe=pipe, **kw)

    monkeypatch.setattr(pipeline.np, "empty", noted_empty)
    monkeypatch.setattr(gc_cmd, "dedup_scan", scan)

    def run():
        capsys.readouterr()
        before = _gains()
        assert main(["gc", meta_url, "--dedup", "--hash-backend", "xla",
                     "--threads", "4"]) == 0
        out = capsys.readouterr().out.strip().splitlines()[-1]
        return json.loads(out), _gains() - before

    stats, (fresh, unready) = run()
    assert stats["hashed_now"] == 40
    # two batches (32 + 8) of 64 KiB blocks, each a buffer's first use
    assert fresh == 40 * LANE_BYTES and 0 <= unready <= fresh
    assert isinstance(pipes[0], HashPipeline) and pipes[0]._prepared is None
    assert no_preparer_runs(2.0)
    gc.collect()  # (the CPU backend's arrays alias host words, in cycles)
    assert made and all(ref() is None for ref in made)
    del made[:]
    gc.disable()
    try:  # nothing hashed, so nothing JAX ever saw: gone without the collector
        stats, gains = run()
        assert (stats["hashed_now"], stats["from_index"]) == (0, 40)
        assert list(gains) == [0, 0]
        assert no_preparer_runs(2.0)
        assert all(ref() is None for ref in made)
    finally:
        gc.enable()


def test_dedup_scan_builds_its_own_pipeline_when_given_none(tmp_path, capsys):
    from juicefs_tpu.cmd import build_store, open_meta
    from juicefs_tpu.cmd.gc import dedup_scan
    from juicefs_tpu.chunk.cached_store import block_key

    meta_url = _volume(tmp_path, blocks=9)
    m, fmt = open_meta(meta_url)

    class Args:
        pass

    store = build_store(fmt, Args(), meta=m, with_indexer=False)
    bs = fmt.block_size * 1024
    live = {}
    for slcs in m.list_slices().values():
        for s in slcs:
            if s.id and s.size:
                for i in range((s.size + bs - 1) // bs):
                    bsize = min(bs, s.size - i * bs)
                    live[block_key(s.id, i, bsize)] = bsize
    before = _gains()
    stats = dedup_scan(m, store, live, "xla", "", bs, threads=2)
    assert stats["hashed_now"] == 9 and stats["backend"] == "xla"
    fresh, unready = _gains() - before
    assert fresh == unready == 9 * LANE_BYTES  # nobody announced it
    assert no_preparer_runs()
