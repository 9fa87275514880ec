"""The scan's host-memory contract (ISSUE 27): `pack_blocks(out=)` writes a
batch into a buffer its caller keeps, byte for byte what the fresh call
returns; one `hash_stream` keeps at most `max_inflight_batches` such buffers,
gets one back only once its batch's digests were read, and holds nothing
after it ends or is abandoned. Since ISSUE 34 a stream that was announced
(`HashPipeline.prepare()`) takes those buffers from a preparer that faulted
them in on threads of its own; tests/test_pack_prepare.py holds that."""

import gc
import weakref

import numpy as np
import pytest

from juicefs_tpu.metric import global_registry
from juicefs_tpu.tpu import LANE_BYTES, jth256
from juicefs_tpu.tpu import pipeline
from juicefs_tpu.tpu.jth256 import (COLS, ROWS, hash_packed_np, pack_block,
                                    pack_blocks)
from juicefs_tpu.tpu.pipeline import HashPipeline, PipelineConfig

MIB4 = 4 << 20
STALE = 0xFFFFFFFF


def _blocks(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
            for n in sizes]


@pytest.mark.parametrize(
    "size", [0, 1, 65_535, 65_536, 100_001, MIB4 - 1, MIB4])
def test_pack_into_a_dirty_buffer_equals_the_fresh_pack(size):
    blocks = _blocks(seed=size % 1000, sizes=[size, 777])
    words, counts, lengths = pack_blocks(blocks, pad_lanes=64)
    by_hand = np.zeros((2, 64, ROWS, COLS), dtype=np.uint32)
    for i, b in enumerate(blocks):
        w = pack_block(b)
        by_hand[i, : w.shape[0]] = w
    assert words.tobytes() == by_hand.tobytes()  # without `out`: as it was

    # B < the buffer's rows: the batch lies in its first rows
    dirty = np.full((3, 64, ROWS, COLS), STALE, dtype=np.uint32)
    kept, kcounts, klengths = pack_blocks(blocks, pad_lanes=64, out=dirty)
    assert kept.base is dirty and kept.shape == words.shape
    assert kept.dtype == words.dtype and kept.tobytes() == words.tobytes()
    assert (kcounts.dtype, klengths.dtype) == (counts.dtype, lengths.dtype)
    assert list(kcounts) == list(counts) == [max(1, -(-size // LANE_BYTES)), 1]
    assert list(klengths) == list(lengths) == [size, 777]
    assert dirty[2].min() == STALE  # rows past the batch: not touched
    # a shorter batch into the same buffer leaves nothing of the one before
    again = pack_blocks(blocks[1:], pad_lanes=64, out=dirty)[0]
    assert again.tobytes() == pack_blocks(blocks[1:], pad_lanes=64)[0].tobytes()
    # without pad_lanes the buffer's lanes have to be the batch's own
    m = int(counts.max())
    tight = pack_blocks(blocks, out=np.full((2, m, ROWS, COLS), 7, np.uint32))
    assert tight[0].tobytes() == pack_blocks(blocks)[0].tobytes()


def _read_only():
    a = np.empty((2, 64, ROWS, COLS), np.uint32)
    a.flags.writeable = False
    return a


@pytest.mark.parametrize("bad", [
    lambda: np.empty((1, 64, ROWS, COLS), np.uint32),       # too few rows
    lambda: np.empty((2, 63, ROWS, COLS), np.uint32),       # too few lanes
    lambda: np.empty((2, 65, ROWS, COLS), np.uint32),       # another shape
    lambda: np.empty((2, 64, ROWS * COLS), np.uint32),
    lambda: np.empty((2, 64, ROWS, COLS), np.int32),        # wrong dtype
    lambda: np.empty((2, 64, ROWS, COLS), ">u4"),
    lambda: np.empty((4, 64, ROWS, COLS), np.uint32)[::2],  # not contiguous
    _read_only,
    lambda: bytearray(16),
], ids=["rows", "lanes", "wide", "ndim", "int32", "big-endian", "strided",
        "read-only", "not-an-array"])
def test_pack_refuses_a_bad_out(bad):
    blocks = _blocks(seed=5, sizes=[70_000, 777])
    with pytest.raises(ValueError):
        pack_blocks(blocks, pad_lanes=64, out=bad())


def test_pack_refuses_an_oversize_block_before_it_writes():
    blocks = _blocks(seed=6, sizes=[MIB4 + 7, 777])
    dirty = np.full((2, 64, ROWS, COLS), STALE, dtype=np.uint32)
    with pytest.raises(ValueError):
        pack_blocks(blocks, pad_lanes=64, out=dirty)
    wide = np.full((2, 65, ROWS, COLS), STALE, dtype=np.uint32)
    with pytest.raises(ValueError):  # no pad_lanes: still over a block
        pack_blocks(blocks, out=wide)
    assert dirty.min() == wide.min() == STALE


def _ragged_stream(batches=5, batch_blocks=4, tail=3):
    sizes = [(37 + 9_973 * i) % (2 * LANE_BYTES + 1)
             for i in range(batches * batch_blocks + tail)]
    sizes[1], sizes[6] = 0, 2 * LANE_BYTES
    return _blocks(seed=27, sizes=sizes)


def _fresh_bytes():
    return global_registry()._metrics["juicefs_tpu_pack_fresh_bytes"].value


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_hash_stream_packs_into_the_buffers_it_keeps(backend, depth):
    """Five full batches and a short tail of ragged blocks: every digest is
    the numpy spec's, and only `max_inflight_batches` batches were packed
    into memory on its first use."""
    blocks = _ragged_stream()
    pipe = HashPipeline(PipelineConfig(
        backend=backend, batch_blocks=4, pad_lanes=2,
        max_inflight_batches=depth))
    before = _fresh_bytes()
    got = list(pipe.hash_stream((f"k{i}", b) for i, b in enumerate(blocks)))
    assert got == [(f"k{i}", jth256(b)) for i, b in enumerate(blocks)]
    assert _fresh_bytes() - before == depth * 4 * 2 * LANE_BYTES
    # a one-batch stream (the indexer's hash_blocks): one fresh array,
    # counted as what was shipped of it
    before = _fresh_bytes()
    assert pipe.hash_blocks(blocks[:3]) == [jth256(b) for b in blocks[:3]]
    assert _fresh_bytes() - before == 3 * 2 * LANE_BYTES


class _Pending:
    """A stand-in device program: keeps the host words it was given, as a
    device may (the CPU backend's `device_put` can alias them), and holds
    them against a copy taken at dispatch when its result is read."""

    seen: list

    def __init__(self, words, counts, lengths):
        self.words, self.at_dispatch = words, words.copy()
        self.counts, self.lengths = counts, lengths

    def __array__(self, dtype=None, copy=None):
        # a buffer's first batch is the buffer; later ones are views of it
        buf = self.words if self.words.base is None else self.words.base
        self.seen.append((buf, np.array_equal(self.words, self.at_dispatch)))
        return hash_packed_np(self.at_dispatch, self.counts, self.lengths)


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_hash_stream_gives_a_buffer_back_only_after_its_drain(depth):
    """No buffer is rewritten while its batch is pending, and no more than
    `max_inflight_batches` distinct ones are ever used."""
    _Pending.seen = seen = []
    blocks = _ragged_stream()
    pipe = HashPipeline(PipelineConfig(
        backend="xla", batch_blocks=4, pad_lanes=2,
        max_inflight_batches=depth))
    pipe._fn = _Pending
    got = list(pipe.hash_stream((f"k{i}", b) for i, b in enumerate(blocks)))
    assert got == [(f"k{i}", jth256(b)) for i, b in enumerate(blocks)]
    assert len(seen) == 6 and all(same for _, same in seen)
    assert len({id(buf) for buf, _ in seen}) == depth  # and they were reused
    assert all(buf.shape == (4, 2, ROWS, COLS) for buf, _ in seen)


def test_a_pack_stand_in_without_out_still_hashes_right(monkeypatch):
    """`pipeline.pack_blocks` is a name others stand functions in (the
    benchmark's span wrapper, a test's stand-in). Under one that cannot
    pack into a buffer the stream keeps none and every batch is fresh."""
    blocks = _ragged_stream()
    pipe = HashPipeline(PipelineConfig(
        backend="xla", batch_blocks=4, pad_lanes=2))
    monkeypatch.setattr(
        pipeline, "pack_blocks",
        lambda blocks, pad_lanes=None: pack_blocks(blocks, pad_lanes))
    before = _fresh_bytes()
    assert pipe.hash_blocks(blocks) == [jth256(b) for b in blocks]
    assert _fresh_bytes() - before == len(blocks) * 2 * LANE_BYTES


@pytest.mark.parametrize("how", ["closed-early", "run-to-its-end"])
@pytest.mark.parametrize("announced", [False, True],
                         ids=["its-own-buffers", "prepared-buffers"])
def test_a_stream_holds_no_buffer_once_it_is_over(monkeypatch, how, announced):
    """Every buffer a batch was packed into, made by the pack itself or (an
    announced stream's) by the preparer, is gone with the stream."""
    from test_pack_prepare import no_preparer_runs, wait_ready

    made, seen = [], set()

    def noting(blocks, pad_lanes=None, out=None):
        packed = pack_blocks(blocks, pad_lanes, out)
        buf = packed[0] if out is None else out
        if id(buf) not in seen:
            seen.add(id(buf))
            made.append(weakref.ref(buf))
        return packed

    monkeypatch.setattr(pipeline, "pack_blocks", noting)
    blocks = _ragged_stream()
    pipe = HashPipeline(PipelineConfig(
        backend="xla", batch_blocks=4, pad_lanes=2))
    prepared, prepared_ids = [], set()
    if announced:
        pipe.prepare()
        prepared = [weakref.ref(b) for b in wait_ready(pipe)]
        prepared_ids = {id(ref()) for ref in prepared}
    stream = pipe.hash_stream((f"k{i}", b) for i, b in enumerate(blocks))
    if how == "closed-early":
        # batches 1 and 2 are out, one drained and its buffer free, one pending
        assert next(stream)[1] == jth256(blocks[0])
        stream.close()
    else:
        assert len(list(stream)) == len(blocks)
    del stream
    assert no_preparer_runs() and pipe._prepared is None
    gc.collect()  # (the CPU backend's arrays alias host words, in cycles)
    assert len(made) == 2 and all(ref() is None for ref in made)
    # an announced stream's batches went into the buffers made for it
    assert all(ref() is None for ref in prepared)
    assert not announced or seen == prepared_ids


@pytest.mark.parametrize("how", ["library", "no-library", "bytearray", "read-only"])
def test_pack_in_one_native_call_and_row_by_row_write_the_same(how,
                                                               monkeypatch):
    """ISSUE 32: the rows are written in one call of libjfscore where it is
    there (one leave and retake of the interpreter lock a batch), row by
    row with numpy where it is not, or where a block is a read-only buffer
    that is not `bytes` (ctypes takes no pointer to one)."""
    from juicefs_tpu import native

    sizes = [0, 1, 65_535, 65_536, 100_001, MIB4 - 1, MIB4, 131_072, 777]
    blocks = _blocks(seed=32, sizes=sizes)
    by_hand = np.zeros((len(blocks), 64, ROWS, COLS), dtype=np.uint32)
    for i, b in enumerate(blocks):
        w = pack_block(b)
        by_hand[i, : w.shape[0]] = w
    calls = []
    real = native.pack_rows
    if how == "no-library":
        monkeypatch.setattr(native, "pack_rows", lambda blocks, rows: False)
    else:
        if how == "bytearray":
            blocks = [bytearray(b) for b in blocks]
        elif how == "read-only":
            blocks = [memoryview(b) for b in blocks]

        def counted(blocks, rows):
            calls.append(real(blocks, rows))
            return calls[-1]
        monkeypatch.setattr(native, "pack_rows", counted)
    fresh = pack_blocks(blocks, pad_lanes=64)[0]
    dirty = np.full((len(blocks) + 1, 64, ROWS, COLS), STALE, dtype=np.uint32)
    kept = pack_blocks(blocks, pad_lanes=64, out=dirty)[0]
    assert fresh.tobytes() == kept.tobytes() == by_hand.tobytes()
    assert dirty[-1].min() == STALE
    if how in ("library", "bytearray") and native.available():
        assert calls == [True, True]  # one call a batch, and it wrote
    elif how == "read-only":
        assert calls == [False, False]
    assert pack_blocks([], pad_lanes=64)[0].shape == (0, 64, ROWS, COLS)
