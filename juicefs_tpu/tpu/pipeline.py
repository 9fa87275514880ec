"""Streaming host->device hash pipeline (SURVEY.md §7 stage 4).

Feeds block bytes from the chunk/object layer to the device in fixed-shape
batches and returns (key, digest) pairs. Mirrors the role of the reference's
async per-block upload/load pools (pkg/chunk/cached_store.go:415-472) but as
a double-buffered device pipeline: JAX dispatch is async, so packing batch
k+1 on the host overlaps hashing batch k on the TPU; results are only
blocked on one batch behind.

Each batch is one `tpu.hash.dispatch` span with the children `pack` (here),
`h2d` and `enqueue` (the transfer and the jitted call: tpu/sharding.py on
the plane, `_SingleDeviceHash` here for the kernel that runs on one
device), then one `tpu.hash.drain` when its digests are read back.

A caller that knows a stream is coming says so (`HashPipeline.prepare()`):
helper threads then make the stream's pack buffers resident while the
caller does something else, one `tpu.pack.prepare` span a buffer, and
`hash_stream` packs into those instead of first-touching its own.

Backend selection mirrors the reference's Compressor registry pattern
(pkg/compress/compress.go:31-49): "cpu" (C++/numpy host hash), "xla",
"pallas", and "tpu" (the xla program, on a TPU or not at all). Names are
resolved by tpu/device.py; a device backend that cannot initialise raises
— the host hash is reached by asking for `cpu`, never by falling back.
"""

from __future__ import annotations

import ctypes
import inspect
import os
import threading
import time
from dataclasses import dataclass
from typing import Iterable, Iterator, Optional

import numpy as np

from ..metric import global_registry
from ..metric.trace import global_tracer, stage_hist
from ..utils import get_logger
from .jth256 import (
    BLOCK_BYTES,
    COLS,
    LANE_BYTES,
    ROWS,
    digests_to_bytes,
    hash_packed_np,
    pack_blocks,
)

logger = get_logger("tpu.pipeline")

_reg = global_registry()
_BLOCKS_HASHED = _reg.counter(
    "juicefs_tpu_blocks_hashed", "Blocks hashed by the TPU pipeline"
)
_HASH_BYTES = _reg.counter(
    "juicefs_tpu_hash_bytes", "Raw bytes hashed by the TPU pipeline"
)
_H2D_BYTES = _reg.counter(
    "juicefs_tpu_h2d_bytes",
    "Host-to-device bytes shipped as packed hash batches",
)
_PACK_FRESH_BYTES = _reg.counter(
    "juicefs_tpu_pack_fresh_bytes",
    "Packed bytes of hash batches written into a pack buffer on its first "
    "use: the part of juicefs_tpu_h2d_bytes that no kept buffer took",
)
_PACK_UNREADY_BYTES = _reg.counter(
    "juicefs_tpu_pack_unready_bytes",
    "Packed bytes of hash batches whose pack buffer was not resident when "
    "the pack came: nobody had announced the stream (prepare()), or the "
    "pack waited for the preparer or took the buffer over; the part of "
    "juicefs_tpu_pack_fresh_bytes whose pack still paid for its pages",
)
_BATCH_BLOCKS = _reg.histogram(
    "juicefs_tpu_batch_blocks", "Blocks per dispatched hash batch",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
)
_DEVICE_INFO = _reg.gauge(
    "juicefs_tpu_device_info",
    "Device the hash pipeline resolved to (value 1; the labels are the "
    "device report of tpu/device.py)",
    ("platform", "device_kind", "devices", "visible_devices", "backend",
     "pallas_mode", "degraded"),
)
_FIRST_BATCH = _reg.gauge(
    "juicefs_tpu_first_batch_seconds",
    "Dispatch-to-digests wall time of this process's first device hash "
    "batch (compilation included; set-up, not rate)",
)
_TR = global_tracer()
_H_DISPATCH = stage_hist("tpu", "hash", "dispatch")
_H_PACK = stage_hist("tpu", "hash", "pack")
_H_DRAIN = stage_hist("tpu", "hash", "drain")
_H_PREPARE = stage_hist("tpu", "pack", "prepare")
# the same children tpu/sharding.py observes for the plane's batches
_H_H2D = stage_hist("tpu", "hash", "h2d")
_H_ENQUEUE = stage_hist("tpu", "hash", "enqueue")

# Threads that fault one pack buffer in, each on its own part. On the chip
# machine one thread takes 132 ms for 128 MiB and leaves pages that cost the
# next writer 1.5 us each again; four take 59 ms and leave them whole
# (tools/prefault_probe.py; PERF.md section 6), and two buffers in turn are
# both there before a scan's listing ends.
_PREPARE_THREADS = 4
_PREPARE_SLICE = 4 << 20  # numpy's touch looks at the stop flag this often


def _touch(part: np.ndarray, stop: ctypes.c_int) -> str:
    """First-touch every page of `part` (uint8, contiguous) unless `stop`
    is set on the way; says how."""
    from .. import native

    if native.touch_pages(part, stop) is not None:
        return "native"
    for at in range(0, part.size, _PREPARE_SLICE):
        if stop.value:
            break
        part[at:at + _PREPARE_SLICE:4096] = 0
    return "numpy"


class _PreparedBuffers:
    """The pack buffers of a stream that was announced before it began:
    allocated and made resident by a thread of their own, one after the
    other, and handed to `hash_stream` in that order. What a buffer holds
    is whatever the touch left: `pack_blocks(out=)` writes every row whole.

    A slot is `waiting` (not begun: the stream that wants it now takes it
    over and packs fresh, as if nothing had been prepared), `touching`
    (the stream waits for the remainder: the pages are faulted once) or
    `ready`. `stop()` ends the preparing within a MiB's touch and lets go
    of every buffer; nobody joins the thread."""

    def __init__(self, shape: tuple, count: int, parent):
        self.shape = shape
        self._parent = parent  # the announcer's span: one trace tree
        self._stop = ctypes.c_int(0)
        self._lock = threading.Lock()
        self._slots = [{"state": "waiting", "buf": None,
                        "done": threading.Event()} for _ in range(count)]
        threading.Thread(target=self._run, args=(list(self._slots),),
                         name="jfs-pack-prepare", daemon=True).start()

    def _run(self, slots) -> None:
        try:
            for slot in slots:
                with self._lock:
                    if self._stop.value:
                        return
                    if slot["state"] != "waiting":
                        continue  # the stream came first and packed fresh
                    slot["state"] = "touching"
                with _TR.span("tpu", "pack", stage="prepare", hist=_H_PREPARE,
                              parent=self._parent) as sp:
                    buf = np.empty(self.shape, dtype="<u4")
                    how = self._make_resident(buf)
                    if sp.active:
                        sp.set(bytes=buf.nbytes, how=how,
                               stopped=int(bool(self._stop.value)))
                with self._lock:
                    if not self._stop.value:
                        slot["buf"], slot["state"] = buf, "ready"
                del buf
                slot["done"].set()
        except Exception:
            # the stream packs fresh, as it would have without us
            logger.exception("preparing pack buffers failed")
        finally:
            for slot in slots:  # nobody waits for a preparer that is gone
                slot["done"].set()

    def _make_resident(self, buf: np.ndarray) -> str:
        """Every page of `buf` touched, by this thread and helpers of its
        own on disjoint parts (plain threads for the length of one buffer:
        this is no I/O for the scheduler's lanes to order)."""
        flat = buf.reshape(-1).view(np.uint8)
        n = max(1, min(_PREPARE_THREADS, os.cpu_count() or 1))
        step = -(-flat.size // n // 4096) * 4096
        parts = [flat[at:at + step] for at in range(0, flat.size, step)]
        failed = []

        def helper(part):
            try:
                _touch(part, self._stop)
            except Exception as e:
                failed.append(e)

        helpers = [threading.Thread(target=helper, args=(part,),
                                    name="jfs-pack-prepare", daemon=True)
                   for part in parts[1:]]
        for t in helpers:
            t.start()
        try:
            how = _touch(parts[0], self._stop)
        finally:
            for t in helpers:
                t.join()
        if failed:
            raise failed[0]
        return how

    def take(self) -> "tuple[np.ndarray, bool] | None":
        """The next buffer and whether it was ready when asked for; None
        once there is none to have (all taken, not begun, failed)."""
        with self._lock:
            if not self._slots:
                return None
            slot = self._slots.pop(0)
            state = slot["state"]
            if state == "waiting":
                slot["state"] = "taken"
                return None
        if state == "touching":
            slot["done"].wait()
        buf = slot["buf"]
        return None if buf is None else (buf, state == "ready")

    def stop(self) -> None:
        with self._lock:
            self._stop.value = 1
            for slot in self._slots:
                slot["buf"] = None
            self._slots = []


class _SingleDeviceHash:
    """A hash program that runs on one device and not on the sharding
    plane (the Pallas kernel): its transfer and its jitted call as the two
    steps the plane makes of them, under the same spans, so that a batch's
    dispatch is pack + h2d + enqueue whichever kernel hashes it."""

    def __init__(self, program):
        self._program = program

    def put_packed(self, words, lane_counts, lengths) -> tuple:
        """The one host->device transfer of a packed batch."""
        import jax

        with _TR.span("tpu", "hash", stage="h2d", hist=_H_H2D) as sp:
            if sp.active:
                sp.set(bytes=int(words.nbytes), sharded=False)
            return tuple(
                jax.device_put(a) for a in (words, lane_counts, lengths))

    def hash_async(self, words, lane_counts, lengths):
        """Dispatch the program and return the (still-async) device array
        of digests. Accepts host arrays (placed here) or arrays already
        placed by `put_packed` (no second transfer)."""
        import jax

        if not isinstance(words, jax.Array):
            words, lane_counts, lengths = self.put_packed(
                words, lane_counts, lengths)
        # the async call alone: a retrace or a recompile lands here
        with _TR.span("tpu", "hash", stage="enqueue", hist=_H_ENQUEUE):
            return self._program(words, lane_counts, lengths)


@dataclass
class PipelineConfig:
    backend: str = "xla"  # cpu | xla | pallas | tpu (tpu/device.py)
    batch_blocks: int = 32
    # Pad every batch to this many lanes so one compiled program serves the
    # whole stream (4 MiB default block = 64 lanes).
    pad_lanes: int = BLOCK_BYTES // LANE_BYTES
    # Dispatched-but-undrained batches allowed before hash_stream blocks on
    # the oldest result.  2 = classic double buffering (device hashes batch
    # k while the host packs k+1); deeper keeps the device busy across a
    # fetch hiccup upstream at the cost of one packed batch of host RAM per
    # extra slot.
    max_inflight_batches: int = 2


class HashPipeline:
    """hash_stream(iter[(key, bytes)]) -> iter[(key, 32-byte digest)]."""

    def __init__(self, config: Optional[PipelineConfig] = None):
        from .device import resolve_backend

        self.config = config or PipelineConfig()
        self.requested = self.config.backend
        # "tpu" -> xla on a TPU, or DeviceUnavailable; a failed backend
        # init raises here too (tpu/device.py): never a silent host hash
        self.config.backend = resolve_backend(self.requested)
        self._fn = None
        self._plane = None
        self._single: _SingleDeviceHash | None = None
        self._prepared: _PreparedBuffers | None = None
        # wall time of the first device batch, dispatch to digests: it
        # carries the compilation, so consumers report it apart from rate
        self.first_batch_seconds: float | None = None
        if self.config.backend == "xla":
            # xla rides the sharding plane (ISSUE 20): mesh over all
            # local devices, single-device jit on the degrade rung —
            # byte-identical either way.
            from .sharding import get_plane

            self._plane = get_plane()
            self._fn = self._plane.hash_async
        elif self.config.backend == "pallas":
            from .hash_jax import make_hash_fn

            self._single = _SingleDeviceHash(make_hash_fn("pallas"))
            self._fn = self._single.hash_async
        # initialises the backend for xla/pallas (raising if it cannot)
        # and says which mode Pallas will run in
        report = self.device_report()
        _DEVICE_INFO.labels(*(
            str(report[k]) for k in _DEVICE_INFO.label_names)).set(1)

    def _note_first_batch(self, t0: float) -> None:
        if self.first_batch_seconds is None:
            self.first_batch_seconds = time.perf_counter() - t0
            _FIRST_BATCH.set(self.first_batch_seconds)

    def _buffer_shape(self) -> tuple:
        return (self.config.batch_blocks, self.config.pad_lanes, ROWS, COLS)

    def prepare(self) -> None:
        """A stream is coming: have its pack buffers resident by the time
        it packs, without holding the caller up (`_PreparedBuffers`). The
        next `hash_stream` takes them; `release()` is for the caller whose
        stream may never come. Nothing to prepare on the `cpu` backend,
        which packs nothing."""
        if self._fn is not None and self._prepared is None:
            self._prepared = _PreparedBuffers(
                self._buffer_shape(),
                max(1, self.config.max_inflight_batches), _TR.current_ref())

    def release(self) -> None:
        """Stop preparing and let go of what was prepared and not taken."""
        prepared, self._prepared = self._prepared, None
        if prepared is not None:
            prepared.stop()

    def hash_stream(
        self, items: Iterable[tuple[str, bytes]]
    ) -> Iterator[tuple[str, bytes]]:
        cfg = self.config
        pending: list[tuple[list[str], object, float, object]] = []
        keys: list[str] = []
        blocks: list[bytes] = []
        # Pack buffers this stream keeps (docs/ARCHITECTURE.md "The scan's
        # host memory"): a batch packs into a free one, or fresh, and the
        # fresh array becomes one. Only drain() gives a buffer back, once
        # its batch's digests are here: until then the device may read the
        # host words (the CPU backend's device_put can alias them, a TPU's
        # may still be copying when it returns). At most
        # max_inflight_batches of them, none outliving the stream. Where
        # the stream was announced (prepare()) its first packs take the
        # buffers made ready for it instead of making their own.
        free: list[np.ndarray] = []
        # the module's name is a seam others stand functions in; under one
        # that takes no `out` every batch packs fresh, as before
        params = inspect.signature(pack_blocks).parameters.values()
        keeps = any(p.name == "out" or p.kind is p.VAR_KEYWORD for p in params)
        prepared, self._prepared = self._prepared, None
        if prepared is not None and not (
                keeps and prepared.shape == self._buffer_shape()):
            prepared.stop()
            prepared = None

        def pack(blocks):
            """-> packed, the buffer to keep, first use of it, was ready"""
            # a buffer has the rows of its first batch (a prepared one
            # batch_blocks): only a stream's last batch is shorter than
            # batch_blocks, and nothing follows
            if free:
                buf = free.pop()
                return pack_blocks(blocks, pad_lanes=cfg.pad_lanes,
                                   out=buf), buf, False, True
            got = prepared.take() if prepared is not None else None
            if got is not None:
                buf, ready = got
                return pack_blocks(blocks, pad_lanes=cfg.pad_lanes,
                                   out=buf), buf, True, ready
            packed = pack_blocks(blocks, pad_lanes=cfg.pad_lanes)
            return packed, packed[0] if keeps else None, True, False

        def dispatch():
            nonlocal keys, blocks
            if not blocks:
                return
            nbytes = sum(len(b) for b in blocks)
            t0 = time.perf_counter()
            with _TR.span("tpu", "hash", stage="dispatch",
                          hist=_H_DISPATCH) as sp:
                if sp.active:
                    sp.set(batch=len(blocks), bytes=nbytes,
                           backend=self.config.backend)
                from .. import native

                if self._fn is None:
                    # CPU path: hash raw bytes directly (native C++ batch with
                    # numpy fallback) — no packing cost, already synchronous,
                    # and no device transfer (h2d counter stays untouched).
                    pending.append(
                        (keys, native.jth256_batch(blocks), t0, None))
                else:
                    with _TR.span("tpu", "hash", stage="pack",
                                  hist=_H_PACK) as psp:
                        # a wait for a buffer still being made ready is
                        # the pack's own time: what this thread paid
                        (words, counts, lengths), buf, fresh, ready = pack(
                            blocks)
                        if psp.active:
                            psp.set(batch=len(blocks), bytes=nbytes,
                                    padded_bytes=words.nbytes,
                                    fresh=int(fresh), ready=int(ready),
                                    # which pack ran: libjfscore's one
                                    # call, or numpy row by row (0)
                                    native=int(native.available()))
                    if fresh:
                        _PACK_FRESH_BYTES.inc(words.nbytes)
                    if not ready:
                        _PACK_UNREADY_BYTES.inc(words.nbytes)
                    _H2D_BYTES.inc(words.nbytes)
                    pending.append(
                        (keys, self._fn(words, counts, lengths), t0, buf))
            _BATCH_BLOCKS.observe(len(blocks))
            _BLOCKS_HASHED.inc(len(blocks))
            _HASH_BYTES.inc(nbytes)
            keys, blocks = [], []

        def drain(batch) -> Iterator[tuple[str, bytes]]:
            bkeys, out, t0, buf = batch
            if isinstance(out, list):
                digests = out
            else:
                # blocking device sync: the stage where dispatch latency
                # actually lands (JAX dispatch above is async)
                with _TR.span("tpu", "hash", stage="drain",
                              hist=_H_DRAIN) as sp:
                    if sp.active:
                        sp.set(batch=len(bkeys),
                               backend=self.config.backend)
                    digests = digests_to_bytes(np.asarray(out))
                if buf is not None:
                    free.append(buf)  # its batch has been read
                self._note_first_batch(t0)
            return zip(bkeys, digests[: len(bkeys)])

        try:
            for key, data in items:
                if len(data) > cfg.pad_lanes * LANE_BYTES:
                    raise ValueError(
                        f"block {key} larger than pipeline pad size")
                keys.append(key)
                blocks.append(data)
                if len(blocks) >= cfg.batch_blocks:
                    dispatch()
                    # Async dispatch: the device hashes batch k while the
                    # host packs later ones; block only past the
                    # configured depth.
                    depth = max(1, cfg.max_inflight_batches)
                    while len(pending) >= depth:
                        yield from drain(pending.pop(0))
            dispatch()
            while pending:
                yield from drain(pending.pop(0))
        finally:
            # ended, closed early or failed: what was prepared and not
            # taken goes, and nobody touches pages for a stream that is over
            if prepared is not None:
                prepared.stop()

    def hash_blocks(self, blocks: Iterable[bytes]) -> list[bytes]:
        return [d for _, d in self.hash_stream((str(i), b) for i, b in enumerate(blocks))]

    @property
    def device_backend(self) -> bool:
        """True when digests come off a JAX device program."""
        return self._fn is not None

    def shard_packed(self, packed):
        """Place a packed triple on devices for the shared-H2D contract
        (ISSUE 8/20): ONE (sharded, on the plane) device transfer feeds
        both the hash and the estimator jits. This is the sharding-plane
        seam chunk/ consumers enter through — no bare device_put above
        tpu/. A placement failure raises (the device is unusable; hiding
        it would double the transfer silently). cpu backend: no-op (host
        arrays hash on the host)."""
        if self._plane is not None:
            return self._plane.put_packed(*packed)
        if self._single is not None:  # single-device backend (pallas)
            return self._single.put_packed(*packed)
        return packed

    def device_report(self) -> dict:
        """Which device this pipeline's digests come from (tpu/device.py):
        platform, device_kind, device counts, mesh, degraded + reason,
        resolved backend, Pallas mode, peak device memory so far — plus
        the first device batch's wall time, which carries the compilation
        and so is reported apart from any rate."""
        from .device import device_report

        first = self.first_batch_seconds
        return {
            **device_report(self.config.backend, self.requested),
            "first_batch_seconds": None if first is None else round(first, 3),
        }

    def hash_packed(self, words, counts, lengths,
                    n: int | None = None) -> list[bytes]:
        """Digest a pre-packed batch (shared-H2D contract, ISSUE 8): the
        caller packs once and the SAME upload feeds hash and compress
        outputs. On the cpu backend this is the vectorized numpy path —
        byte-identical, no transfer (h2d counter untouched). `n` is the
        original batch size when the input was padded by the sharding
        plane (`shard_packed`); outputs are sliced back to it."""
        if n is None:
            n = int(getattr(words, "shape", [len(counts)])[0])
        with _TR.span("tpu", "hash", stage="dispatch",
                      hist=_H_DISPATCH) as sp:
            nbytes = int(np.asarray(lengths)[:n].sum()) if n else 0
            if sp.active:
                sp.set(batch=n, bytes=nbytes,
                       backend=self.config.backend)
            t0 = time.perf_counter()
            if self._fn is None:
                out = hash_packed_np(words, counts, lengths)
            else:
                _H2D_BYTES.inc(words.nbytes)
                out = self._fn(words, counts, lengths)
        _BATCH_BLOCKS.observe(n)
        _BLOCKS_HASHED.inc(n)
        _HASH_BYTES.inc(nbytes)
        with _TR.span("tpu", "hash", stage="drain", hist=_H_DRAIN) as sp:
            if sp.active:
                sp.set(batch=n, backend=self.config.backend)
            digests = digests_to_bytes(np.asarray(out))[:n]
        if self._fn is not None:
            self._note_first_batch(t0)
        return digests


_FLUSH = object()  # kick(): hash whatever is buffered NOW (commit barrier)
_CLOSE = object()


class HashBatcher:
    """Bounded-queue accumulator in front of a HashPipeline (flush-timeout
    mode, ISSUE 5).

    The pipeline wants device-sized batches (batch_blocks × block_size per
    dispatch) but the ingest path produces blocks one upload at a time, and
    a writer's commit barrier (`WSlice.finish`) may be waiting on a single
    block. The batcher bridges the two rates: producers `submit()` without
    ever blocking (a full queue returns False — overload is the caller's
    degrade signal, mirroring chunk/indexer.py's drop contract), and the
    consumer pulls batches that are flushed by whichever comes first —

      - the batch filled (`batch_blocks`),
      - `flush_timeout` expired since the batch's first block (a lone
        block never waits out a full batch window), or
      - `kick()` — a commit barrier is waiting; hash what we have NOW.
    """

    def __init__(self, pipe: HashPipeline, queue_blocks: int = 64,
                 flush_timeout: float = 0.005):
        import queue as _queue

        self.pipe = pipe
        self.flush_timeout = flush_timeout
        self._q: "_queue.Queue" = _queue.Queue(maxsize=max(1, queue_blocks))
        self._empty = _queue.Empty
        self._closed = False

    def submit(self, item) -> bool:
        """Producer side; returns False when the hash plane is saturated
        (queue full) or the batcher is closed (an item enqueued behind
        the close sentinel would never be consumed) — the caller
        degrades, it never blocks here."""
        if self._closed:
            return False
        try:
            self._q.put_nowait(item)
            return True
        except Exception:
            return False

    def kick(self) -> None:
        """Flush the current partial batch immediately. Non-blocking by
        contract (a commit barrier calls this): when the queue is full
        the marker is simply dropped — a full queue means the consumer is
        saturated and the batch flushes on size or timeout anyway."""
        try:
            self._q.put_nowait(_FLUSH)
        except Exception:
            pass

    def close(self) -> None:
        """Non-blocking by contract (ISSUE 8 satellite): the old
        blocking `put(_CLOSE)` could park the closer behind a saturated
        consumer when the queue was full. The closed flag is the
        authoritative signal — the consumer drains everything accepted
        before the flag, then exits on an empty queue; the sentinel is
        only a wake-up fast path and is dropped when there is no room."""
        self._closed = True
        try:
            self._q.put_nowait(_CLOSE)
        except Exception:
            pass

    def qsize(self) -> int:
        return self._q.qsize()

    def batches(self) -> Iterator[list]:
        """Consumer side: yield non-empty item batches until close().
        Drain guard: a close() that could not enqueue its sentinel (full
        queue) still terminates this loop — every accepted item is
        yielded first, then the closed+empty state ends it."""
        batch_blocks = max(1, self.pipe.config.batch_blocks)
        while True:
            try:
                item = self._q.get(timeout=0.1)
            except self._empty:
                if self._closed:
                    return
                continue
            if item is _CLOSE:
                return
            if item is _FLUSH:
                continue
            batch = [item]
            deadline = time.monotonic() + self.flush_timeout
            while len(batch) < batch_blocks:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    nxt = self._q.get(timeout=remaining)
                except self._empty:
                    break
                if nxt is _CLOSE:
                    yield batch
                    return
                if nxt is _FLUSH:
                    break
                batch.append(nxt)
            yield batch
