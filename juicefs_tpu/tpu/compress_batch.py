"""Batched compression plane (ISSUE 8 tentpole).

The third stage of the north-star triad (PAPER.md §7: device-batched
hashing + dedup scan + LZ4/Zstd compression behind the chunk-store
boundary). Hashing and the dedup scan went device-batched in PRs 3-5;
compression stayed serial ctypes-liblz4 inside each upload worker, where
it took most of an ingest's wall time (a host-clock reading of an earlier
round; no chip figure — speed is in PERF.md).

`CompressPlane` mirrors the `HashPipeline` backend-registry contract
(`cpu | xla`, tpu/pipeline.py):

  cpu   batched encode: the batch fans out across a qos "slice"-lane
        executor sized to the host cores, one zero-copy liblz4 call per
        block (ctypes releases the GIL, so lanes compress in parallel).
  xla   the same CPU lane encode (output stays byte-identical to the
        serial ctypes path — the acceptance bar), plus a device
        compressibility estimator that rides the SAME packed H2D upload
        the HashBatcher already ships: one `pack_blocks` transfer feeds
        hash digests AND per-block entropy/ratio predictions. The
        estimate is advisory (ratio telemetry, elision-bypass inputs);
        the encoded bytes come from liblz4 either way, which is what
        makes the decompress path and every existing volume compatible.

Degrade ladder (same advisory contract as the hash plane): a backend
that fails to initialize falls back to cpu; a lane fan-out that cannot
place work (scheduler closed, queue full under `nowait`) degrades that
batch to the serial in-thread encode. Compression never fails a write
for want of parallelism — `juicefs_compress_degraded` counts every rung
taken.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Optional, Sequence

from ..metric import global_registry
from ..utils import get_logger

logger = get_logger("tpu.compress")

_reg = global_registry()
_BATCH_BLOCKS = _reg.histogram(
    "juicefs_compress_batch_blocks", "Blocks per batched compress call",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
)
_BYTES_IN = _reg.counter(
    "juicefs_compress_bytes_in", "Raw bytes entering the compression plane"
)
_BYTES_OUT = _reg.counter(
    "juicefs_compress_bytes_out", "Compressed bytes leaving the plane"
)
_RATIO = _reg.histogram(
    "juicefs_compress_ratio",
    "Per-block compressed/raw size ratio (1.0+ = incompressible)",
    buckets=(0.05, 0.1, 0.2, 0.3, 0.5, 0.7, 0.9, 0.97, 1.0, 1.1),
)
_DEGRADED = _reg.counter(
    "juicefs_compress_degraded",
    "Compression-plane degrades taken (device backend -> cpu at init, "
    "lane fan-out -> serial in-thread encode at runtime)",
)

BACKENDS = ("cpu", "xla")


@dataclass
class CompressBatchConfig:
    backend: str = "cpu"  # cpu | xla (xla adds the device estimator)
    # parallel encode lanes on the qos "slice" lane; 0 = host cores
    lanes: int = 0
    # batches below either floor encode serially: a lane hop costs more
    # than it saves on a lone or tiny block
    min_fanout_blocks: int = 2
    min_fanout_bytes: int = 64 << 10


def _make_estimator():
    """Jitted per-block compressibility estimator from packed words.

    Subsamples 256 bytes per 64 KiB lane (every 16th row x every 16th
    column of the uint32 word matrix), builds a per-block byte histogram
    with padded lanes masked out, and returns the byte entropy scaled to
    a predicted compressed-size fraction in (0, 1]. Runs on whatever
    backend JAX initialized; raising here is the caller's degrade signal.
    """
    import jax
    import jax.numpy as jnp

    jax.devices()  # force backend init; may raise

    @jax.jit
    def est(words, lane_counts):
        b, m = words.shape[0], words.shape[1]
        sub = words[:, :, ::16, ::16].reshape(b, -1)  # (B, M*64) uint32
        by = jnp.stack(
            [(sub >> jnp.uint32(8 * i)) & jnp.uint32(0xFF) for i in range(4)],
            axis=-1,
        ).reshape(b, -1).astype(jnp.int32)  # (B, M*256) sampled bytes
        lanes = jnp.arange(m, dtype=jnp.int32)
        mask = (lanes[None, :] < lane_counts[:, None]).astype(jnp.float32)
        w = jnp.repeat(mask, 256, axis=1)  # 256 sampled bytes per lane

        def hist(v, wt):
            return jnp.zeros((256,), jnp.float32).at[v].add(wt)

        h = jax.vmap(hist)(by, w)
        p = h / jnp.maximum(h.sum(-1, keepdims=True), 1.0)
        ent = -jnp.sum(jnp.where(p > 0, p * jnp.log2(p), 0.0), axis=-1)
        return jnp.minimum(ent / 8.0, 1.0)

    return est


class CompressPlane:
    """Batched write-path compression with the hash plane's degrade
    contract. `compress_one` is the single-block seam `_put_block`
    routes through (serial fast path, the degrade target); the ingest
    finalizer feeds whole MISS batches to `compress_blocks`."""

    def __init__(self, compressor, config: Optional[CompressBatchConfig] = None,
                 scheduler=None):
        self.compressor = compressor
        self.config = config or CompressBatchConfig()
        if self.config.backend not in BACKENDS:
            raise ValueError(
                f"unknown compress backend {self.config.backend!r} "
                f"(want {'|'.join(BACKENDS)})"
            )
        self._est_fn = None
        if self.config.backend == "xla" and self.active:
            try:
                # the estimator comes off the sharding plane (ISSUE 20):
                # pjit-sharded over the mesh when the shared pack divides,
                # the same single-device jit as before otherwise —
                # advisories identical either way
                from .sharding import get_plane

                self._est_fn = get_plane().make_estimator()
            except Exception as e:
                # no usable accelerator: compressed bytes must still flow,
                # so drop to the lane-parallel CPU plane (byte-identical
                # output; only the advisory estimate is lost)
                logger.warning(
                    "compress backend %r unavailable (%s); degrading to cpu",
                    self.config.backend, e,
                )
                self.config.backend = "cpu"
                _DEGRADED.inc()
        self._exec = None
        self.lanes = 0
        if self.active:
            from ..qos import IOClass, global_scheduler

            sched = scheduler or global_scheduler()
            self.lanes = self.config.lanes or max(2, os.cpu_count() or 2)
            # qos lane sizing: the encode fan-out shares the "slice" lane
            # (CPU-bound work, same as the read-side slice spool) at
            # INGEST class — it outranks background bulk work but never
            # starves a foreground read's slice fan-out
            self._exec = sched.executor("slice", IOClass.INGEST,
                                        width=self.lanes)
        self._lock = threading.Lock()
        # stats mirror of the global counters, per plane (bench/tests)
        self.blocks = 0
        self.batches = 0
        self.bytes_in = 0
        self.bytes_out = 0
        self.degraded = 0
        self.estimated = 0
        self.last_estimate: Optional[list] = None

    @property
    def active(self) -> bool:
        """False for the none-compressor: everything passes through."""
        return bool(self.compressor.name)

    @property
    def backend(self) -> str:
        return self.config.backend

    # -- single block (the `_put_block` seam) ------------------------------
    def compress_one(self, raw) -> bytes:
        data = self.compressor.compress(raw)
        self._account([len(raw)], [len(data)], batch=False)
        return data

    # -- whole batch (the ingest-finalizer seam) ---------------------------
    def compress_blocks(self, blocks: Sequence, packed=None) -> list[bytes]:
        """Compress a batch, fanning out across the slice lane.

        `packed` is the (words, lane_counts, lengths) triple the hash
        dispatch already uploaded (tpu/jth256.pack_blocks): with the xla
        backend it feeds the device estimator — no second H2D. Output is
        byte-identical to [compressor.compress(b) for b in blocks].
        """
        blocks = list(blocks)
        if not blocks:
            return []
        if not self.active:
            return [self.compressor.compress(b) for b in blocks]
        if self._est_fn is not None and packed is not None:
            self.estimate_packed(packed)
        nbytes = sum(len(b) for b in blocks)
        if (self._exec is None
                or len(blocks) < self.config.min_fanout_blocks
                or nbytes < self.config.min_fanout_bytes):
            out = [self.compressor.compress(b) for b in blocks]
        else:
            out = self._fanout(blocks)
        self._account([len(b) for b in blocks], [len(d) for d in out])
        return out

    def _fanout(self, blocks: list) -> list[bytes]:
        # one task per LANE, not per block: each submit/result crossing
        # is Python-level work competing for the GIL against the encode
        # threads themselves — chunking keeps the lanes C-dominated
        n = min(self.lanes, len(blocks))
        step = -(-len(blocks) // n)
        chunks = [blocks[i:i + step] for i in range(0, len(blocks), step)]
        comp = self.compressor

        def encode(chunk: list) -> list[bytes]:
            return [comp.compress(b) for b in chunk]

        futs = []
        for chunk in chunks:
            try:
                # nowait: a saturated slice lane must degrade THIS batch
                # to the serial path, not park the ingest worker behind
                # someone else's backlog (advisory contract)
                futs.append(self._exec.submit(encode, chunk, nowait=True))
            except (TimeoutError, RuntimeError):
                futs.append(None)
        out: list[bytes] = []
        degraded = 0
        for chunk, f in zip(chunks, futs):
            if f is None:
                degraded += len(chunk)
                out.extend(comp.compress(b) for b in chunk)
            else:
                out.extend(f.result())
        if degraded:
            self.degraded += degraded
            _DEGRADED.inc(degraded)
        return out

    def estimate_packed(self, packed) -> None:
        """Advisory device pass from the shared H2D words (the ingest
        worker calls this with the same packed triple the hash batch
        uploaded); failures only cost the estimate, never the batch."""
        if self._est_fn is None:
            return
        try:
            import numpy as np

            words, counts, _lengths = packed
            pred = np.asarray(self._est_fn(words, counts))
            # a plane-placed pack (ShardedPack) was padded to the mesh's
            # data-axis extent; slice the advisory back to the real batch
            n = getattr(packed, "batch", None)
            if n is not None:
                pred = pred[:n]
            with self._lock:
                self.estimated += len(pred)
                self.last_estimate = [round(float(p), 4) for p in pred]
        except Exception as e:
            logger.warning("compress estimate degraded: %s", e)
            self.degraded += 1
            _DEGRADED.inc()
            self._est_fn = None  # broken device: stop paying for retries

    def _account(self, sizes_in: list, sizes_out: list, batch=True) -> None:
        n_in, n_out = sum(sizes_in), sum(sizes_out)
        _BYTES_IN.inc(n_in)
        _BYTES_OUT.inc(n_out)
        if batch:
            _BATCH_BLOCKS.observe(len(sizes_in))
        for i, o in zip(sizes_in, sizes_out):
            if i > 0:
                _RATIO.observe(o / i)
        with self._lock:
            self.blocks += len(sizes_in)
            if batch:
                self.batches += 1
            self.bytes_in += n_in
            self.bytes_out += n_out

    def close(self) -> None:
        """Drain this plane's outstanding lane submissions (the executor
        owns only its own futures — closing never stops slice-lane
        workers another consumer shares)."""
        if self._exec is not None:
            self._exec.shutdown(wait=True, timeout=60.0)

    def stats(self) -> dict:
        with self._lock:
            return {
                "backend": self.backend,
                "algorithm": self.compressor.name or "none",
                "lanes": self.lanes,
                "blocks": self.blocks,
                "batches": self.batches,
                "bytes_in": self.bytes_in,
                "bytes_out": self.bytes_out,
                "ratio": round(self.bytes_out / self.bytes_in, 4)
                if self.bytes_in else 0.0,
                "degraded": self.degraded,
                "estimated": self.estimated,
            }
