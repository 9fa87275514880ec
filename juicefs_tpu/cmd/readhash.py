"""The read-and-hash stage of the bulk scans: `gc --dedup` over the blocks
its content index lacks, `fsck --verify-data` over every block it expects.

Object GETs run `threads` deep through the ordered parallel-fetch stage
(chunk/parallel.py), overlapping storage I/O with TPU hash dispatch;
results arrive in input order, so digests are byte-identical to a serial
walk.  Never more than `threads` GETs run at once; the stage fetches one
hash batch ahead of them (`batch_blocks` of the pipeline, 32), so that
while the calling thread packs, ships and drains batch k the pool is
fetching batch k + 1.  Host memory: at most `(threads + batch_blocks) x
block_size` of fetched blocks wait for the hash (42 x 4 MiB = 168 MiB at
the defaults with `--threads 10`), beside the batch being gathered.

A caller builds the pipeline when it opens the volume (`scan_pipeline`) and
announces the stream there (`HashPipeline.prepare()`), so that the pack
buffers are resident by the time the first batch packs
(docs/ARCHITECTURE.md "The scan's host memory").
"""

from __future__ import annotations

from typing import Iterable, Iterator

from ..chunk.parallel import FetchStats, fetch_ordered
from ..object.resilient import BreakerOpenError


def scan_pipeline(backend: str, block_size: int):
    """The hash pipeline of a scan over blocks of up to `block_size` bytes:
    one program for the whole stream, every block padded to the volume's
    block."""
    from ..tpu.pipeline import HashPipeline, PipelineConfig

    return HashPipeline(PipelineConfig(
        backend=backend, pad_lanes=max(1, block_size // 65536)))


class ReadHash:
    """One scan's read-and-hash stage over `store`, hashing through `pipe`.

    `window` GETs at once, `ahead` fetched past them (what `hash_stream`
    takes between two stretches of its own work); `fetched` is the fetch
    stage's own account of its time (`FetchStats`); `failed` maps each key
    whose GET raised to what the error said.

    An open circuit at the store ends the fetch stage (chunk/parallel.py).
    With `outlive_open` the stream then ends there instead of raising:
    what was fetched is still hashed and yielded, and `stopped` holds the
    error.
    """

    def __init__(self, store, pipe, threads: int, outlive_open: bool = False):
        self.store, self.pipe = store, pipe
        self.outlive_open = outlive_open
        self.stopped: BreakerOpenError | None = None
        self.window = max(1, threads)
        self.ahead = pipe.config.batch_blocks
        self.fetched = FetchStats()
        self.failed: dict[str, str] = {}

    def digests(self, keys: Iterable[str],
                sizes: dict[str, int]) -> Iterator[tuple[str, bytes]]:
        """(key, digest) of every block of `keys` that could be read, in
        input order; `sizes` gives each block's length."""
        store = self.store

        def load(key):
            try:
                return store._load_block(key, sizes[key], cache_after=False)
            except Exception as e:
                self.failed[key] = str(e)
                raise

        def blocks():
            # windowed parallel GETs on the store's download pool, a batch
            # ahead of the hash pipeline and yielded into it in input
            # order; a bad block is skipped (and logged by the stage),
            # never aborts the scan
            try:
                yield from fetch_ordered(
                    keys, load, store._bulk_pool, self.window,
                    on_error="skip", stats=self.fetched, ahead=self.ahead,
                )
            except BreakerOpenError as e:
                if not self.outlive_open:
                    raise
                self.stopped = e

        return self.pipe.hash_stream(blocks())

    def stage_seconds(self, readhash: float) -> dict[str, float]:
        """The stage's rows of a scan's `stage_seconds`, given the wall
        time of the span it ran in.  `get` is WALL time the fetch stage
        had GETs in flight; `get_threads` is aggregate per-thread GET
        seconds — their ratio is the achieved I/O overlap factor (ISSUE
        2) — and `hash` is the read+hash wall (`readhash`) not hidden
        behind the fetch window."""
        wall = self.fetched.wall
        return {
            "get": round(wall, 6),
            "get_threads": round(self.fetched.seconds, 6),
            "hash": round(max(readhash - wall, 0.0), 6),
            "readhash": round(readhash, 6),
        }
