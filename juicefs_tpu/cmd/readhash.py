"""The read-and-hash stage of the bulk scans: `gc --dedup` over the blocks
its content index lacks, `fsck --verify-data` over every block it expects,
`sync --check-all|--check-new --hash-backend` over the ranges of both
objects of every pair it compares.

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

What loads an item and the pool its GETs run on are the caller's: a scan
of a volume reads blocks of its chunk store on the store's download pool
(`chunk_blocks`), `sync` reads ranges of two raw object stores on its own
`bulk` executor.  The stage never looks inside an item.
"""

from __future__ import annotations

from typing import Callable, Hashable, Iterable, Iterator, TypeVar

from ..chunk.parallel import FetchStats, fetch_ordered
from ..object import CorruptObjectError
from ..object.resilient import BreakerOpenError

T = TypeVar("T", bound=Hashable)


def scan_pipeline(backend: str, block_size: int):
    """The hash pipeline of a scan over blocks of up to `block_size` bytes:
    one program for the whole stream, every block padded to the volume's
    block."""
    from ..tpu.pipeline import HashPipeline, PipelineConfig

    return HashPipeline(PipelineConfig(
        backend=backend, pad_lanes=max(1, block_size // 65536)))


def chunk_blocks(store, sizes: dict[str, int]):
    """`load` and `pool` of a scan over a volume's chunk store: a block by
    its key (`sizes` gives each block's length), past the cache, on the
    store's download pool."""
    def load(key: str) -> bytes:
        return store._load_block(key, sizes[key], cache_after=False)

    return load, store._bulk_pool


class ReadHash:
    """One scan's read-and-hash stage: `load(item)` gives an item's bytes,
    on a thread of `pool`, hashed through `pipe`.

    `window` GETs at once, `ahead` fetched past them (what `hash_stream`
    takes between two stretches of its own work); `fetched` is the fetch
    stage's own account of its time (`FetchStats`); `failed` maps each
    item whose GET raised to what the error said, and `corrupt` each item
    whose object is corrupt (`CorruptObjectError`) to the check that
    failed. Both keep text alone: an error's traceback holds the frames
    that fetched the object, and with them its bytes.

    An open circuit at the store ends the fetch stage (chunk/parallel.py).
    With `outlive_open` the stream then ends there instead of raising:
    what was fetched is still hashed and yielded, and `stopped` holds the
    error.
    """

    def __init__(self, load: Callable[[T], bytes], pool, pipe, threads: int,
                 outlive_open: bool = False):
        self.load, self.pool, self.pipe = load, pool, pipe
        self.outlive_open = outlive_open
        self.stopped: BreakerOpenError | None = None
        self.window = max(1, threads)
        self.ahead = pipe.config.batch_blocks
        self.fetched = FetchStats()
        self.failed: dict[T, str] = {}
        self.corrupt: dict[T, str] = {}

    def digests(self, items: Iterable[T]) -> Iterator[tuple[T, bytes]]:
        """(item, digest) of every item that could be read, in input
        order."""
        def load(item):
            try:
                return self.load(item)
            except Exception as e:
                self.failed[item] = str(e)
                if isinstance(e, CorruptObjectError):
                    self.corrupt[item] = e.reason
                raise

        def blocks():
            # windowed parallel GETs on the caller's pool, a batch ahead
            # of the hash pipeline and yielded into it in input order; a
            # bad item is skipped (and logged by the stage), never aborts
            # the scan
            try:
                yield from fetch_ordered(
                    items, load, self.pool, self.window,
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
