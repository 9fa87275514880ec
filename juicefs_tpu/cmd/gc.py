"""`gc`: garbage-collect leaked objects; TPU content dedup scan.

Reference cmd/gc.go:76-330: scan all slices from meta, list `chunks/`
objects from the store, diff -> leaked/pending, optionally delete.

New TPU-first capability (BASELINE.md north star): `--dedup` streams every
live block through the batched JTH-256 pipeline and reports duplicate
content groups and reclaimable bytes — content addressing the reference
does not have (its gc diffs block *names* only, cmd/gc.go:253-296).

One invocation is one trace (metric/trace.py): the root span `cmd.gc`, its
stages `open`, `live`, `list`, `index_load`, `readhash`, `backfill`, `group`,
`write_index`, `list_wait`, `reconcile` below it, and below `readhash` the
fetch stage's and the hash pipeline's own spans. Every stage feeds
`juicefs_tpu_stage_seconds{layer="cmd",op="gc"}` whether anyone listens or
not; `--trace DIR` attaches a reader and writes what it heard.

With `--dedup` the store is listed (`list`) on a thread of its own,
`jfs-gc-list`, beside the scan: the hash stage reads what the slices say is
live and never what the store holds, so only the name diff waits for the
listing (`list_wait`), after the scan (`_gc`).
"""

from __future__ import annotations

import contextlib
import json
import threading
import time

from ..chunk.cached_store import block_key, parse_block_key
from ..metric import global_registry
from ..metric.trace import global_tracer, span_summary, stage_hist
from ..qos import IOClass
from ..tpu.device import HASH_BACKENDS
from ..utils import get_logger
from .readhash import ReadHash, chunk_blocks, scan_pipeline

logger = get_logger("cmd.gc")

_TR = global_tracer()
_H_GC = stage_hist("cmd", "gc")
_H_OPEN = stage_hist("cmd", "gc", "open")
_H_LIVE = stage_hist("cmd", "gc", "live")
_H_LIST = stage_hist("cmd", "gc", "list")
_H_LIST_WAIT = stage_hist("cmd", "gc", "list_wait")
_H_INDEX_LOAD = stage_hist("cmd", "gc", "index_load")
_H_READHASH = stage_hist("cmd", "gc", "readhash")
_H_BACKFILL = stage_hist("cmd", "gc", "backfill")
_H_GROUP = stage_hist("cmd", "gc", "group")
_H_WRITE_INDEX = stage_hist("cmd", "gc", "write_index")
_H_RECONCILE = stage_hist("cmd", "gc", "reconcile")
_SCAN_MINOR_FAULTS = global_registry().counter(
    "juicefs_scan_minor_faults",
    "Minor page faults of this process (getrusage ru_minflt) over the "
    "read+hash stage of dedup scans: what first-touching host memory costs",
)


def add_parser(sub):
    p = sub.add_parser("gc", help="collect leaked objects / dedup scan")
    p.add_argument("meta_url")
    p.add_argument("--delete", action="store_true", help="delete leaked objects")
    p.add_argument("--compact", action="store_true", help="compact fragmented chunks")
    p.add_argument("--dedup", action="store_true", help="content-addressed dedup scan")
    p.add_argument("--hash-backend", default=None,
                   choices=HASH_BACKENDS,
                   help="hash backend for --dedup (default: the volume's; "
                        "`tpu` fails unless JAX finds a TPU)")
    p.add_argument("--threads", type=int, default=10)
    p.add_argument("--age", type=float, default=3600.0,
                   help="only treat objects older than this (seconds) as leaked")
    p.add_argument("--dedup-index", default="", help="write content index JSON here")
    p.add_argument("--trace", default="", metavar="DIR",
                   help="record this invocation's spans: a chrome://tracing-"
                        "loadable juicefs-trace.json in DIR, per-span self "
                        "times in the --dedup stats line and, with a device "
                        "hash backend, a JAX profiler trace of the scan in "
                        "DIR with the same spans on the device's clock")
    p.set_defaults(func=run)


def run(args) -> int:
    trace_dir = getattr(args, "trace", "")
    with (_ScanTrace(trace_dir) if trace_dir
          else contextlib.nullcontext()) as trace:
        # what the invocation started and has to end whatever happens
        with _TR.span("cmd", "gc", hist=_H_GC) as root, \
                contextlib.ExitStack() as at_exit:
            stats = _gc(args, trace, root, at_exit)
    # after the root has closed: its own row belongs in the table
    if stats is not None:
        if trace is not None:
            stats["spans"] = span_summary(trace.events)
        print(json.dumps(stats))
    return 0


class _ScanTrace:
    """`gc --trace DIR`: `profile --trace DIR` for the process that has no
    mount, hence no `.trace` file anyone could hold open. An in-process
    reader hears every span of the invocation; around a scan that hashes
    on a device the JAX profiler runs as well, and for as long as it does
    the tracer opens each span as a `jax.profiler.TraceAnnotation` too
    (`Tracer.annotate`), so DIR gets the device's planes with the
    program's spans beside them."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.events: list[dict] = []
        self._profiling = False

    def __enter__(self):
        _TR.open_reader(self, max_events=None)
        return self

    def start_profiler(self) -> None:
        """Called once the backend is known to be a device: starting the
        profiler initialises JAX's backend, which a host-hash scan must
        never do."""
        import jax.profiler

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # spans, not every Python call
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        self._profiling = True
        _TR.annotate = jax.profiler.TraceAnnotation

    def __exit__(self, *exc):
        from .stats import write_chrome_trace

        try:
            if self._profiling:
                import jax.profiler

                _TR.annotate = None
                jax.profiler.stop_trace()
        finally:
            lines = _TR.read(self, 1 << 62).splitlines()
            _TR.close_reader(self)
        self.events = [json.loads(line) for line in lines]
        path = write_chrome_trace(self.out_dir, self.events)
        logger.info("%d spans -> %s", len(self.events), path)
        return False


class _StoreListing:
    """The store's half of the name diff: every block object under
    `chunks/` with its size (`stored`) and those younger than `cutoff`
    (`recent`), read in the span `cmd.gc.list`.

    `run()` lists on the calling thread. `start()` lists on a thread of
    its own, `jfs-gc-list`, whose span hangs off `parent`; `wait()` joins
    it, raises what the listing raised and says how much of the listing
    the caller did not wait for; `stop()` ends it early and joins, for the
    invocation that is on its way out. The thread touches the object store
    and no meta client."""

    def __init__(self, storage, cutoff: float, parent=None):
        self.storage = storage
        self.cutoff = cutoff
        self.parent = parent
        self.stored: dict[str, int] = {}
        self.recent: set[str] = set()
        self.seconds = 0.0  # what the listing took, once it is done
        self._stopped = False
        self._error: BaseException | None = None
        self._thread: threading.Thread | None = None

    def run(self) -> None:
        with _TR.span("cmd", "gc", stage="list", hist=_H_LIST,
                      parent=self.parent) as sp:
            for obj in self.storage.list_all("chunks/"):
                if self._stopped:
                    break
                if parse_block_key(obj.key) is not None:
                    self.stored[obj.key] = obj.size
                    if obj.mtime > self.cutoff:
                        self.recent.add(obj.key)
        self.seconds = sp.dur

    def start(self) -> None:
        def listed():
            try:
                self.run()
            except BaseException as e:  # raised again by wait()
                self._error = e

        self._thread = threading.Thread(target=listed, name="jfs-gc-list",
                                        daemon=True)
        self._thread.start()

    def wait(self) -> float:
        t0 = time.perf_counter()
        self._thread.join()
        waited = time.perf_counter() - t0
        if self._error is not None:
            raise self._error
        return round(1.0 - min(waited / max(self.seconds, 1e-9), 1.0), 4)

    def stop(self) -> None:
        self._stopped = True
        self._thread.join()


def _gc(args, trace: "_ScanTrace | None", root,
        at_exit: contextlib.ExitStack) -> dict | None:
    """The invocation below its root span; returns the --dedup stats
    (which `run` prints), else None.

    The order of a `--dedup` invocation, and which thread runs what:
    `open` (this thread; the `jfs-pack-prepare` threads start faulting in
    the pack buffers), then the listing of `chunks/` starts on
    `jfs-gc-list` while this thread walks the slices (`live`) and runs
    `dedup_scan` over what they name — index load, read and hash (the
    store's pool threads fetch), backfill, grouping. Only then does it
    join the lister (`list_wait`) and do what needs the store's listing:
    the name diff, its `scanned:` line, the `--delete` sweep, `reconcile`.
    Leaked objects are by definition not live, so the hash stage reads
    nothing the sweep deletes. Without `--dedup` there is no scan to list
    beside: the listing runs on this thread, after `live`."""
    from . import build_store, open_meta

    with _TR.span("cmd", "gc", stage="open", hist=_H_OPEN):
        m, fmt = open_meta(args.meta_url)
        bs = fmt.block_size * 1024
        # the flag's name and the volume's take the same road:
        # tpu/device.py resolves either, and `tpu` without a TPU fails
        # here — before the name diff has listed a single object
        backend = args.hash_backend or fmt.hash_backend
        pipe = None
        if args.dedup:
            from ..utils.malloc import keep_freed_blocks

            # this process is a bulk scan from here on: the allocator
            # recycles block-sized buffers (utils/malloc.py); before the
            # store is built, so before the first GET
            keep_freed_blocks()
            # The scan's pipeline, here and not where the hashing starts:
            # the sizes of its pack buffers are known now, and announcing
            # the stream lets helper threads fault them in while this
            # thread walks the slices (docs/ARCHITECTURE.md "The scan's
            # host memory"). A scan that finds nothing to hash lets go of
            # them unused; whatever happens, nothing is prepared past
            # this invocation.
            pipe = scan_pipeline(backend, bs)
            at_exit.callback(pipe.release)
            pipe.prepare()
        on_device = pipe is not None and pipe.device_backend
        # meta-attached store: dedup-scan reads of PUT-elided blocks
        # resolve through the content-ref plane (ISSUE 5). No indexer: gc
        # backfills digest rows itself through dedup_scan's own pipeline.
        store = build_store(fmt, args, meta=m, with_indexer=False)
    if trace is not None and on_device:
        trace.start_profiler()

    if args.compact:
        from ..vfs.compact import compact_all

        n = compact_all(m, store)
        print(f"compacted {n} chunks")

    # An object can be uploaded before its slice commits to meta (the write
    # pipeline is async), so fresh objects are never "leaked" (reference gc
    # skips recent blocks for the same reason). The cutoff is taken before
    # anything is listed or read: a block uploaded during the scan is recent.
    listing = _StoreListing(store.storage, time.time() - args.age,
                            parent=root.ref())
    if args.dedup:
        at_exit.callback(listing.stop)
        listing.start()

    with _TR.span("cmd", "gc", stage="live", hist=_H_LIVE):
        # live slice -> expected blocks
        slices = m.list_slices()
        live: dict[str, int] = {}
        for ino, slcs in slices.items():
            for s in slcs:
                if s.id == 0 or s.size == 0:
                    continue
                n_blocks = (s.size + bs - 1) // bs
                for i in range(n_blocks):
                    bsize = min(bs, s.size - i * bs)
                    live[block_key(s.id, i, bsize)] = bsize

        # Inline dedup (ISSUE 5): an elided block has no object of its own
        # — its bytes live under the canonical block of its content ref.
        # The name diff must translate through the alias plane: aliased
        # live blocks are not "missing", and a canonical object is not
        # "leaked" while any live alias still references it.
        try:
            from ..chunk.ingest import alias_map

            aliases = alias_map(m)
            protected = set(aliases.values())
        except Exception as e:
            logger.warning("content-ref scan unavailable: %s", e)
            aliases, protected = {}, set()

    if args.dedup:
        stats = dedup_scan(m, store, live, backend, args.dedup_index, bs,
                           threads=args.threads, pipe=pipe)
        with _TR.span("cmd", "gc", stage="list_wait",
                      hist=_H_LIST_WAIT) as sp_wait:
            # the share of the listing the scan hid
            sp_wait.set(hidden=listing.wait())
    else:
        listing.run()
    stored, recent = listing.stored, listing.recent

    leaked = [k for k in stored
              if k not in live and k not in recent and k not in protected]
    missing = [k for k in live
               if k not in stored and aliases.get(k, k) not in stored]
    print(
        f"scanned: {len(stored)} objects, {len(live)} live blocks "
        f"({sum(1 for k in live if k in aliases)} deduped), "
        f"{len(leaked)} leaked, {len(missing)} missing"
    )
    if missing:
        for k in missing[:10]:
            logger.warning("missing block: %s", k)

    if leaked and args.delete:
        # BACKGROUND class on the scheduler's bulk lane (ISSUE 6): a gc
        # sweep sharing a process with a mount must not displace reads
        with store.scheduler.executor(
            "bulk", IOClass.BACKGROUND, width=args.threads
        ) as pool:
            list(pool.map(store.storage.delete, leaked))
        print(f"deleted {len(leaked)} leaked objects")

    if not args.dedup:
        return None
    # offline complement of the inline ingest stage: repair refcounts
    # left by crash windows, register existing content so future
    # writes elide, and (with --delete) collapse duplicate objects
    # already in the store into aliases
    with _TR.span("cmd", "gc", stage="reconcile", hist=_H_RECONCILE):
        stats["content_refs"] = reconcile_content_refs(
            m, store, live, stored, collapse=args.delete, age=args.age
        )
    if root.active:
        root.set(backend=stats["backend"], blocks=stats["blocks"],
                 hashed_now=stats["hashed_now"])
    return stats


def dedup_scan(meta, store, live: dict[str, int], backend: str,
               index_path: str, block_size: int, threads: int = 8,
               pipe=None) -> dict:
    """Content-dedup scan over all live blocks.

    `pipe` is the `HashPipeline` to hash through, where the caller built
    one ahead (`gc` does, to announce the stream before it lists the
    volume); without it one is built here for `backend` and `block_size`.

    Incremental: digests recorded by the write path (meta content index,
    kv.py `B` keys) are trusted as-is; only blocks missing from the index
    are read back and hashed, and their rows are backfilled so the next
    scan is O(new data). Index rows whose slice no longer exists are
    pruned here — the index is advisory and self-healing.

    The missing blocks are read and hashed by the stage `fsck
    --verify-data` shares (cmd/readhash.py): `threads` GETs at once, one
    hash batch fetched ahead of them, digests in input order.
    """
    import resource

    from ..tpu.dedup import dedup_digests
    from ..tpu.jth256 import digest_hex

    t0 = time.perf_counter()
    # 1. load the persistent index; prune rows for dead slices
    with _TR.span("cmd", "gc", stage="index_load",
                  hist=_H_INDEX_LOAD) as sp_index:
        digest_by_key: dict[str, bytes] = {}
        stale: list[tuple[int, int]] = []
        for sid, indx, bsize, digest in meta.scan_block_digests():
            key = block_key(sid, indx, bsize)
            if key in live:
                digest_by_key[key] = digest
            else:
                stale.append((sid, indx))
        if stale:
            meta.delete_block_digests(stale)
        indexed = len(digest_by_key)

    # 2. hash only blocks the write path didn't index; backfill their rows
    missing = [k for k in live if k not in digest_by_key]
    if pipe is None:
        pipe = scan_pipeline(backend, block_size)
    stage = ReadHash(*chunk_blocks(store, live), pipe, threads)

    backfill = []
    faults0 = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
    with _TR.span("cmd", "gc", stage="readhash",
                  hist=_H_READHASH) as sp_readhash:
        for key, digest in stage.digests(missing):
            digest_by_key[key] = digest
            sid, indx, bsize = parse_block_key(key)
            backfill.append((sid, indx, bsize, digest))
        if sp_readhash.active:
            sp_readhash.set(blocks=len(backfill), window=stage.window,
                            ahead=stage.ahead)
    _SCAN_MINOR_FAULTS.inc(
        resource.getrusage(resource.RUSAGE_SELF).ru_minflt - faults0)
    with _TR.span("cmd", "gc", stage="backfill",
                  hist=_H_BACKFILL) as sp_backfill:
        if backfill:
            meta.set_block_digests(backfill)

    # 3. duplicate grouping over the full digest set
    with _TR.span("cmd", "gc", stage="group", hist=_H_GROUP) as sp_group:
        keys = list(digest_by_key)
        digests = [digest_by_key[k] for k in keys]
        dup_mask, first_idx = dedup_digests(digests)
        dup_bytes = sum(live[keys[i]] for i, d in enumerate(dup_mask) if d)
        groups: dict[str, list[str]] = {}
        for i, d in enumerate(dup_mask):
            if d:
                groups.setdefault(keys[first_idx[i]], []).append(keys[i])
    if index_path:
        with _TR.span("cmd", "gc", stage="write_index",
                      hist=_H_WRITE_INDEX):
            with open(index_path, "w") as f:
                json.dump(
                    {keys[i]: digest_hex(digests[i])
                     for i in range(len(keys))},
                    f,
                    indent=1,
                )
    total = time.perf_counter() - t0
    nbytes = sum(live.values())
    from ..object.resilient import resilience_snapshot

    readhash = stage.stage_seconds(sp_readhash.dur)
    return {
        "blocks": len(keys),
        "bytes": nbytes,
        "from_index": indexed,
        "hashed_now": len(backfill),
        "stale_index_rows_removed": len(stale),
        "duplicate_blocks": int(dup_mask.sum()),
        "duplicate_bytes": int(dup_bytes),
        "dedup_groups": len(groups),
        # the backend that RAN (requested name is in device.requested)
        "backend": pipe.config.backend,
        "fetch_window": stage.window,
        "fetch_ahead": stage.ahead,
        # stage breakdown (VERDICT r3 #2: the bottleneck must be explicit);
        # `get`, `get_threads`, `hash` and `readhash` are the shared
        # stage's own (cmd/readhash.py).
        # The stages are their spans' durations, to the microsecond;
        # `seconds` is the scan's own wall, stages and what lies between.
        "seconds": round(total, 3),
        "gibs": round(nbytes / (1 << 30) / total, 3) if total > 0 else 0.0,
        "blocks_per_s": round(len(keys) / total, 1) if total > 0 else 0.0,
        "stage_seconds": {
            "index_load": round(sp_index.dur, 6),
            "get": readhash["get"],
            "get_threads": readhash["get_threads"],
            "hash": readhash["hash"],
            "meta_backfill": round(sp_backfill.dur, 6),
            "dup_group": round(sp_group.dur, 6),
            "readhash": readhash["readhash"],
        },
        # retry/hedge/breaker activity during the scan (the GETs run
        # through object/resilient.py): a scan that paid for fault
        # handling must say so next to its throughput numbers
        "resilience": resilience_snapshot(),
        # where the digests came from (tpu/device.py): platform,
        # device_kind, device counts, mesh axes, whether the plane
        # degraded to single-device jit, Pallas mode, peak device memory,
        # and the first batch's wall time (compilation: set-up, not rate)
        "device": pipe.device_report(),
    }


def reconcile_content_refs(meta, store, live: dict[str, int],
                           stored: dict[str, int],
                           collapse: bool = False,
                           age: float = 3600.0) -> dict:
    """Offline repair + backfill for the content-ref plane (ISSUE 5) —
    the recovery half of the inline ingest dedup contract:

      1. aliases of dead blocks (elide committed, slice never did — the
         crash window between elision and meta commit) are decref'd;
      2. refcounts are pinned to the observed alias count;
      3. dangling aliases (no ref row) self-heal when the block still has
         its own object, and are REPORTED as data loss otherwise;
      4. content already in the store is registered so future writes
         elide against it; with collapse=True duplicate objects are
         rewritten into aliases and deleted (the Venti-style offline
         reclaim the inline stage cannot do retroactively).

    Invariant after this runs: every alias row maps a live block to a
    ref row whose refcount equals its alias count — zero orphaned, zero
    dangling."""
    stats = {"orphaned_aliases_repaired": 0, "refcounts_fixed": 0,
             "dangling_content_refs": 0, "self_healed_aliases": 0,
             "registered": 0, "collapsed": 0, "collapsed_bytes": 0}

    # 1. orphaned aliases: the block is gone but its ref survived. The
    # age cutoff mirrors the leaked-object diff's `recent` guard: a
    # writer elides (alias committed) BEFORE its slice commits to meta,
    # so a fresh alias absent from `live` is an in-flight acked write,
    # not a crash orphan — repairing it would delete data mid-commit.
    cutoff = time.time() - age
    aliases = list(meta.scan_content_aliases())
    orphaned = [
        (sid, indx) for (sid, indx), _d, bsize, ts in aliases
        if block_key(sid, indx, bsize) not in live and ts < cutoff
    ]
    if orphaned:
        for disp, canonical in meta.content_decref(orphaned):
            if disp == "last" and canonical is not None:
                ck = block_key(*canonical)
                if ck not in live:
                    try:
                        store.storage.delete(ck)
                    except Exception:
                        pass
        stats["orphaned_aliases_repaired"] = len(orphaned)
        aliases = list(meta.scan_content_aliases())

    # 2/3. refcount vs alias count; dangling aliases
    ref_rows = {d: (canonical, refs)
                for d, canonical, refs in meta.scan_content_refs()}
    alias_count: dict[bytes, int] = {}
    dangling: list[tuple[int, int]] = []
    for (sid, indx), digest, bsize, _ts in aliases:
        if digest in ref_rows:
            alias_count[digest] = alias_count.get(digest, 0) + 1
        elif block_key(sid, indx, bsize) in stored:
            # the block still has its own object: drop the stray alias
            meta.content_delete_aliases([(sid, indx)])
            stats["self_healed_aliases"] += 1
        else:
            dangling.append((sid, indx))
            logger.error("dangling content ref: block %s has no object "
                         "and no canonical", block_key(sid, indx, bsize))
    stats["dangling_content_refs"] = len(dangling)
    for digest, (canonical, refs) in list(ref_rows.items()):
        observed = alias_count.get(digest, 0)
        if observed != refs:
            meta.content_set_refs(digest, observed)
            stats["refcounts_fixed"] += 1
            if observed == 0:
                ck = block_key(*canonical)
                del ref_rows[digest]  # treated as absent below
                if ck not in live:
                    try:
                        store.storage.delete(ck)
                    except Exception:
                        pass

    # 4. backfill: register live content the inline stage never saw, so
    # future duplicate writes elide against it; collapse rewrites
    # already-duplicated objects into aliases and reclaims their bytes
    aliased = {(sid, indx) for (sid, indx), _d, _b, _ts in
               list(meta.scan_content_aliases())}
    groups: dict[bytes, list[tuple[int, int, int]]] = {}
    for sid, indx, bsize, digest in meta.scan_block_digests():
        key = block_key(sid, indx, bsize)
        if key in live and (sid, indx) not in aliased and key in stored:
            groups.setdefault(digest, []).append((sid, indx, bsize))
    register = []
    collapsible: list[tuple[bytes, int, int, int]] = []
    for digest, members in groups.items():
        start = 0
        if digest not in ref_rows:
            sid, indx, bsize = members[0]
            register.append((digest, sid, indx, bsize))
            start = 1
        else:
            # a canonical whose self-alias row went missing shows up here
            # as an unaliased member: it must NEVER be collapsed (deleting
            # it would orphan every alias of the digest)
            canonical = ref_rows[digest][0]
            members = [m for m in members if m != canonical]
            start = 0
        collapsible.extend((digest, *m) for m in members[start:])
    if register:
        meta.content_register(register)
        stats["registered"] = len(register)
    if collapse and collapsible:
        results = meta.content_incref(
            [(d, sid, indx, bsize) for d, sid, indx, bsize in collapsible]
        )
        for (digest, sid, indx, bsize), got in zip(collapsible, results):
            if got is None:
                continue  # ref vanished mid-flight: leave the object alone
            if got == (sid, indx, bsize):
                continue  # we ARE the canonical: never delete its object
            try:
                store.storage.delete(block_key(sid, indx, bsize))
            except Exception:
                pass
            store.cache.remove(block_key(sid, indx, bsize))
            stats["collapsed"] += 1
            stats["collapsed_bytes"] += bsize
    return stats
