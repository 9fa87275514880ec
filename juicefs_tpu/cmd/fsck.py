"""`fsck`: verify data integrity (reference cmd/fsck.go:75-230).

Lists `chunks/` objects, walks every slice from meta, and checks each
expected block exists with the right size. --verify-data additionally GETs
and decompresses every block; with the TPU hash backend it also streams
blocks through the JTH-256 pipeline and writes a content index, turning
fsck into the full-volume hash-verify workload from BASELINE.md: the
operator's scrub. Every stored block is read back and hashed through the
read-and-hash stage `gc --dedup` uses (cmd/readhash.py), and held to the
digest the content index recorded for it.

One invocation is one trace (metric/trace.py): the root span `cmd.fsck`,
its stages `open`, `list`, `index_load`, `verify`, `report` below it, and
below `verify` the fetch stage's and the hash pipeline's own spans. Every
stage feeds `juicefs_tpu_stage_seconds{layer="cmd",op="fsck"}`.

A block the scrub could not read is reported by why: `corrupt` where the
object's envelope does not open (object/encrypt.py: the object as stored is
wrong — restore the block), `unreadable` where the object is gone or the
store gave up (wait, and scrub again).
"""

from __future__ import annotations

import contextlib
import json
import time

from ..chunk.cached_store import block_key
from ..metric import global_registry
from ..metric.trace import global_tracer, stage_hist
from ..tpu.device import HASH_BACKENDS
from ..utils import get_logger
from .readhash import ReadHash, chunk_blocks, scan_pipeline

logger = get_logger("cmd.fsck")

_TR = global_tracer()
_H_FSCK = stage_hist("cmd", "fsck")
_H_OPEN = stage_hist("cmd", "fsck", "open")
_H_LIST = stage_hist("cmd", "fsck", "list")
_H_INDEX_LOAD = stage_hist("cmd", "fsck", "index_load")
_H_VERIFY = stage_hist("cmd", "fsck", "verify")
_H_REPORT = stage_hist("cmd", "fsck", "report")
_BLOCKS = global_registry().counter(
    "juicefs_fsck_blocks",
    "Blocks fsck expected, by what it found: read back and hashed without "
    "disagreeing with the content index, digest mismatch, no object, "
    "object unreadable, envelope that does not open (corrupt)",
    ("result",),
)


def add_parser(sub):
    p = sub.add_parser("fsck", help="check volume integrity")
    p.add_argument("meta_url")
    p.add_argument("--verify-data", action="store_true",
                   help="GET + decompress every block")
    p.add_argument("--hash-index", default="",
                   help="also hash every block; write content index JSON here")
    p.add_argument("--hash-backend", default=None,
                   choices=HASH_BACKENDS,
                   help="hash backend (default: the volume's; `tpu` fails "
                        "unless JAX finds a TPU)")
    p.add_argument("--threads", type=int, default=10)
    p.set_defaults(func=run)


def run(args) -> int:
    # what the invocation started and has to end whatever happens
    with _TR.span("cmd", "fsck", hist=_H_FSCK) as root, \
            contextlib.ExitStack() as at_exit:
        rc, stats = _fsck(args, root, at_exit)
    if stats is not None:
        print(json.dumps(stats))
    return rc


def _fsck(args, root, at_exit: contextlib.ExitStack) -> tuple[int, dict | None]:
    """The invocation below its root span; returns the exit code and the
    stats of the verify stage (which `run` prints), None without one."""
    from . import build_store, open_meta

    hashing = bool(args.verify_data or args.hash_index)
    with _TR.span("cmd", "fsck", stage="open", hist=_H_OPEN):
        m, fmt = open_meta(args.meta_url)
        bs = fmt.block_size * 1024
        pipe = None
        if hashing:
            from ..utils.malloc import keep_freed_blocks

            # a bulk scan from here on, as `gc --dedup` is (utils/malloc.py);
            # before the store is built, so before the first GET
            keep_freed_blocks()
            # the scrub's pipeline, here and not where the hashing starts:
            # announcing the stream lets helper threads fault its pack
            # buffers in while this thread lists the volume; `tpu` without
            # a TPU fails here, before a single object is listed
            pipe = scan_pipeline(args.hash_backend or fmt.hash_backend, bs)
            at_exit.callback(pipe.release)
            pipe.prepare()
        # meta-attached store: reads of PUT-elided blocks resolve through
        # the content-ref plane (ISSUE 5) — without it every alias is
        # "unreadable". No indexer: fsck never uploads, and hashes through
        # its own pipeline.
        store = build_store(fmt, args, meta=m, with_indexer=False)

    with _TR.span("cmd", "fsck", stage="list", hist=_H_LIST) as sp_list:
        stored = {o.key: o.size for o in store.storage.list_all("chunks/")}
        slices = m.list_slices()

        # inline dedup (ISSUE 5): an elided block's bytes live under its
        # canonical — existence checks must translate through the alias
        # plane
        try:
            from ..chunk.ingest import alias_map

            aliases = alias_map(m)
        except Exception:
            aliases = {}

        broken: list[str] = []
        checked = blocks = 0
        expected: dict[str, int] = {}
        missing: set[str] = set()
        for ino, slcs in slices.items():
            file_broken = False
            for s in slcs:
                if s.id == 0 or s.size == 0:
                    continue
                for i in range((s.size + bs - 1) // bs):
                    bsize = min(bs, s.size - i * bs)
                    key = block_key(s.id, i, bsize)
                    expected[key] = bsize
                    blocks += 1
                    if key not in stored and aliases.get(key, key) not in stored:
                        logger.error("ino %d: missing block %s", ino, key)
                        file_broken = True
                        missing.add(key)
                    elif key not in stored:
                        pass  # deduped: bytes verified under the canonical key
                    elif not fmt.compression and store.compressor.name == "" and stored[key] != bsize:
                        logger.error(
                            "ino %d: block %s size %d != %d", ino, key, stored[key], bsize
                        )
                        file_broken = True
            checked += 1
            if file_broken:
                broken.append(str(ino))
        _BLOCKS.labels("missing").inc(len(missing))

    stats = None
    if hashing:
        from ..tpu.jth256 import digest_hex

        t0 = time.perf_counter()
        # Digests recorded by the write path (meta content index): a block
        # whose recomputed digest disagrees is silent corruption the
        # reference's existence/size fsck cannot see.
        with _TR.span("cmd", "fsck", stage="index_load",
                      hist=_H_INDEX_LOAD) as sp_index:
            recorded = {
                block_key(sid, indx, bsize): digest
                for sid, indx, bsize, digest in m.scan_block_digests()
            }

        # reported missing above: nothing to read
        readable = [key for key in expected
                    if key in stored or key in aliases]
        stage = ReadHash(*chunk_blocks(store, expected), pipe, args.threads,
                         outlive_open=True)
        bitrot = 0
        index = {}
        with _TR.span("cmd", "fsck", stage="verify",
                      hist=_H_VERIFY) as sp_verify:
            for k, d in stage.digests(readable):
                index[k] = digest_hex(d)
                want = recorded.get(k)
                if want is not None and want != d:
                    logger.error("block %s content digest mismatch (bitrot?)", k)
                    broken.append(k)
                    bitrot += 1
            unreadable = [key for key in readable if key not in index]
            corrupt = 0
            for key in unreadable:
                why = stage.failed.get(key)
                if key in stage.corrupt:
                    logger.error("block %s corrupt: %s", key, stage.corrupt[key])
                    corrupt += 1
                elif why is not None or stage.stopped is None:
                    logger.error("block %s unreadable: %s", key,
                                 why or "not read")
                broken.append(key)
            if stage.stopped is not None:
                # the fetch stage gave up on the store: what it had read is
                # verified above, every block it did not get to is counted
                # unreadable, and the scrub still reports
                logger.error("%s: %d of %d blocks were not read",
                             stage.stopped, len(unreadable), len(readable))
            if sp_verify.active:
                sp_verify.set(blocks=len(index), window=stage.window,
                              ahead=stage.ahead)
        _BLOCKS.labels("verified").inc(len(index) - bitrot)
        _BLOCKS.labels("mismatch").inc(bitrot)
        _BLOCKS.labels("unreadable").inc(len(unreadable) - corrupt)
        _BLOCKS.labels("corrupt").inc(corrupt)
        total = time.perf_counter() - t0

    with _TR.span("cmd", "fsck", stage="report", hist=_H_REPORT):
        if hashing:
            if args.hash_index:
                with open(args.hash_index, "w") as f:
                    json.dump(index, f, indent=1)
            print(
                f"verified {len(index)} blocks ({pipe.config.backend}); "
                f"{len(recorded)} indexed, {bitrot} digest mismatches"
            )
            # where the digests came from (tpu/device.py) — the line a
            # reader needs to tell a chip run from a host run
            device = pipe.device_report()
            print("device: " + json.dumps(device))
            stats = {
                "blocks": len(expected),
                "verified": len(index),
                # every block is hashed on every scrub, none answered
                # from the index: `gc`'s name for the same count
                "hashed_now": len(index),
                "indexed": len(recorded),
                "mismatches": bitrot,
                "broken": len(broken),
                # blocks whose envelope does not open: in `broken` too
                "corrupt": corrupt,
                "bytes": sum(expected[k] for k in index),
                # index load and verify, and what lies between
                "seconds": round(total, 3),
                "stage_seconds": {
                    "list": round(sp_list.dur, 6),
                    "index_load": round(sp_index.dur, 6),
                    **stage.stage_seconds(sp_verify.dur),
                },
                "fetch_window": stage.window,
                "fetch_ahead": stage.ahead,
                # the backend that RAN (requested name: device.requested)
                "backend": pipe.config.backend,
                "device": device,
            }
            if root.active:
                root.set(backend=stats["backend"], blocks=stats["blocks"],
                         verified=stats["verified"],
                         mismatches=stats["mismatches"])
        print(f"checked {checked} files / {blocks} blocks; {len(broken)} broken")
    return (1 if broken else 0), stats
