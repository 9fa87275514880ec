"""CLI driver (reference: cmd/, SURVEY.md §2.1).

`python -m juicefs_tpu.cmd <command>` mirrors the reference's 27-subcommand
urfave/cli app (cmd/main.go:61-89). Commands register in COMMANDS; each
module exposes `add_parser(sub)` and a `run(args)`.

Shared plumbing here: open the meta client, load the volume Format, build
the object store with its wrappers (prefix/shard/encrypt — reference
cmd/mount.go:387 NewReloadableStorage), and assemble the chunk store/VFS.
"""

from __future__ import annotations

import argparse
import os
import sys

from ..chunk import CachedStore, ChunkConfig
from ..meta import new_client
from ..meta.types import Format
from ..metric.trace import global_tracer, stage_hist
from ..object import create_storage, sharded, with_prefix
from ..utils import get_logger

logger = get_logger("cmd")
_TR = global_tracer()
# building the parser of every command and parsing the arguments, one an
# invocation of `main`
_H_PARSE = stage_hist("cmd", "main", "parse")


def open_meta(addr: str, **kw):
    m = new_client(addr, **kw)
    fmt = m.load()
    return m, fmt


def storage_for(fmt: Format):
    """Build the blob store stack from a volume Format (reference
    cmd/mount.go:387 + pkg/object wrappers)."""
    bucket = fmt.bucket or ""
    scheme = fmt.storage or "file"
    if fmt.shards > 1:
        stores = [
            create_storage(f"{scheme}://{bucket}{i:02d}") for i in range(fmt.shards)
        ]
        store = sharded(stores)
    else:
        uri = f"{scheme}://{bucket}" if "://" not in bucket else bucket
        store = create_storage(uri)
    # Keep volume objects namespaced like the reference ({name}/ prefix)
    if scheme not in ("mem",):
        store = with_prefix(store, fmt.name + "/")
    if fmt.encrypt_key:
        from ..object import new_encrypted

        # encrypt_algo selects the body cipher (aes256gcm-rsa default,
        # aes256ctr-*); the key side (RSA-OAEP vs ECIES) follows the PEM
        store = new_encrypted(store, fmt.encrypt_key.encode(),
                              algo=fmt.encrypt_algo or "aes256gcm")
    return store


def chunk_conf(fmt: Format, args=None) -> ChunkConfig:
    cache_dirs = ("memory",)
    writeback = False
    if args is not None:
        if getattr(args, "cache_dir", None):
            cache_dirs = tuple(str(args.cache_dir).split(":"))
        writeback = bool(getattr(args, "writeback", False))
    conf = ChunkConfig(
        block_size=fmt.block_size * 1024,
        compress=fmt.compression,
        cache_dirs=cache_dirs,
        writeback=writeback,
    )
    if getattr(args, "cache_size", None):
        conf.cache_size = int(args.cache_size) << 20
    # NOTE (ISSUE 6 satellite): `--threads` used to silently raise
    # conf.max_download here, mutating the process-wide download pool.
    # Command concurrency now routes through the unified scheduler's
    # BACKGROUND class instead — build_store widens the download/bulk
    # lanes to the command's width without touching foreground config.
    # bandwidth shaping (qos/limiter.py): CLI limits are Mbps, the
    # config carries bytes/s
    if getattr(args, "upload_limit", None):
        conf.upload_limit = float(args.upload_limit) * 1e6 / 8
    if getattr(args, "download_limit", None):
        conf.download_limit = float(args.download_limit) * 1e6 / 8
    # object-plane resilience knobs (object/resilient.py)
    if getattr(args, "op_deadline", None):
        conf.op_deadline = float(args.op_deadline)
    if getattr(args, "attempt_timeout", None):
        conf.attempt_timeout = float(args.attempt_timeout)
    if getattr(args, "no_hedge", False):
        conf.hedge = False
    # batched compression plane + elision bypass (ISSUE 8)
    if getattr(args, "compress_backend", None):
        conf.compress_backend = str(args.compress_backend)
    if getattr(args, "compress_lanes", None):
        conf.compress_lanes = int(args.compress_lanes)
    if getattr(args, "no_dedup_bypass", False):
        conf.dedup_bypass = False
    return conf


def build_store(fmt: Format, args=None, meta=None,
                with_indexer: bool = True) -> CachedStore:
    """Assemble the chunk store; with `meta` and a volume hash_backend,
    every uploaded block is fingerprinted into the meta content index
    (VERDICT r2 #3: the write-path hashing seam, role-match to the
    reference upload hook pkg/chunk/cached_store.go:371-413).

    Any meta-attached store also gets the content-ref plane (ISSUE 5):
    reads resolve elided blocks through aliases and deletes decref —
    required for correctness on any volume another --inline-dedup client
    may have written to. The ingest elision stage itself is opt-in via
    the mount flag. Read-only admin commands (fsck/gc/warmup) pass
    with_indexer=False: they need alias resolution but never upload, so
    spinning up the fingerprint worker (and possibly an accelerator
    backend) for them would be pure startup cost.

    The volume's hash_backend name goes to the pipelines unmapped; they
    resolve it through tpu/device.py, so a `tpu` volume opened for writing
    on a host without a TPU fails here instead of hashing elsewhere."""
    conf = chunk_conf(fmt, args)
    store = CachedStore(storage_for(fmt), conf)
    # bulk commands (gc/warmup --threads) run at BACKGROUND class; widen
    # the shared lanes so the command's fetch window can actually go that
    # deep — foreground config (max_download) is left untouched, and the
    # scheduler's class priority keeps any concurrent foreground traffic
    # ahead of the widened background stream (ISSUE 6 satellite)
    threads = int(getattr(args, "threads", 0) or 0)
    if threads > 0:
        store.scheduler.widen("download", threads)
        store.scheduler.widen("bulk", threads)
    if meta is not None:
        from ..chunk.ingest import ContentRefs, IngestPipeline

        store.content_refs = ContentRefs(meta)
        if fmt.hash_backend and with_indexer:
            from ..chunk.indexer import BlockIndexer

            store.indexer = BlockIndexer(
                meta=meta,
                backend=fmt.hash_backend,
                block_size=conf.block_size,
            )
            conf.fingerprint = store.indexer.submit
        if getattr(args, "inline_dedup", False):
            flush_ms = getattr(args, "ingest_flush_ms", None)
            if flush_ms is None:
                flush_ms = 5.0  # explicit 0 means "flush immediately"
            store.ingest = IngestPipeline(
                store,
                store.content_refs,
                backend=fmt.hash_backend,
                flush_timeout=max(0.0, float(flush_ms)) / 1e3,
                bypass=conf.dedup_bypass,
            )
    return store


def main(argv: list[str] | None = None) -> int:
    from . import (
        bench,
        config,
        dump,
        format as format_cmd,
        fsck,
        gateway,
        gc,
        info,
        meta_server,
        mount,
        objbench,
        quota,
        stats,
        sync,
        warmup,
    )

    with _TR.span("cmd", "main", stage="parse", hist=_H_PARSE):
        parser = argparse.ArgumentParser(
            prog="juicefs-tpu",
            description="TPU-native JuiceFS-capability distributed file system",
        )
        sub = parser.add_subparsers(dest="command", required=True)
        for mod in (
            format_cmd, mount, bench, objbench, gc, fsck, sync, dump, warmup,
            info, gateway, stats, quota, meta_server, config,
        ):
            mod.add_parser(sub)
        args = parser.parse_args(argv)
    try:
        return args.func(args) or 0
    except KeyboardInterrupt:
        return 130
    except BrokenPipeError:
        return 0  # output piped into head/less that exited early
    except Exception as e:
        logger.error("%s: %s", args.command, e)
        return 1


def fstab_shim(argv: list[str]) -> list[str]:
    """Translate mount(8) helper arguments into `mount` command args
    (reference cmd/main.go:107-121: /sbin/mount.juicefs shim).

    mount(8) invokes: mount.juicefs SPEC DIR [-sfnv] [-o opt1,opt2...]
    """
    spec, mountpoint = argv[0], argv[1]
    out = ["mount", spec, mountpoint]
    it = iter(argv[2:])
    for a in it:
        if a == "-o":
            for opt in next(it, "").split(","):
                if not opt or opt in ("rw", "defaults", "auto", "noauto",
                                      "user", "nouser", "exec", "noexec",
                                      "suid", "nosuid", "dev", "nodev",
                                      "_netdev"):
                    continue
                if opt == "ro":
                    out.append("--readonly")
                elif opt == "background":
                    out.append("-d")
                elif "=" in opt:
                    k, v = opt.split("=", 1)
                    out += [f"--{k.replace('_', '-')}", v]
                else:
                    out.append(f"--{opt.replace('_', '-')}")
        # -s/-f/-n/-v from mount(8) have no meaning here: ignore
    if "-d" not in out:
        out.append("-d")  # fstab mounts must daemonize
    return out


def cli_entry() -> None:
    if os.path.basename(sys.argv[0]).startswith("mount.") and len(sys.argv) >= 3:
        sys.exit(main(fstab_shim(sys.argv[1:])))
    sys.exit(main())
