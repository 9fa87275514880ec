"""`sync`: bulk object copy between stores (reference pkg/sync + cmd/sync.go).

Producer/consumer layout mirroring the reference: both sides stream sorted
listings, an ordered-merge diff decides what to copy/delete (sync.go:777),
a worker pool moves the objects (worker :616), include/exclude rules filter
keys (:881-1076), and --check-new/--check-all content-compare (doCheckSum
:232 — here a streaming ranged compare, constant memory).

Large objects are partitioned into ranged GET + multipart-upload parts
(reference copyData sync.go:440-587) so a 5 GiB object moves through a
fixed-size buffer instead of resident memory.

Cluster mode (reference pkg/sync/cluster.go:132,237): `--manager-listen`
turns this process into an HTTP task server feeding the ordered diff to
any number of `--worker --manager host:port` processes (launched by the
operator or an external scheduler; the reference bootstraps them via ssh),
which pull task batches, copy with their own store clients, and push
stats back.

With `--hash-backend` the content compare of a local pass is a digest
compare on the hash pipeline: the 4 MiB ranges of both objects of every
pair go, source then destination, through the scans' read-and-hash stage
(cmd/readhash.py) as one stream, the GETs `--threads` at once on the pass's
own `bulk` executor and a hash batch ahead, and a pair is equal iff every
range's JTH-256 digests are (the diff has sent a size difference to `copy`
before any compare). Both sides are read on every pass: nothing is
answered from an index or a cache. Without the flag the compare is the
ranged byte compare on the host that it was; cluster mode keeps that one.

One local pass is one trace (metric/trace.py): the root span `cmd.sync`,
its stages `open`, `list` (both listings and the diff; with a hash backend),
`copy`, `check`, `report` below it, each feeding
`juicefs_tpu_stage_seconds{layer="cmd",op="sync"}`. Without a hash backend
the diff stays lazy and drives the worker pool: listing, copies, deletes and
byte compares overlap inside `copy`.
"""

from __future__ import annotations

import contextlib
import fnmatch
import json
import threading
import time

from ..metric import global_registry
from ..metric.trace import global_tracer, stage_hist
from ..object import create_storage
from ..object.metered import metered
from ..object.resilient import RetryPolicy, resilient
from ..qos import IOClass, global_scheduler
from ..tpu.device import HASH_BACKENDS
from ..utils import get_logger
from .readhash import ReadHash, scan_pipeline

logger = get_logger("cmd.sync")

_TR = global_tracer()
_H_SYNC = stage_hist("cmd", "sync")
_H_OPEN = stage_hist("cmd", "sync", "open")
_H_LIST = stage_hist("cmd", "sync", "list")
_H_COPY = stage_hist("cmd", "sync", "copy")
_H_CHECK = stage_hist("cmd", "sync", "check")
_H_REPORT = stage_hist("cmd", "sync", "report")
_OBJECTS = global_registry().counter(
    "juicefs_sync_objects",
    "Objects of sync passes by what the pass did with them: content "
    "compared with their counterpart, found to differ from it (each also "
    "compared or copied), copied, given up on after an error",
    ("result",),
)
_COUNTED = {r: _OBJECTS.labels(r)
            for r in ("checked", "mismatch", "copied", "skipped")}
_CHECKED_BYTES = global_registry().counter(
    "juicefs_sync_checked_bytes",
    "Source bytes of the object pairs sync passes content-compared",
)


def _open_store(uri: str):
    """Sync endpoints go through the resilience wrapper (ISSUE 3: no
    bare-store escapes): classified retries per object op, per-backend
    breaker; beneath it the metering one, as on every stack
    (object/metered.py), so each attempt is timed at the store.  Hedging
    stays off — bulk copy already runs `--threads` wide, and doubling
    GETs there is bandwidth, not tail latency.  The
    wall deadline is effectively unbounded: a multi-GiB part on a slow
    link may LEGITIMATELY take many minutes, and the wrapper cannot
    know object sizes — the pre-existing contract (ops run to
    completion, failed objects retry on later passes) stays intact."""
    return resilient(metered(create_storage(uri)),
                     policy=RetryPolicy(deadline=7 * 86400.0,
                                        max_attempts=5),
                     hedge=False)

CMP_CHUNK = 8 << 20  # streaming-compare window
HASH_RANGE = 4 << 20  # the digest compare's range: one block of the pipeline


def add_parser(sub):
    p = sub.add_parser("sync", help="sync objects between two stores")
    p.add_argument("src")
    p.add_argument("dst")
    p.add_argument("--start", default="", help="first key (inclusive)")
    p.add_argument("--end", default="", help="last key (exclusive)")
    p.add_argument("--threads", type=int, default=10)
    p.add_argument("--update", action="store_true",
                   help="overwrite when src is newer (default: size/name diff)")
    p.add_argument("--force-update", action="store_true")
    p.add_argument("--check-new", action="store_true",
                   help="content-compare objects copied this run")
    p.add_argument("--check-all", action="store_true",
                   help="content-compare every object pair")
    p.add_argument("--hash-backend", default=None, choices=HASH_BACKENDS,
                   help="compare contents by JTH-256 digest on this hash "
                        "backend instead of byte by byte on the host (`tpu` "
                        "fails unless JAX finds a TPU; not in cluster mode)")
    p.add_argument("--hash-index", default="",
                   help="with --hash-backend: write every compared object's "
                        "range digests, both sides, as JSON here")
    p.add_argument("--delete-dst", action="store_true")
    p.add_argument("--delete-src", action="store_true")
    p.add_argument("--include", action="append", default=[])
    p.add_argument("--exclude", action="append", default=[])
    p.add_argument("--dry", action="store_true")
    p.add_argument("--big-threshold", type=int, default=32,
                   help="MiB; objects at least this big copy via ranged "
                        "multipart parts (reference sync.go:440)")
    p.add_argument("--part-size", type=int, default=8, help="MiB per part")
    p.add_argument("--bwlimit", type=int, default=0,
                   help="aggregate copy bandwidth cap in Mbps (0=unlimited; "
                        "reference sync.go bwlimit token bucket)")
    # cluster mode (reference cluster.go)
    p.add_argument("--manager-listen", default="",
                   help="host:port — serve the diff as an HTTP task queue "
                        "instead of copying locally")
    p.add_argument("--worker", action="store_true",
                   help="pull task batches from --manager and execute them")
    p.add_argument("--manager", default="", help="manager host:port")
    p.add_argument("--worker-hosts", default="",
                   help="comma-separated hosts: the manager BOOTSTRAPS one "
                        "worker per host via --worker-launch (reference "
                        "cluster.go:237 ssh bootstrap)")
    p.add_argument("--worker-launch", default="",
                   help="launch template with {host} and {cmd} placeholders "
                        "run through the shell, e.g. 'ssh {host} {cmd}'; "
                        "default: run {cmd} as a local subprocess")
    p.set_defaults(func=run)


def _match(key: str, includes: list[str], excludes: list[str]) -> bool:
    """Rule filter (reference sync.go:918 matchKey; first match wins)."""
    for pat in excludes:
        if fnmatch.fnmatch(key, pat):
            return False
    if includes:
        return any(fnmatch.fnmatch(key, pat) for pat in includes)
    return True


def _diff(src_iter, dst_iter, args):
    """Ordered-merge diff of two sorted listings (reference produce :777).

    Yields ("copy" | "del-dst" | "del-src" | "check", src_obj, dst_obj).
    """
    def nxt(it):
        return next(it, None)

    s, d = nxt(src_iter), nxt(dst_iter)
    while s is not None or d is not None:
        if d is None or (s is not None and s.key < d.key):
            yield "copy", s, None
            s = nxt(src_iter)
        elif s is None or d.key < s.key:
            if args.delete_dst:
                yield "del-dst", None, d
            d = nxt(dst_iter)
        else:
            if args.force_update:
                yield "copy", s, d
            elif s.size != d.size:
                yield "copy", s, d
            elif args.update and s.mtime > d.mtime:
                yield "copy", s, d
            elif args.check_all:
                yield "check", s, d
            elif args.delete_src:
                yield "del-src", s, None
            s, d = nxt(src_iter), nxt(dst_iter)


def _content_equal(src, dst, key: str, size: int) -> bool:
    """Streaming ranged compare: constant memory for any object size
    (replaces whole-object loads; reference doCheckSum streams too)."""
    if size <= 0:
        return bytes(src.get(key)) == bytes(dst.get(key))
    off = 0
    while off < size:
        n = min(CMP_CHUNK, size - off)
        if bytes(src.get(key, off, n)) != bytes(dst.get(key, off, n)):
            return False
        off += n
    return True


class _TokenBucket:
    """Aggregate bandwidth cap shared by all copy workers
    (reference pkg/sync bwlimit via juju/ratelimit)."""

    def __init__(self, mbps: int):
        self.rate = mbps * 125_000  # bytes/s
        self._avail = float(self.rate)  # 1s burst
        self._last = time.monotonic()
        self._mu = threading.Lock()

    def take(self, nbytes: int) -> None:
        while nbytes > 0:
            with self._mu:
                now = time.monotonic()
                self._avail = min(
                    float(self.rate), self._avail + (now - self._last) * self.rate
                )
                self._last = now
                grant = min(nbytes, self._avail)
                self._avail -= grant
                nbytes -= int(grant)
                if nbytes <= 0:
                    return
                wait = nbytes / self.rate
            time.sleep(min(wait, 0.5))


def _copy_object(src, dst, obj, args, stats) -> None:
    """Move one object; big objects go part-by-part through a fixed buffer
    (reference copyData sync.go:440-587 single-PUT vs UploadPart split)."""
    threshold = args.big_threshold << 20
    part_size = max(1 << 20, args.part_size << 20)
    up = None
    if obj.size >= threshold:
        try:
            up = dst.create_multipart_upload(obj.key)
        except Exception:
            up = None
    if up is None:
        data = bytes(src.get(obj.key))
        dst.put(obj.key, data)
        stats.add("copied_bytes", len(data))
        return
    part_size = max(part_size, up.min_part_size)
    n_parts = (obj.size + part_size - 1) // part_size
    if n_parts > up.max_count:  # few huge parts beat failing outright
        part_size = (obj.size + up.max_count - 1) // up.max_count
        n_parts = (obj.size + part_size - 1) // part_size
    parts = []
    try:
        for i in range(n_parts):
            off = i * part_size
            n = min(part_size, obj.size - off)
            data = bytes(src.get(obj.key, off, n))
            parts.append(dst.upload_part(obj.key, up.upload_id, i + 1, data))
            stats.add("copied_bytes", n)
        dst.complete_upload(obj.key, up.upload_id, parts)
    except BaseException:
        try:
            dst.abort_upload(obj.key, up.upload_id)
        except Exception:
            pass
        raise


def _make_executor(src, dst, args, stats, verify_later: list | None = None):
    """The per-task state machine shared by local and worker modes. With
    `verify_later` the `--check-new` compare of a copied object, and the
    `--delete-src` behind it, are the caller's: the object is appended
    there once its copy has completed (the digest compare's pair stage
    takes them when the copies are done)."""
    bucket = _TokenBucket(args.bwlimit) if getattr(args, "bwlimit", 0) else None

    def do(task):
        op, s, d = task
        try:
            if op == "copy":
                if args.dry:
                    stats.add("copied")
                else:
                    if bucket is not None:
                        bucket.take(s.size)
                    _copy_object(src, dst, s, args, stats)
                    stats.add("copied")
                    if args.check_new and verify_later is not None:
                        verify_later.append(s)
                    else:
                        if args.check_new:
                            _CHECKED_BYTES.inc(s.size)
                            if not _content_equal(src, dst, s.key, s.size):
                                stats.add("mismatch")
                                logger.error(
                                    "verify failed after copy: %s", s.key)
                        if args.delete_src:
                            src.delete(s.key)
                            stats.add("deleted")
            elif op == "del-dst":
                if not args.dry:
                    dst.delete(d.key)
                stats.add("deleted")
            elif op == "del-src":
                if not args.dry:
                    src.delete(s.key)
                stats.add("deleted")
            elif op == "check":
                stats.add("checked")
                _CHECKED_BYTES.inc(s.size)
                if not _content_equal(src, dst, s.key, s.size):
                    stats.add("mismatch")
                    logger.error("content mismatch: %s", s.key)
            # counted only on full execution: a BaseException (interrupt)
            # skips this, so the manager sees the task as unaccounted
            stats.add("tasks_done")
        except Exception as e:
            logger.error("%s %s: %s", op, (s or d).key, e)
            stats.add("skipped")
            stats.add("tasks_done")

    return do


class _Stats(dict):
    """Counter dict updated concurrently by pool workers; the bare
    `d[k] += 1` read-modify-write loses updates under threads, and a lost
    tasks_done makes the cluster manager report a spurious partial sync."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.lock = threading.Lock()

    def add(self, key: str, n: int = 1) -> None:
        with self.lock:
            self[key] = self.get(key, 0) + n
        if key in _COUNTED:
            _COUNTED[key].inc(n)


def _new_stats() -> _Stats:
    # tasks_done counts tasks that ran to completion (including skips):
    # the manager's completion check compares it against dispatched count
    return _Stats({"copied": 0, "copied_bytes": 0, "deleted": 0, "checked": 0,
                   "mismatch": 0, "skipped": 0, "tasks_done": 0})


def _offsets(size: int) -> range:
    """Where the digest compare's ranges of an object start; an empty
    object has one, empty, range."""
    return range(0, max(size, 1), HASH_RANGE)


def _ranges(key: str, size: int):
    """The digest compare's items of one pair: each range of the source,
    then the same range of the destination."""
    for off in _offsets(size):
        n = min(HASH_RANGE, size - off)  # 0: an empty object, read whole
        yield ("src", key, off, n)
        yield ("dst", key, off, n)


def _check_pairs(src, pairs, copied, stage, args, stats) -> dict:
    """The pair stage of a pass with a hash backend: `pairs` (the source
    objects of the diff's `check` tasks) and `copied` (what `--check-new`
    has to verify), one stream through `stage`, the read-and-hash stage
    over `_ranges` items. Counts each pair's verdict into `stats` and
    returns key -> both sides' range digests."""
    objs = [*pairs, *copied]
    index: dict[str, dict[str, list[bytes]]] = {}
    for (side, key, _, _), digest in stage.digests(
            item for o in objs for item in _ranges(o.key, o.size)):
        index.setdefault(key, {"src": [], "dst": []})[side].append(digest)
    if stage.stopped is not None:
        # the fetch stage gave up on a store: what it had read is compared
        # below, every pair it did not get to is counted skipped
        logger.error("%s: the compare stopped early", stage.stopped)
    for i, o in enumerate(objs):
        new = i >= len(pairs)  # copied by this pass: no `check` task
        if not new:
            stats.add("checked")
        got = index.get(o.key, {"src": [], "dst": []})
        want = len(_offsets(o.size))
        if len(got["src"]) != want or len(got["dst"]) != want:
            why = next((stage.failed[item] for item in _ranges(o.key, o.size)
                        if item in stage.failed), "not read")
            logger.error("check %s: %s", o.key, why)
            stats.add("skipped")
        else:
            stats.add("checked_bytes", o.size)
            _CHECKED_BYTES.inc(o.size)
            if got["src"] != got["dst"]:
                stats.add("mismatch")
                logger.error("verify failed after copy: %s" if new
                             else "content mismatch: %s", o.key)
            elif new and args.delete_src:
                try:
                    src.delete(o.key)
                    stats.add("deleted")
                except Exception as e:
                    logger.error("del-src %s: %s", o.key, e)
                    stats.add("skipped")
        if not new:
            stats.add("tasks_done")
    return index


def run(args) -> int:
    if args.hash_backend and (args.worker or args.manager_listen):
        logger.error("--hash-backend is for a local pass: cluster mode "
                     "(--worker, --manager-listen) compares byte by byte")
        return 2
    if args.worker:
        return run_worker(args)
    # what the pass started and has to end whatever happens
    with _TR.span("cmd", "sync", hist=_H_SYNC) as root, \
            contextlib.ExitStack() as at_exit:
        return _sync(args, root, at_exit)


def _sync(args, root, at_exit: contextlib.ExitStack) -> int:
    """The pass below its root span."""
    with _TR.span("cmd", "sync", stage="open", hist=_H_OPEN):
        pipe = None
        if args.hash_backend and (args.check_all or args.check_new):
            from ..utils.malloc import keep_freed_blocks

            # a bulk scan of two stores from here on, as `gc --dedup` and
            # `fsck --verify-data` are of one (utils/malloc.py); before a
            # store is opened, so before the first GET
            keep_freed_blocks()
            # the compare's pipeline, here and not where the hashing
            # starts: announcing the stream lets helper threads fault its
            # pack buffers in while this thread lists both stores; `tpu`
            # without a TPU fails here, before a single key is listed
            pipe = scan_pipeline(args.hash_backend, HASH_RANGE)
            at_exit.callback(pipe.release)
            pipe.prepare()
        src = _open_store(args.src)
        dst = _open_store(args.dst)
        dst.create()

    def filtered(store):
        for obj in store.list_all("", args.start):
            if args.end and obj.key >= args.end:
                break
            if obj.is_dir:
                continue  # folder markers are not copyable objects
            if _match(obj.key, args.include, args.exclude):
                yield obj

    tasks = _diff(filtered(src), filtered(dst), args)
    if args.manager_listen:
        return run_manager(args, tasks)

    stats = _new_stats()
    index = None
    t0 = time.perf_counter()
    # BACKGROUND class (ISSUE 6): bulk replication yields to any
    # foreground traffic sharing the process and its bandwidth budget
    with global_scheduler().executor(
        "bulk", IOClass.BACKGROUND, width=args.threads
    ) as pool:
        if pipe is None:
            do = _make_executor(src, dst, args, stats)
            with _TR.span("cmd", "sync", stage="copy", hist=_H_COPY):
                list(pool.map(do, tasks))
        else:
            index = _hashed_pass(src, dst, tasks, pipe, pool, args, stats)
    stats["seconds"] = round(time.perf_counter() - t0, 3)
    with _TR.span("cmd", "sync", stage="report", hist=_H_REPORT):
        if index is not None and args.hash_index:
            from ..tpu.jth256 import digest_hex

            with open(args.hash_index, "w") as f:
                json.dump({key: {side: [digest_hex(d) for d in digests]
                                 for side, digests in sides.items()}
                           for key, sides in index.items()}, f, indent=1)
        if root.active:
            root.set(backend=stats.get("backend", ""),
                     checked=stats["checked"], mismatch=stats["mismatch"])
        print(json.dumps(stats))
    return 1 if stats["mismatch"] else 0


def _hashed_pass(src, dst, tasks, pipe, pool, args, stats) -> dict:
    """A local pass with a hash backend, between `open` and `report`: the
    diff drained, what it copies or deletes on the worker pool, then every
    pair it compares through the pair stage. Fills `stats`; returns the
    range digests of both sides of every object compared."""
    with _TR.span("cmd", "sync", stage="list", hist=_H_LIST) as sp_list:
        pairs, others = [], []
        for task in tasks:
            if task[0] == "check":
                pairs.append(task[1])
            else:
                others.append(task)
    copied: list = []
    if others:
        do = _make_executor(src, dst, args, stats, verify_later=copied)
        with _TR.span("cmd", "sync", stage="copy", hist=_H_COPY):
            list(pool.map(do, others))
        copied.sort(key=lambda o: o.key)
    stores = {"src": src, "dst": dst}

    def load(item) -> bytes:
        side, key, off, n = item
        store = stores[side]
        return bytes(store.get(key, off, n) if n else store.get(key))

    stage = ReadHash(load, pool, pipe, args.threads, outlive_open=True)
    with _TR.span("cmd", "sync", stage="check", hist=_H_CHECK) as sp_check:
        stats["checked_bytes"] = 0
        index = _check_pairs(src, pairs, copied, stage, args, stats)
        hashed = sum(len(d) for sides in index.values()
                     for d in sides.values())
        if sp_check.active:
            sp_check.set(pairs=len(pairs) + len(copied), blocks=hashed,
                         window=stage.window, ahead=stage.ahead)
    stats.update({
        # blocks hashed, both sides; every one on every pass
        "hashed_now": hashed,
        # the backend that RAN (requested name: device.requested)
        "backend": pipe.config.backend,
        "device": pipe.device_report(),
        "stage_seconds": {"list": round(sp_list.dur, 6),
                          **stage.stage_seconds(sp_check.dur)},
        "fetch_window": stage.window,
        "fetch_ahead": stage.ahead,
    })
    return index


# -- cluster mode ----------------------------------------------------------
# Wire protocol (JSON over HTTP, reference gob-over-HTTP cluster.go):
#   POST /fetch {"n": N}   -> {"tasks": [[op, obj|null, obj|null], ...],
#                              "done": bool}   (obj = [key, size, mtime])
#   POST /stats {<stats>}  -> {}

_BATCH = 256
# how long the manager keeps answering after the queue drained and the last
# request came: a worker started beside the one that drained it may
# register only then, and is owed an empty batch, not a closed socket
_LINGER = 3.0


def _obj_wire(o):
    return None if o is None else [o.key, o.size, o.mtime]


def _obj_unwire(v):
    from ..object.interface import Obj

    return None if v is None else Obj(key=v[0], size=v[1], mtime=v[2])


def _launch_workers(args, addr: str, flags: list[str]) -> list:
    """Bootstrap one worker per --worker-hosts entry (reference
    cluster.go:237, which ssh-launches workers).  The launch template gets
    {host} and {cmd}; the default runs {cmd} as a local subprocess — the
    hermetic analog of `ssh localhost {cmd}` — so a single command drives
    a whole localhost cluster end to end."""
    import shlex
    import subprocess
    import sys

    hosts = [h.strip() for h in
             getattr(args, "worker_hosts", "").split(",") if h.strip()]
    if not hosts:
        return []
    worker_argv = ["sync", args.src, args.dst, *flags,
                   "--worker", "--manager", addr,
                   "--threads", str(args.threads)]
    template = getattr(args, "worker_launch", "")
    procs = []
    for host in hosts:
        if template:
            # remote form: the template decides the transport and the
            # remote entrypoint; {cmd} is the bare subcommand string
            shell_cmd = template.format(
                host=host, cmd=shlex.join(worker_argv))
            procs.append(subprocess.Popen(
                shell_cmd, shell=True,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
        else:
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "juicefs_tpu.cmd", *worker_argv],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
        logger.info("launched worker on %s", host)
    return procs


def _reap_workers(procs: list, timeout: float = 30.0) -> bool:
    """Collect bootstrapped workers; True when any failed (nonzero exit
    or had to be killed) — the manager must not report a clean sync."""
    import subprocess

    failed = False
    for p in procs:
        try:
            rc = p.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
            rc = -9
        if rc != 0:
            logger.error("bootstrapped worker exited %s", rc)
            failed = True
    return failed


def run_manager(args, tasks) -> int:
    """Serve the ordered diff as a task queue (reference startManager
    cluster.go:132); aggregate worker stats.

    Completion integrity: the manager counts every task it hands out and
    requires the workers' aggregated stats to account for all of them —
    a worker that dies mid-batch (tasks fetched but never reported) turns
    into a nonzero exit, never a silent partial sync. A worker that dies
    without even posting stats is caught by the idle timeout instead of
    hanging the manager forever.
    """
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    it = iter(tasks)
    lock = threading.Lock()
    totals = _new_stats()
    done = threading.Event()
    state = {"busy": 0, "dispatched": 0, "exhausted": False,
             "last_activity": time.monotonic()}

    class Handler(BaseHTTPRequestHandler):
        def _json(self, obj, code=200):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):  # noqa: N802
            n = int(self.headers.get("Content-Length") or 0)
            req = json.loads(self.rfile.read(n) or b"{}")
            with lock:
                state["last_activity"] = time.monotonic()
            if self.path == "/fetch":
                batch = []
                with lock:
                    for _ in range(min(int(req.get("n", _BATCH)), _BATCH)):
                        t = next(it, None)
                        if t is None:
                            state["exhausted"] = True
                            break
                        batch.append([t[0], _obj_wire(t[1]), _obj_wire(t[2])])
                    state["dispatched"] += len(batch)
                self._json({"tasks": batch, "done": not batch})
            elif self.path == "/stats":
                with lock:
                    for k, v in req.items():
                        if k in totals:
                            totals[k] += v
                    state["busy"] -= 1
                    if state["busy"] <= 0:
                        done.set()
                self._json({})
            elif self.path == "/register":
                with lock:
                    state["busy"] += 1
                self._json({})
            elif self.path == "/ping":
                self._json({})  # worker heartbeat (long in-batch copies)
            else:
                self.send_error(404)

        def log_message(self, *a):
            pass

    host, _, port = args.manager_listen.rpartition(":")
    httpd = ThreadingHTTPServer((host or "127.0.0.1", int(port or 0)), Handler)
    addr = f"{httpd.server_address[0]}:{httpd.server_address[1]}"
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    # the hint must carry every execution flag: a worker missing --dry
    # would really copy, missing --delete-src would skip deletions, etc.
    flags = []
    for f in ("dry", "check_new", "check_all", "delete_src", "delete_dst",
              "update", "force_update"):
        if getattr(args, f):
            flags.append("--" + f.replace("_", "-"))
    flags += ["--big-threshold", str(args.big_threshold),
              "--part-size", str(args.part_size)]
    if args.bwlimit:
        flags += ["--bwlimit", str(args.bwlimit)]  # per-worker cap
    print(json.dumps({"manager": addr,
                      "worker_cmd": f"sync {args.src} {args.dst} "
                                    f"{' '.join(flags)} --worker "
                                    f"--manager {addr}"}), flush=True)
    workers = _launch_workers(args, addr, flags)
    idle_limit = 300.0
    timed_out = False
    while not done.wait(timeout=5.0):
        with lock:
            started = state["busy"] > 0 or state["dispatched"] > 0
            busy = state["busy"]
            idle = time.monotonic() - state["last_activity"]
        if started and idle > idle_limit:
            logger.error("no worker activity for %.0fs; giving up", idle)
            timed_out = True
            break
        if workers and busy <= 0 \
                and all(p.poll() is not None for p in workers):
            if not args.worker_launch:
                # every bootstrapped worker already exited and none is
                # still registered: nothing will ever drain the queue —
                # fail now instead of waiting out the idle limit
                logger.error("all bootstrapped workers exited prematurely")
                timed_out = True
                break
            if state["dispatched"] == 0 \
                    and all(p.returncode != 0 for p in workers):
                # custom template: a detaching launcher (ssh -f, tmux)
                # exiting 0 says nothing about the worker, so the idle
                # limit is the backstop there — but every LAUNCH command
                # failing outright before any work is a dead cluster
                logger.error("every worker launch command failed")
                timed_out = True
                break
    while not timed_out:
        with lock:
            quiet = time.monotonic() - state["last_activity"]
        if quiet >= _LINGER or (
                workers and all(p.poll() is not None for p in workers)):
            break
        time.sleep(0.05)
    httpd.shutdown()
    httpd.server_close()
    worker_failed = _reap_workers(workers)
    # every dispatched task must come back as a completed task: a worker
    # killed mid-batch reports fewer tasks_done than it fetched.  A
    # bootstrapped worker's nonzero exit matters only when the accounting
    # is ALSO short — a straggler that registered after a fast sibling
    # drained the whole queue (its /register hits a closed manager) must
    # not fail a sync whose every task completed.
    incomplete = (timed_out or not state["exhausted"]
                  or totals["tasks_done"] < state["dispatched"])
    if worker_failed and not incomplete:
        logger.warning("a bootstrapped worker exited nonzero after the "
                       "sync completed (late straggler); result unaffected")
    if incomplete and not timed_out:
        logger.error(
            "workers completed %d of %d dispatched tasks — partial sync",
            totals["tasks_done"], state["dispatched"],
        )
    totals["dispatched"] = state["dispatched"]
    print(json.dumps(totals))
    return 1 if (totals["mismatch"] or incomplete) else 0


def run_worker(args) -> int:
    """Pull task batches from the manager and execute them
    (reference cluster.go:340 fetchJobs / :90 sendStats)."""
    import urllib.request

    if not args.manager:
        logger.error("--worker requires --manager host:port")
        return 2
    base = args.manager if "://" in args.manager else f"http://{args.manager}"

    def post(path, payload):
        req = urllib.request.Request(
            base + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            return json.loads(resp.read() or b"{}")

    src = _open_store(args.src)
    dst = _open_store(args.dst)
    stats = _new_stats()
    do = _make_executor(src, dst, args, stats)
    post("/register", {})
    # heartbeat: a batch of large multipart copies can run far longer than
    # the manager's idle timeout between /fetch posts
    stop_ping = threading.Event()

    def ping():
        while not stop_ping.wait(30.0):
            try:
                post("/ping", {})
            except Exception:
                pass

    pinger = threading.Thread(target=ping, daemon=True)
    pinger.start()
    try:
        with global_scheduler().executor(
            "bulk", IOClass.BACKGROUND, width=args.threads
        ) as pool:
            while True:
                out = post("/fetch", {"n": _BATCH})
                tasks = [
                    (t[0], _obj_unwire(t[1]), _obj_unwire(t[2]))
                    for t in out.get("tasks", [])
                ]
                if tasks:
                    list(pool.map(do, tasks))
                if out.get("done"):
                    break
    finally:
        stop_ping.set()
        post("/stats", stats)
    print(json.dumps(stats))
    return 1 if stats["mismatch"] else 0
