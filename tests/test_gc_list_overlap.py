"""`gc --dedup` lists the store beside its scan (ISSUE 37).

The listing of `chunks/` runs on a thread of its own, `jfs-gc-list`, while
the main thread walks the slices and reads and hashes what they name; the
name diff waits for it after the scan. Held here: what `gc` prints, deletes
and returns is what the serial order printed, deleted and returned (literals
taken from the parent commit over the same volume); the overlap is real;
whichever side raises, `gc` raises it and the lister is gone; without
`--dedup` there is no lister.
"""

import argparse
import contextlib
import hashlib
import io
import json
import threading
import time

import pytest

from juicefs_tpu.cmd import build_store, main, open_meta
from juicefs_tpu.cmd import gc as gc_cmd
from juicefs_tpu.meta.context import Context
from juicefs_tpu.vfs import ROOT_INO, VFS

CTX = Context(uid=0, gid=0, pid=1)
BS = 64 << 10
LISTER = "jfs-gc-list"
LEAKED = "chunks/0/0/999999_0_1000"


def _content(tag: str, n: int) -> bytes:
    out = bytearray()
    i = 0
    while len(out) < n:
        out += hashlib.sha256(f"{tag}:{i}".encode()).digest()
        i += 1
    return bytes(out[:n])


def _write(v, name: bytes, data: bytes) -> None:
    st, ino, _, fh = v.create(CTX, ROOT_INO, name, 0o644)
    assert st == 0
    assert v.write(CTX, ino, fh, 0, data) == 0
    assert v.release(CTX, ino, fh) == 0


def _volume(tmp_path) -> str:
    """Five files over a file:// store. Written through the inline dedup
    stage: `a` and `b` hold one content (b's block is an alias of a's), `c`
    is three blocks whose middle one has lost its object, `d` is a ragged
    block of its own. Written past that stage: `e`, d's content again in an
    object of its own, which `--dedup --delete` collapses. Beside them an
    object no slice names. The content index is emptied, so a scan reads
    and hashes every block it can."""
    meta_url = f"sqlite3://{tmp_path}/meta.db"
    assert main(["format", meta_url, "ovol", "--storage", "file",
                 "--bucket", str(tmp_path / "blobs"), "--block-size", "64",
                 "--hash-backend", "cpu", "--trash-days", "0"]) == 0

    class A:
        cache_dir = str(tmp_path / "cache")
        writeback = False
        cache_size = 0
        inline_dedup = True

    m, fmt = open_meta(meta_url)
    m.new_session()
    store = build_store(fmt, A(), meta=m)
    v = VFS(m, store, fmt=fmt)
    for name, data in ((b"a", _content("same", BS)),
                       (b"b", _content("same", BS)),
                       (b"c", _content("c", 3 * BS)),
                       (b"d", _content("d", BS // 2 + 7))):
        _write(v, name, data)
    store.flush_all()
    assert store.ingest.stats()["put_elided"] == 1
    v.close()

    A.inline_dedup, A.cache_dir = False, str(tmp_path / "cache-e")
    m, fmt = open_meta(meta_url)
    m.new_session()
    store = build_store(fmt, A(), meta=m)
    v = VFS(m, store, fmt=fmt)
    _write(v, b"e", _content("d", BS // 2 + 7))
    store.flush_all()
    stored = sorted(o.key for o in store.storage.list_all("chunks/"))
    middle = next(k for k in stored if k.endswith(f"_1_{BS}"))
    store.storage.delete(middle)
    store.storage.put(LEAKED, b"\0" * 1000)
    m.delete_block_digests(
        [(sid, indx) for sid, indx, _b, _d in m.scan_block_digests()])
    v.close()
    return meta_url


def _objects(meta_url) -> set[str]:
    m, fmt = open_meta(meta_url)
    store = build_store(fmt, None, meta=m, with_indexer=False)
    try:
        return {o.key for o in store.storage.list_all("chunks/")}
    finally:
        store.close()
        m.close_session()


def _lister_threads() -> list[str]:
    return [t.name for t in threading.enumerate() if t.name == LISTER]


def _parsed(meta_url, *flags):
    """`gc`'s own arguments, for calling its `run` below `cmd.main`'s
    catch-all."""
    parser = argparse.ArgumentParser()
    gc_cmd.add_parser(parser.add_subparsers())
    return parser.parse_args(["gc", meta_url, "--age", "0", "--threads", "4",
                              *flags])


# what the scan returns, less its timings and the device's report
STEADY = ("blocks", "bytes", "from_index", "hashed_now",
          "stale_index_rows_removed", "duplicate_blocks", "duplicate_bytes",
          "dedup_groups", "backend", "fetch_window", "fetch_ahead",
          "content_refs")
REFS = {"orphaned_aliases_repaired": 0, "refcounts_fixed": 0,
        "dangling_content_refs": 0, "self_healed_aliases": 0,
        "registered": 0, "collapsed": 0, "collapsed_bytes": 0}
STATS = {"blocks": 6, "bytes": 393230, "from_index": 0, "hashed_now": 6,
         "stale_index_rows_removed": 0, "duplicate_blocks": 2,
         "duplicate_bytes": 98311, "dedup_groups": 2, "backend": "cpu",
         "fetch_window": 4, "fetch_ahead": 32, "content_refs": REFS}
COLLAPSED = "chunks/0/0/257_0_32775"  # e's object: d's content again
SCANNED = "scanned: 6 objects, 7 live blocks (1 deduped), 1 leaked, 1 missing"
SWEPT = "deleted 1 leaked objects"

# flags -> (stdout less the stats line, keys deleted, stats): the parent's
# (7eaff0c), which listed the store before it scanned
SERIAL = {
    (): ([SCANNED], set(), None),
    ("--delete",): ([SCANNED, SWEPT], {LEAKED}, None),
    ("--dedup",): ([SCANNED], set(), STATS),
    ("--dedup", "--delete"): (
        [SCANNED, SWEPT], {LEAKED, COLLAPSED},
        {**STATS, "content_refs": {**REFS, "collapsed": 1,
                                   "collapsed_bytes": 32775}}),
}


@pytest.mark.parametrize("flags", list(SERIAL), ids=lambda f: " ".join(f) or "-")
def test_gc_says_and_does_what_the_serial_order_did(tmp_path, capsys, flags):
    lines, deleted, stats = SERIAL[flags]
    meta_url = _volume(tmp_path)
    before = _objects(meta_url)
    capsys.readouterr()
    assert main(["gc", meta_url, "--age", "0", "--threads", "4", *flags]) == 0
    out = capsys.readouterr().out.splitlines()
    if stats is not None:
        got = json.loads(out.pop())
        assert {k: got[k] for k in STEADY} == stats
    assert out == lines
    assert before - _objects(meta_url) == deleted
    assert _lister_threads() == []


class _Store:
    """The object store a `gc` was given, with `list_all` and `get`
    replaced; everything else is the store's own."""

    def __init__(self, inner, list_all, get=None):
        self._inner = inner
        self.list_all = lambda prefix: list_all(inner, prefix)
        if get is not None:
            self.get = lambda *a, **kw: get(inner, *a, **kw)

    def __getattr__(self, name):
        return getattr(self._inner, name)


@pytest.fixture
def storage_as(monkeypatch):
    """Have the next `build_store` of a command wrap its object store."""
    import juicefs_tpu.cmd as cmd

    def install(**replaced):
        def build(*a, **kw):
            store = build_store(*a, **kw)
            store.storage = _Store(store.storage, **replaced)
            return store
        monkeypatch.setattr(cmd, "build_store", build)
    return install


def test_the_first_get_is_served_while_the_listing_is_still_out(
        tmp_path, storage_as):
    """A store whose listing answers only once a GET has been served: the
    serial order would wait here for good."""
    log: list[str] = []
    got = threading.Event()

    def list_all(inner, prefix):
        log.append("list:asked")
        log.append("list:answered" if got.wait(30) else "list:gave_up")
        yield from inner.list_all(prefix)

    def get(inner, *a, **kw):
        try:
            return inner.get(*a, **kw)
        finally:
            log.append("get")
            got.set()

    class Out(io.StringIO):
        def write(self, s):
            if s.strip():
                log.append("out:" + s.split(":")[0].split()[0])
            return super().write(s)

    meta_url = _volume(tmp_path)
    storage_as(list_all=list_all, get=get)
    with contextlib.redirect_stdout(Out()) as out:
        assert main(["gc", meta_url, "--dedup", "--age", "0",
                     "--threads", "4"]) == 0
    assert out.getvalue().splitlines()[0] == SCANNED
    assert "list:gave_up" not in log
    assert log.index("get") < log.index("list:answered") < log.index(
        "out:scanned")
    assert _lister_threads() == []


class _Broke(Exception):
    pass


def test_a_listing_that_raises_fails_gc_with_that_error(tmp_path, storage_as,
                                                        capsys):
    def list_all(inner, prefix):
        yield from ()
        raise _Broke("the listing")

    meta_url = _volume(tmp_path)
    capsys.readouterr()
    storage_as(list_all=list_all)
    with pytest.raises(_Broke, match="the listing"):
        gc_cmd.run(_parsed(meta_url, "--dedup"))
    # never a scan that reports without a name diff
    assert capsys.readouterr().out == ""
    assert _lister_threads() == []


def test_a_scan_that_raises_ends_the_lister(tmp_path, storage_as,
                                            monkeypatch, capsys):
    """The listing here never ends by itself: only the invocation on its
    way out can end it."""
    asked = threading.Event()

    def list_all(inner, prefix):
        asked.set()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            yield from inner.list_all(prefix)
            time.sleep(0.001)

    def scan(*a, **kw):
        assert asked.wait(30)
        raise _Broke("the scan")

    meta_url = _volume(tmp_path)
    capsys.readouterr()
    storage_as(list_all=list_all)
    monkeypatch.setattr(gc_cmd, "dedup_scan", scan)
    t0 = time.monotonic()
    with pytest.raises(_Broke, match="the scan"):
        gc_cmd.run(_parsed(meta_url, "--dedup"))
    assert time.monotonic() - t0 < 20
    assert capsys.readouterr().out == ""
    assert _lister_threads() == []


@pytest.mark.parametrize("flags,listers", [((), 0), (("--delete",), 0),
                                           (("--dedup",), 1)],
                         ids=["gc", "gc --delete", "gc --dedup"])
def test_only_a_scan_has_a_lister(tmp_path, monkeypatch, capsys, flags,
                                  listers):
    started: list[str] = []
    start = threading.Thread.start

    def noting(self):
        started.append(self.name)
        start(self)

    meta_url = _volume(tmp_path)
    monkeypatch.setattr(threading.Thread, "start", noting)
    assert gc_cmd.run(_parsed(meta_url, *flags)) == 0
    assert started.count(LISTER) == listers
    assert _lister_threads() == []
