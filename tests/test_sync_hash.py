"""`sync --check-all|--check-new --hash-backend` (ISSUE 38): the content
compare of a local pass as a digest compare through the scans' read-and-hash
stage (cmd/readhash.py), held to the plain reference — the ranged byte
compare on the host that `sync` runs without the flag (`_content_equal`) —
on seeded random buckets: the same verdict for every key and the same exit
code, mismatches and ranged objects included; and every digest of
`--hash-index` to the numpy spec the benchmark keeps
(benchmark/lib/jth256_spec.py imports nothing of the program)."""

import json
import logging
import os
import sys

import numpy as np
import pytest

from benchmark.lib import jth256_spec
from juicefs_tpu.cmd import fsck, gc, main, readhash, sync
from juicefs_tpu.metric import global_registry
from juicefs_tpu.object import create_storage

SEED = 2**31 + 38
RANGE = sync.HASH_RANGE
BACKENDS = ["cpu", "xla"]
# key -> size: objects of one range, of none, of one byte, and one of three
# ranges whose last is short (9 MiB + 5)
SIZES = {"a/small-0": 1000, "a/small-1": 70_001, "b/empty": 0, "b/one": 1,
         "c/lanes": 65_536 + 1, "ranged": (9 << 20) + 5, "z/last": 4097}


def content(key: str, size: int) -> bytes:
    rng = np.random.default_rng([SEED, sorted(SIZES).index(key)])
    return rng.bytes(size)


class Buckets:
    def __init__(self, root):
        self.src_dir, self.dst_dir = str(root / "src"), str(root / "dst")
        self.src_url = f"file://{self.src_dir}/"
        self.dst_url = f"file://{self.dst_dir}/"
        self.src = create_storage(self.src_url)
        self.src.create()
        for key, size in SIZES.items():
            self.src.put(key, content(key, size))
        self.dst = create_storage(self.dst_url)
        self.dst.create()

    def mirror(self):
        for key, size in SIZES.items():
            self.dst.put(key, content(key, size))
        return self

    def flip(self, key: str, at: int) -> None:
        path = os.path.join(self.dst_dir, key)
        with open(path, "r+b") as f:
            f.seek(at)
            byte = f.read(1)
            f.seek(at)
            f.write(bytes([byte[0] ^ 0x10]))


@pytest.fixture
def buckets(tmp_path):
    return Buckets(tmp_path)


@pytest.fixture
def one_pass(capsys, caplog):
    """one_pass(buckets, *flags) -> (exit code, the stats line or None, what
    `cmd.sync` and `cmd.main` logged at error)"""
    def run(b, *flags):
        caplog.clear()
        capsys.readouterr()
        rc = main(["sync", b.src_url, b.dst_url, "--threads", "4", *flags])
        lines = capsys.readouterr().out.strip().splitlines()
        said = [r.getMessage() for r in caplog.records
                if r.name in ("cmd.sync", "cmd") and r.levelno >= logging.ERROR]
        return rc, json.loads(lines[-1]) if lines else None, said
    return run


def reported(messages):
    return sorted(m.rsplit(": ", 1)[1] for m in messages
                  if m.startswith(("content mismatch", "verify failed")))


def counter(name, *labels):
    m = global_registry()._metrics[name]
    return (m.labels(*labels) if labels else m).value


# -- the new path against the plain reference ---------------------------------

FLIPS = {
    "equal": None,
    "small": ("a/small-1", 70_000),
    "last-range": ("ranged", (9 << 20) + 4),
    "first-range": ("ranged", 0),
    "one-byte": ("b/one", 0),
}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", sorted(FLIPS))
def test_digest_compare_and_byte_compare_agree(buckets, one_pass, backend, case):
    b = buckets.mirror()
    if FLIPS[case] is not None:
        b.flip(*FLIPS[case])
    ref_rc, ref, ref_said = one_pass(b, "--check-all")
    rc, stats, said = one_pass(b, "--check-all",
                               "--hash-backend", backend)
    differing = [] if FLIPS[case] is None else [FLIPS[case][0]]
    assert reported(ref_said) == differing  # the reference sees the flip
    assert (rc, reported(said)) == (ref_rc, reported(ref_said))
    assert rc == (1 if differing else 0)
    for key in ("copied", "copied_bytes", "deleted", "checked", "mismatch",
                "skipped", "tasks_done"):
        assert stats[key] == ref[key], key
    assert stats["checked"] == len(SIZES) and stats["mismatch"] == len(differing)
    # both sides of every range, every pass: 7 objects, the ranged one three
    assert stats["hashed_now"] == 2 * (len(SIZES) + 2)
    assert stats["checked_bytes"] == sum(SIZES.values())
    assert stats["backend"] == backend == stats["device"]["backend"]
    assert set(stats["stage_seconds"]) == {"list", "get", "get_threads",
                                           "hash", "readhash"}
    assert (stats["fetch_window"], stats["fetch_ahead"]) == (4, 32)


@pytest.mark.parametrize("backend", BACKENDS)
def test_hash_index_holds_the_specs_digests_of_both_sides(
        buckets, one_pass, tmp_path, backend):
    b = buckets.mirror()
    b.flip("ranged", RANGE + 17)  # the second range of the destination
    index_file = str(tmp_path / "index.json")
    rc, stats, said = one_pass(b, "--check-all", "--hash-backend",
                               backend, "--hash-index", index_file)
    assert rc == 1 and reported(said) == ["ranged"]
    with open(index_file) as f:
        got = json.load(f)
    want = {}
    for key, size in SIZES.items():
        sides = {}
        for side, root in (("src", b.src_dir), ("dst", b.dst_dir)):
            with open(os.path.join(root, key), "rb") as f:
                data = f.read()
            assert len(data) == size
            sides[side] = [jth256_spec.jth256(data[off:off + RANGE]).hex()
                           for off in range(0, max(size, 1), RANGE)]
        want[key] = sides
    assert got == want
    assert len(got["ranged"]["src"]) == 3 and got["b/empty"]["src"] == [
        jth256_spec.jth256(b"").hex()]
    differing = [(k, i) for k, s in got.items()
                 for i, (x, y) in enumerate(zip(s["src"], s["dst"])) if x != y]
    assert differing == [("ranged", 1)]


@pytest.mark.parametrize("backend", BACKENDS)
def test_check_new_verifies_only_what_the_pass_copied(buckets, one_pass,
                                                      tmp_path, backend):
    b = buckets
    have = ["a/small-0", "b/one", "ranged"]
    for key in have:
        b.dst.put(key, content(key, SIZES[key]))
    b.flip("ranged", 5)  # already there, same size: --check-new never looks
    index_file = str(tmp_path / "index.json")
    rc, stats, said = one_pass(b, "--check-new", "--hash-backend",
                               backend, "--hash-index", index_file)
    copied = sorted(set(SIZES) - set(have))
    assert rc == 0 and said == []
    assert (stats["copied"], stats["checked"], stats["mismatch"]) == (
        len(copied), 0, 0)
    assert stats["checked_bytes"] == sum(SIZES[k] for k in copied)
    assert stats["hashed_now"] == 2 * len(copied)
    with open(index_file) as f:
        assert sorted(json.load(f)) == copied
    for key in copied:
        assert bytes(b.dst.get(key)) == content(key, SIZES[key])
    # what the byte compare does on the same buckets
    ref = Buckets(tmp_path / "ref")
    for key in have:
        ref.dst.put(key, content(key, SIZES[key]))
    ref_rc, ref_stats, _ = one_pass(ref, "--check-new")
    assert (ref_rc, ref_stats["copied"], ref_stats["checked"]) == (
        rc, stats["copied"], stats["checked"])


def test_check_new_reports_a_copy_that_does_not_read_back(buckets, one_pass,
                                                          monkeypatch):
    """A destination that stores one object wrong: the digest compare after
    the copy says so, by its key, as the byte compare does."""
    real = sync._copy_object

    def lossy(src, dst, obj, args, stats):
        real(src, dst, obj, args, stats)
        if obj.key == "c/lanes":
            dst.put(obj.key, b"\0" * obj.size)

    monkeypatch.setattr(sync, "_copy_object", lossy)
    rc, stats, said = one_pass(buckets, "--check-new",
                               "--hash-backend", "cpu", "--delete-src")
    assert rc == 1 and said == ["verify failed after copy: c/lanes"]
    assert (stats["copied"], stats["mismatch"]) == (len(SIZES), 1)
    # a source whose copy is wrong is kept; the verified ones are gone
    assert stats["deleted"] == len(SIZES) - 1
    assert [o.key for o in buckets.src.list_all("", "")
            if not o.is_dir] == ["c/lanes"]


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_failing_get_is_skipped_not_a_mismatch(buckets, one_pass, backend,
                                                 monkeypatch):
    b = buckets.mirror()
    from juicefs_tpu.object.file import FileStorage

    get = FileStorage.get

    def failing(self, key, off=0, limit=-1):
        if key == "ranged" and off == RANGE and self.root.startswith(b.dst_dir):
            raise PermissionError("no such luck")
        return get(self, key, off, limit)

    monkeypatch.setattr(FileStorage, "get", failing)
    before = counter("juicefs_sync_objects", "skipped")
    rc, stats, said = one_pass(b, "--check-all",
                               "--hash-backend", backend)
    assert rc == 0 and reported(said) == []
    assert (stats["checked"], stats["mismatch"], stats["skipped"]) == (
        len(SIZES), 0, 1)
    assert any("check ranged" in m and "no such luck" in m for m in said)
    assert stats["checked_bytes"] == sum(SIZES.values()) - SIZES["ranged"]
    assert stats["tasks_done"] == len(SIZES)
    assert counter("juicefs_sync_objects", "skipped") - before == 1


# -- the flag's edges -----------------------------------------------------------

def test_without_the_flag_it_is_the_old_path_and_no_pipeline(
        buckets, one_pass, monkeypatch):
    b = buckets.mirror()

    def never(*a, **kw):
        raise AssertionError("a pass without --hash-backend built a pipeline")

    monkeypatch.setattr(sync, "scan_pipeline", never)
    monkeypatch.setattr(readhash.ReadHash, "digests", never)
    rc, stats, said = one_pass(b, "--check-all")
    assert rc == 0 and said == []
    assert list(stats) == ["copied", "copied_bytes", "deleted", "checked",
                           "mismatch", "skipped", "tasks_done", "seconds"]
    assert stats["checked"] == len(SIZES)
    # the flag without a compare to make builds none either
    rc, stats, _ = one_pass(b, "--hash-backend", "cpu")
    assert rc == 0 and "backend" not in stats


@pytest.mark.parametrize("mode", [["--worker", "--manager", "127.0.0.1:1"],
                                  ["--manager-listen", "127.0.0.1:0"]])
def test_the_flag_is_refused_in_cluster_mode(buckets, one_pass, mode):
    rc, stats, said = one_pass(buckets, "--check-all", "--hash-backend", "cpu",
                               *mode)
    assert rc == 2 and stats is None
    assert len(said) == 1 and "cluster mode" in said[0]
    assert os.listdir(buckets.dst_dir) == []  # nothing was listed or copied


def test_tpu_without_a_tpu_fails_in_open_before_a_key_is_listed(
        buckets, one_pass, monkeypatch):
    from juicefs_tpu.object.file import FileStorage

    listed = []
    monkeypatch.setattr(FileStorage, "list_all",
                        lambda self, *a, **kw: listed.append(self.root) or [])
    rc, stats, said = one_pass(buckets, "--check-all", "--hash-backend", "tpu")
    assert rc == 1 and listed == [] and stats is None
    assert any("needs a TPU" in m for m in said)


def test_the_digest_compare_reads_ranges_never_a_whole_big_object(
        buckets, one_pass, monkeypatch):
    """Constant memory, as `_content_equal` promises: no GET of the pair
    stage is longer than one range."""
    b = buckets.mirror()
    from juicefs_tpu.object.file import FileStorage

    get = FileStorage.get
    longest = []

    def noted(self, key, off=0, limit=-1):
        data = get(self, key, off, limit)
        longest.append(len(data))
        return data

    monkeypatch.setattr(FileStorage, "get", noted)
    rc, stats, _ = one_pass(b, "--check-all", "--hash-backend", "cpu")
    assert rc == 0 and max(longest) == RANGE
    assert len(longest) == stats["hashed_now"] == 2 * (len(SIZES) + 2)


# -- one stage, three callers ---------------------------------------------------

def test_sync_gc_and_fsck_call_the_one_shared_stage(buckets, one_pass,
                                                    monkeypatch):
    """`sync` reads and hashes through `ReadHash.digests`, on its own
    `bulk` executor, and keeps no copy of the stage's steps
    (tests/test_fsck_verify.py holds `gc` and `fsck` to the same)."""
    b = buckets.mirror()
    callers, pools = [], []
    digests = readhash.ReadHash.digests

    def noted(self, items):
        callers.append(sys._getframe(1).f_globals["__name__"])
        pools.append((self.pool.lane, self.pool.cls.label))
        return digests(self, items)

    monkeypatch.setattr(readhash.ReadHash, "digests", noted)
    rc, _, _ = one_pass(b, "--check-all", "--hash-backend", "cpu")
    assert rc == 0
    assert callers == ["juicefs_tpu.cmd.sync"]
    assert pools == [("bulk", "background")]
    for mod in (sync, gc, fsck):
        with open(mod.__file__) as f:
            source = f.read()
        assert "fetch_ordered" not in source and "hash_stream" not in source
        assert "_load_block" not in source and "_bulk_pool" not in source


# -- one trace, its histograms and counters ---------------------------------------

def _names(evs):
    return [".".join(x for x in (e["layer"], e["op"], e.get("stage", "")) if x)
            for e in evs]


def stage_count(stage):
    from juicefs_tpu.metric.trace import stage_hist

    return stage_hist("cmd", "sync", stage).total


def test_a_pass_is_one_trace_tree(buckets, one_pass):
    """cmd.sync -> {open, list, check -> {chunk.fetch.wait, object.get,
    tpu.hash.dispatch -> {pack, h2d, enqueue}, tpu.hash.drain}, report}; over
    a complete mirror nothing is copied, so no `copy`."""
    from test_trace import _reader

    b = buckets.mirror()
    stages = ("open", "list", "copy", "check", "report", "total")
    before = {s: stage_count(s) for s in stages}
    counted = {r: counter("juicefs_sync_objects", r)
               for r in ("checked", "mismatch", "copied", "skipped")}
    nbytes = counter("juicefs_sync_checked_bytes")
    with _reader() as r:
        rc, stats, _ = one_pass(b, "--check-all",
                                "--hash-backend", "xla")
        evs = r.drain()
    assert rc == 0
    name_of = dict(zip((e["id"] for e in evs), _names(evs)))
    root = next(e for e in evs if name_of[e["id"]] == "cmd.sync")
    assert root["parent"] == 0 and root["backend"] == "xla"
    assert (root["checked"], root["mismatch"]) == (len(SIZES), 0)
    # `main`'s argument parsing is a trace of its own, before the pass's
    parse, = [e for e in evs if name_of[e["id"]] == "cmd.main.parse"]
    assert parse["parent"] == 0
    assert {e["trace"] for e in evs if e is not parse} == {root["trace"]}
    below_root = sorted(name_of[e["id"]] for e in evs
                        if e["parent"] == root["id"])
    assert below_root == ["cmd.sync.check", "cmd.sync.list", "cmd.sync.open",
                          "cmd.sync.report"]
    check = next(e for e in evs if name_of[e["id"]] == "cmd.sync.check")
    blocks = 2 * (len(SIZES) + 2)
    assert (check["pairs"], check["blocks"], check["window"],
            check["ahead"]) == (len(SIZES), blocks, 4, 32)
    for e in evs:
        name = name_of[e["id"]]
        if name in ("chunk.fetch.wait", "tpu.hash.dispatch", "tpu.hash.drain",
                    "object.get"):
            assert name_of[e["parent"]] == "cmd.sync.check", name
        elif name in ("tpu.hash.pack", "tpu.hash.h2d", "tpu.hash.enqueue"):
            assert name_of[e["parent"]] == "tpu.hash.dispatch", name
        elif name == "tpu.pack.prepare":
            assert name_of[e["parent"]] == "cmd.sync.open"
    counts = {n: _names(evs).count(n) for n in set(_names(evs))}
    assert counts["chunk.fetch.wait"] == blocks
    # both stores are metered: one `object.get` a range read, either side
    assert counts["object.get"] == blocks
    assert (counts["tpu.hash.pack"] == counts["tpu.hash.h2d"]
            == counts["tpu.hash.enqueue"] == counts["tpu.hash.dispatch"]
            == counts["tpu.hash.drain"] == 1)
    # one observation a stage a pass; the stats' stages are the spans'
    gained = {s: stage_count(s) - before[s] for s in stages}
    assert gained == {"open": 1, "list": 1, "copy": 0, "check": 1,
                      "report": 1, "total": 1}
    dur = dict(zip(_names(evs), (e["dur"] for e in evs)))
    assert stats["stage_seconds"]["list"] == round(dur["cmd.sync.list"], 6)
    assert stats["stage_seconds"]["readhash"] == round(
        dur["cmd.sync.check"], 6)
    # the counters add up to the pairs
    gained = {r: counter("juicefs_sync_objects", r) - counted[r]
              for r in counted}
    assert gained == {"checked": len(SIZES), "mismatch": 0, "copied": 0,
                      "skipped": 0}
    assert counter("juicefs_sync_checked_bytes") - nbytes == sum(SIZES.values())


def test_a_copying_pass_has_a_copy_stage_and_counts_what_it_copied(
        buckets, one_pass):
    before = {s: stage_count(s) for s in ("list", "copy", "check")}
    copied = counter("juicefs_sync_objects", "copied")
    rc, stats, _ = one_pass(buckets, "--check-new",
                            "--hash-backend", "cpu")
    assert rc == 0 and stats["copied"] == len(SIZES)
    assert {s: stage_count(s) - n for s, n in before.items()} == {
        "list": 1, "copy": 1, "check": 1}
    assert counter("juicefs_sync_objects", "copied") - copied == len(SIZES)
    # and the old path: the lazy diff drives the pool inside `copy`
    before = {s: stage_count(s) for s in ("list", "copy", "check")}
    rc, _, _ = one_pass(buckets, "--check-all")
    assert rc == 0
    assert {s: stage_count(s) - n for s, n in before.items()} == {
        "list": 0, "copy": 1, "check": 0}
