"""The scrub on the normal path (ISSUE 35): `fsck --verify-data` reads and
hashes through the stage `gc --dedup` uses (cmd/readhash.py), over sqlite3
and over the Redis-protocol meta server as a process of its own, on the host
hash, the XLA program and the Pallas kernel (interpreted here). Every
comparison is of bytes, against the numpy spec the benchmark keeps
(benchmark/lib/jth256_spec.py imports nothing of the program) over a volume
planned from a seed: a kernel computing anything but JTH-256, or a stage
skipping a block, fails them."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from benchmark.lib import jth256_spec, volume, volume_served
from benchmark.lib.plan import block_bytes, make_plan
from juicefs_tpu.cmd import fsck, gc, main, open_meta, readhash
from juicefs_tpu.metric import global_registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 35
BLOCK = 256 << 10
ENGINES = ["sqlite3", "redis"]
BACKENDS = ["cpu", "xla", "pallas"]
DEPLOYMENT = {"storage": "file", "block_bytes": BLOCK, "compression": "none"}
LISTENING = "meta-server listening on "


@pytest.fixture(scope="module")
def meta_server(tmp_path_factory):
    """The bundled `meta-server` as a child: a meta request is a round trip
    to another process."""
    aof = tmp_path_factory.mktemp("meta-server") / "meta.aof"
    child = subprocess.Popen(
        [sys.executable, "-m", "juicefs_tpu.cmd", "meta-server",
         "--host", "127.0.0.1", "--port", "0", "--data", str(aof),
         "--fsync", "everysec"],
        env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE, text=True)
    try:
        line = child.stdout.readline()
        assert line.startswith(LISTENING), line
        yield int(line[len(LISTENING):].split()[0].rsplit(":", 1)[1])
    finally:
        child.kill()
        child.wait()
        child.stdout.close()


class Volume:
    def __init__(self, engine, workdir, meta_url, plan):
        self.engine, self.workdir, self.meta_url = engine, workdir, meta_url
        with open(os.path.join(workdir, volume.RESULT)) as f:
            stored = json.load(f)["blocks"]
        by_content = {(tuple(b.content), b.size): b for b in plan.blocks}
        self.block_of = {key: by_content[(tuple(content), size)]
                         for key, content, size in stored}
        ref = {b.content: jth256_spec.jth256(block_bytes(SEED, b)).hex()
               for b in self.block_of.values()}
        self.want = {key: ref[b.content] for key, b in self.block_of.items()}

    def path(self, key):
        return os.path.join(self.workdir, "blob", "benchvol", key)


@pytest.fixture(scope="module", params=ENGINES)
def vol(request, tmp_path_factory):
    """17 blocks of up to 256 KiB from the seed, through the program's own
    write path (the benchmark's builders): three objects of four blocks,
    duplicates from a pool of two, and the ragged handful."""
    plan = make_plan(SEED, 3, block=BLOCK, object_blocks=4, pool_blocks=2,
                     dup_probability=0.4,
                     ragged_sizes=(1, 100_001, BLOCK - 1, BLOCK + 7))
    workdir = str(tmp_path_factory.mktemp("vol-" + request.param))
    if request.param == "redis":
        port = request.getfixturevalue("meta_server")
        meta_url = f"redis://127.0.0.1:{port}/1"
        volume_served.build(workdir, plan, DEPLOYMENT, meta_url)
    else:
        volume.build(workdir, plan, dict(DEPLOYMENT, meta="sqlite3"))
        meta_url = f"sqlite3://{workdir}/meta.db"
    v = Volume(request.param, workdir, meta_url, plan)
    assert len(v.block_of) == 17 and plan.expected_duplicates > 0
    return v


def scrub(vol, backend, capsys, tmp_path, threads=3):
    """-> (exit code, stats line, hash index, everything printed)"""
    index = str(tmp_path / "scrub.json")
    capsys.readouterr()
    rc = main(["fsck", vol.meta_url, "--verify-data", "--hash-index", index,
               "--hash-backend", backend, "--threads", str(threads)])
    out = capsys.readouterr().out
    with open(index) as f:
        return rc, json.loads(out.strip().splitlines()[-1]), json.load(f), out


def series(prefix):
    """Every series of the registry's exposition text that starts so."""
    return {line.rpartition(" ")[0]: float(line.rpartition(" ")[2])
            for line in global_registry().render().splitlines()
            if line.startswith(prefix)}


def stage_count(layer, op, stage):
    key = ('juicefs_tpu_stage_seconds_count{layer="%s",op="%s",stage="%s"}'
           % (layer, op, stage))
    return series("juicefs_tpu_stage_seconds_count").get(key, 0.0)


@pytest.mark.parametrize("backend", BACKENDS)
def test_every_digest_is_the_specs_and_gcs(vol, backend, capsys, tmp_path):
    gc_index = str(tmp_path / "gc.json")
    assert main(["gc", vol.meta_url, "--dedup", "--hash-backend", backend,
                 "--dedup-index", gc_index, "--threads", "3"]) == 0
    rc, stats, index, out = scrub(vol, backend, capsys, tmp_path)
    assert rc == 0
    assert index == vol.want
    with open(gc_index) as f:
        assert index == json.load(f)
    n = len(vol.want)
    assert (stats["blocks"], stats["verified"], stats["hashed_now"],
            stats["indexed"], stats["mismatches"], stats["broken"]) == (
            n, n, n, n, 0, 0)
    assert stats["bytes"] == sum(b.size for b in vol.block_of.values())
    assert stats["backend"] == stats["device"]["backend"] == backend
    assert (stats["fetch_window"], stats["fetch_ahead"]) == (3, 32)
    assert {"list", "index_load", "get", "get_threads", "hash",
            "readhash"} == set(stats["stage_seconds"])
    # the three lines chip_smoke.py reads, word for word, then the stats
    lines = out.strip().splitlines()
    assert lines[-4] == (f"verified {n} blocks ({backend}); {n} indexed, "
                         "0 digest mismatches")
    assert lines[-3].startswith("device: {")
    assert lines[-2].endswith(f"/ {n} blocks; 0 broken")


@pytest.mark.parametrize("backend", BACKENDS)
def test_one_flipped_byte_is_found_by_its_key(vol, backend, capsys, tmp_path,
                                              caplog):
    assert main(["gc", vol.meta_url, "--dedup", "--hash-backend", "cpu"]) == 0
    key = sorted(k for k, b in vol.block_of.items() if b.size > 1000)[5]
    path = vol.path(key)
    with open(path, "rb") as f:
        sound = f.read()
    rotten = bytearray(sound)
    rotten[777] ^= 0x04
    with open(path, "wb") as f:
        f.write(rotten)
    try:
        with caplog.at_level("ERROR", logger="cmd.fsck"):
            rc, stats, index, out = scrub(vol, backend, capsys, tmp_path)
    finally:
        with open(path, "wb") as f:
            f.write(sound)
    assert rc == 1 and stats["mismatches"] == 1 and stats["broken"] == 1
    reported = [r.getMessage() for r in caplog.records
                if "digest mismatch" in r.getMessage()]
    assert reported == [f"block {key} content digest mismatch (bitrot?)"]
    assert [k for k in vol.want if index.get(k) != vol.want[k]] == [key]
    assert "1 digest mismatches" in out and "; 1 broken" in out
    # put back, the volume is sound again
    assert scrub(vol, backend, capsys, tmp_path)[0] == 0


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_removed_object_is_reported_missing(vol, backend, capsys, tmp_path,
                                              caplog):
    key = sorted(vol.block_of)[2]
    os.rename(vol.path(key), vol.path(key) + ".gone")
    before = series("juicefs_fsck_blocks")
    try:
        with caplog.at_level("ERROR", logger="cmd.fsck"):
            rc, stats, index, out = scrub(vol, backend, capsys, tmp_path)
    finally:
        os.rename(vol.path(key) + ".gone", vol.path(key))
    assert rc == 1 and "; 1 broken" in out
    assert any(f"missing block {key}" in r.getMessage() for r in caplog.records)
    assert set(index) == set(vol.want) - {key}
    assert stats["verified"] == len(vol.want) - 1 and stats["mismatches"] == 0
    gained = {k: v - before.get(k, 0.0)
              for k, v in series("juicefs_fsck_blocks").items()}
    assert gained == {'juicefs_fsck_blocks{result="missing"}': 1.0,
                      'juicefs_fsck_blocks{result="verified"}': len(vol.want) - 1,
                      'juicefs_fsck_blocks{result="mismatch"}': 0.0,
                      'juicefs_fsck_blocks{result="unreadable"}': 0.0,
                      'juicefs_fsck_blocks{result="corrupt"}': 0.0}


def gained_blocks(before):
    return {k.split('"')[1]: int(v - before.get(k, 0.0))
            for k, v in series("juicefs_fsck_blocks").items()}


def test_an_object_that_fails_to_read_is_reported_with_why(
        vol, capsys, tmp_path, caplog, monkeypatch):
    from juicefs_tpu.chunk.cached_store import CachedStore

    key = sorted(vol.block_of)[7]
    load = CachedStore._load_block

    def one_fails(self, k, *a, **kw):
        if k == key:
            raise OSError(5, "the disk under it is gone")
        return load(self, k, *a, **kw)

    monkeypatch.setattr(CachedStore, "_load_block", one_fails)
    before = series("juicefs_fsck_blocks")
    with caplog.at_level("ERROR", logger="cmd.fsck"):
        rc, stats, index, out = scrub(vol, "cpu", capsys, tmp_path)
    n = len(vol.want)
    assert rc == 1 and "; 1 broken" in out
    assert [r.getMessage() for r in caplog.records] == [
        f"block {key} unreadable: [Errno 5] the disk under it is gone"]
    assert index == {k: d for k, d in vol.want.items() if k != key}
    assert (stats["verified"], stats["mismatches"], stats["broken"]) == (
        n - 1, 0, 1)
    assert gained_blocks(before) == {
        "missing": 0, "verified": n - 1, "mismatch": 0, "unreadable": 1,
        "corrupt": 0}


def test_a_store_whose_circuit_opens_still_gets_its_report(
        vol, capsys, tmp_path, caplog, monkeypatch):
    """The fetch stage gives up on an open circuit (chunk/parallel.py). The
    scrub verifies what it had read, counts the rest unreadable, names the
    block it had found rotten before, and ends with its lines and exit 1."""
    from juicefs_tpu.chunk.cached_store import CachedStore
    from juicefs_tpu.object.resilient import BreakerOpenError

    assert main(["gc", vol.meta_url, "--dedup", "--hash-backend", "cpu"]) == 0
    keys = sorted(vol.block_of)  # the order the scrub reads them in
    order = {}
    load = CachedStore._load_block

    def opens_after_five(self, k, *a, **kw):
        if order.setdefault(k, len(order)) >= 5:
            raise BreakerOpenError("file")
        data = load(self, k, *a, **kw)
        return bytes([data[0] ^ 1]) + data[1:] if order[k] == 2 else data

    monkeypatch.setattr(CachedStore, "_load_block", opens_after_five)
    before = series("juicefs_fsck_blocks")
    with caplog.at_level("ERROR", logger="cmd.fsck"):
        rc, stats, index, out = scrub(vol, "cpu", capsys, tmp_path, threads=1)
    n = len(vol.want)
    read = [k for k, i in order.items() if i < 5]
    rotten = read[2]
    assert rc == 1 and set(index) == set(read) <= set(keys)
    assert gained_blocks(before) == {
        "missing": 0, "verified": 4, "mismatch": 1, "unreadable": n - 5,
        "corrupt": 0}
    assert (stats["verified"], stats["mismatches"], stats["broken"]) == (
        5, 1, n - 4)
    said = [r.getMessage() for r in caplog.records]
    assert said[0] == f"block {rotten} content digest mismatch (bitrot?)"
    assert said[-1] == ("[Errno 5] object backend file: circuit open: "
                        f"{n - 5} of {n} blocks were not read")
    assert all("unreadable: [Errno 5] object backend file: circuit open" in m
               for m in said[1:-1]) and len(said) >= 3
    lines = out.strip().splitlines()
    assert lines[-4].startswith("verified 5 blocks (cpu); ")
    assert lines[-2].endswith(f"/ {n} blocks; {n - 4} broken")


def test_never_more_than_threads_gets_at_once_and_more_than_one(
        vol, capsys, tmp_path, monkeypatch):
    from juicefs_tpu.chunk.cached_store import CachedStore

    lock = threading.Lock()
    running = peak = 0
    load = CachedStore._load_block

    def counted(self, *a, **kw):
        nonlocal running, peak
        with lock:
            running += 1
            peak = max(peak, running)
        try:
            time.sleep(0.01)
            return load(self, *a, **kw)
        finally:
            with lock:
                running -= 1

    monkeypatch.setattr(CachedStore, "_load_block", counted)
    rc, stats, index, _ = scrub(vol, "cpu", capsys, tmp_path, threads=3)
    assert rc == 0 and index == vol.want
    assert 1 < peak <= 3
    assert stats["stage_seconds"]["get_threads"] > stats["stage_seconds"]["get"]


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_the_stream_is_announced(vol, backend, capsys, tmp_path, monkeypatch):
    """`fsck` builds its pipeline when it opens the volume and calls
    prepare() there: with the buffers given the time a real listing gives
    them, no pack of the scrub finds its buffer unready
    (`tpu.pack_unready_share` reads 0), and nothing is prepared past the
    invocation."""
    from juicefs_tpu.tpu.pipeline import HashPipeline
    from test_pack_prepare import no_preparer_runs, wait_ready

    announced = []
    prepare = HashPipeline.prepare

    def prepare_and_wait(self):
        prepare(self)
        announced.append(wait_ready(self))

    monkeypatch.setattr(HashPipeline, "prepare", prepare_and_wait)
    before = series("juicefs_tpu_pack_")
    assert scrub(vol, backend, capsys, tmp_path)[0] == 0
    after = series("juicefs_tpu_pack_")
    assert len(announced) == 1 and len(announced[0]) == 2
    assert after["juicefs_tpu_pack_fresh_bytes"] > before["juicefs_tpu_pack_fresh_bytes"]
    assert (after["juicefs_tpu_pack_unready_bytes"]
            == before["juicefs_tpu_pack_unready_bytes"])
    assert no_preparer_runs()


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_a_batchs_dispatch_is_pack_h2d_enqueue_on_either_kernel(
        vol, backend, capsys, tmp_path):
    """The single-device path (the Pallas kernel) makes its transfer and its
    jitted call two steps under the spans the plane's path has."""
    stages = ("dispatch", "pack", "h2d", "enqueue", "drain")
    before = {s: stage_count("tpu", "hash", s) for s in stages}
    assert scrub(vol, backend, capsys, tmp_path)[0] == 0
    # 17 blocks: one batch
    assert {s: stage_count("tpu", "hash", s) - before[s] for s in stages} == {
        s: 1.0 for s in stages}


def test_gc_and_fsck_call_the_one_shared_stage(vol, capsys, tmp_path,
                                               monkeypatch):
    callers = []
    digests = readhash.ReadHash.digests

    def noted(self, items):
        callers.append(sys._getframe(1).f_globals["__name__"])
        return digests(self, items)

    monkeypatch.setattr(readhash.ReadHash, "digests", noted)
    m, _ = open_meta(vol.meta_url)
    m.delete_block_digests([(sid, indx) for sid, indx, _, _ in m.scan_block_digests()])
    m.close_session()
    assert main(["gc", vol.meta_url, "--dedup", "--hash-backend", "cpu"]) == 0
    assert scrub(vol, "cpu", capsys, tmp_path)[0] == 0
    assert callers == ["juicefs_tpu.cmd.gc", "juicefs_tpu.cmd.fsck"]
    # a pass of `sync --check-all --hash-backend` is the third caller
    from juicefs_tpu.object import create_storage

    for side in ("src", "dst"):
        bucket = create_storage(f"file://{tmp_path}/{side}/")
        bucket.create()
        bucket.put("k", b"the same on both sides")
    assert main(["sync", f"file://{tmp_path}/src/", f"file://{tmp_path}/dst/",
                 "--check-all", "--hash-backend", "cpu"]) == 0
    assert callers[2:] == ["juicefs_tpu.cmd.sync"]
    # and none keeps a copy of the stage's steps (sync: tests/test_sync_hash.py)
    for mod in (gc, fsck):
        with open(mod.__file__) as f:
            source = f.read()
        assert "fetch_ordered" not in source and "hash_stream" not in source
        assert "_load_block" not in source


def test_the_scrub_is_one_trace_and_the_socket_path_counts_its_round_trips(
        vol, capsys, tmp_path):
    stages = ("open", "list", "index_load", "verify", "report", "total")
    before = {s: stage_count("cmd", "fsck", s) for s in stages}
    trips = stage_count("meta", "kv", "roundtrip")
    assert scrub(vol, "cpu", capsys, tmp_path)[0] == 0
    assert {s: stage_count("cmd", "fsck", s) - before[s] for s in stages} == {
        s: 1.0 for s in stages}
    gained = stage_count("meta", "kv", "roundtrip") - trips
    if vol.engine == "redis":
        assert 5 <= gained < 100  # a handful of scans, never one a block
    else:
        assert gained == 0  # sqlite3 and the in-process KV pay nothing


def test_plain_fsck_hashes_nothing_and_prints_no_stats(vol, capsys):
    capsys.readouterr()
    before = stage_count("cmd", "fsck", "verify")
    assert main(["fsck", vol.meta_url]) == 0
    out = capsys.readouterr().out
    assert "{" not in out and out.strip().endswith("0 broken")
    assert stage_count("cmd", "fsck", "verify") == before
