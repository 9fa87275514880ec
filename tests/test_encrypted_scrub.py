"""The scrub of a volume encrypted at rest: `format --encrypt-rsa-key`, then
`fsck --verify-data` opens every block's envelope before it hashes the
plaintext. Over the GCM body (the default `aes256gcm-rsa`), the CTR body and
an EC key, on the XLA program: the digests are the numpy spec's of the
plaintext and `gc --dedup`'s over the same plan unencrypted. An envelope that
does not open (`object/encrypt.py`) is a `CorruptObjectError`: one GET
attempt, no retry, nothing charged to the breaker, and the scrub reports that
block as `corrupt` and no other. The decrypt's span and counters read where
an envelope was opened and nowhere else. The GCM body under an RSA key opens
in libjfscore where the host has it; each of its cases runs once more with
that open made unavailable, on the `cryptography` package's."""

import gc
import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import pytest

from benchmark.lib import jth256_spec, volume, volume_encrypted
from benchmark.lib.plan import block_bytes, make_plan
from juicefs_tpu import native
from juicefs_tpu.cmd import main
from juicefs_tpu.metric import global_registry
from juicefs_tpu.metric.trace import global_tracer
from juicefs_tpu.object import (CorruptObjectError, FileStorage, MemStorage, new_encrypted,
                                resilient)
from juicefs_tpu.object.encrypt import generate_ec_key_pem, generate_rsa_key_pem

SEED = 2**31 + 44
BLOCK = 64 << 10
DEPLOYMENT = {"storage": "file", "block_bytes": BLOCK, "compression": "none"}
# (format's --encrypt-algo, the key's kind)
CIPHERS = [("aes256gcm-rsa", "rsa"), ("aes256ctr-rsa", "rsa"), ("aes256gcm-rsa", "ec")]
# and the GCM body under an RSA key opened by `cryptography` (a third item
# "python": libjfscore's open made unavailable)
OPENS = CIPHERS + [("aes256gcm-rsa", "rsa", "python")]
HAVE_OPEN = native.crypto_available()
KEYS = {"rsa": lambda: generate_rsa_key_pem(2048), "ec": generate_ec_key_pem}
DECRYPT_SERIES = (
    'juicefs_tpu_stage_seconds_count{layer="object",op="get",stage="decrypt"}',
    'juicefs_tpu_stage_seconds_count{layer="object",op="get",stage="unwrap"}',
    "juicefs_object_decrypt_cpu_seconds_count",
)


def series(prefix):
    """Every series of the registry's exposition text that starts so."""
    return {line.rpartition(" ")[0]: float(line.rpartition(" ")[2])
            for line in global_registry().render().splitlines()
            if line.startswith(prefix)}


def gained(before, prefix):
    return {k: v - before.get(k, 0.0) for k, v in series(prefix).items()
            if v - before.get(k, 0.0)}


def decrypts(before):
    """Envelopes opened since `before`: (decrypt spans, unwraps, CPU
    observations), each summed over its labels."""
    now = series("juicefs_")
    return tuple(sum(v - before.get(k, 0.0) for k, v in now.items()
                     if k.startswith(s)) for s in DECRYPT_SERIES)


def how_it_opens(monkeypatch, cipher):
    """-> how `cipher`'s envelopes open from here on: natively for a GCM
    body under an RSA key where the host can, unless the cipher says
    "python" (then the native open is made unavailable); by `cryptography`
    otherwise."""
    if cipher[2:] == ("python",):
        monkeypatch.setattr(native, "crypto_available", lambda: False)
        return "python"
    return "native" if cipher[:2] == ("aes256gcm-rsa", "rsa") and HAVE_OPEN else "python"


def opened_how(before):
    """The `how` of every open since `before`."""
    return {k.split('how="')[1].split('"')[0]
            for k in gained(before, "juicefs_object_decrypt_opens")}


def the_plan():
    """13 blocks of up to 64 KiB: two objects of four, duplicates from a
    pool of two, and the ragged handful."""
    return make_plan(SEED, 2, block=BLOCK, object_blocks=4, pool_blocks=2,
                     dup_probability=0.4,
                     ragged_sizes=(1, 10_001, BLOCK - 1, BLOCK + 7))


class Volume:
    def __init__(self, workdir, meta_url, plan, cipher=()):
        self.workdir, self.meta_url, self.cipher = workdir, meta_url, cipher
        _, self.block_of = volume.wait(_Done(), workdir, plan)
        ref = {b.content: jth256_spec.jth256(block_bytes(SEED, b)).hex()
               for b in self.block_of.values()}
        self.want = {key: ref[b.content] for key, b in self.block_of.items()}

    def path(self, key):
        return os.path.join(self.workdir, "blob", "benchvol", key)

    def by_content(self, index):
        return {self.block_of[k].content: d for k, d in index.items()}


class _Done:
    returncode = 0

    def wait(self):
        return 0


@pytest.fixture(scope="module", params=OPENS, ids="-".join)
def encrypted(request, tmp_path_factory):
    algo, kind = request.param[:2]
    workdir = str(tmp_path_factory.mktemp("enc-" + "-".join(request.param)))
    key_file = os.path.join(workdir, "key.pem")
    with open(key_file, "wb") as f:
        f.write(KEYS[kind]())
    meta_url = f"sqlite3://{workdir}/meta.db"
    volume_encrypted.build(workdir, the_plan(), dict(DEPLOYMENT, encryption=algo),
                           meta_url, key_file)
    return Volume(workdir, meta_url, the_plan(), request.param)


@pytest.fixture(scope="module")
def plain(tmp_path_factory):
    workdir = str(tmp_path_factory.mktemp("plain"))
    volume.build(workdir, the_plan(), dict(DEPLOYMENT, meta="sqlite3"))
    return Volume(workdir, f"sqlite3://{workdir}/meta.db", the_plan())


def scrub(vol, capsys, tmp_path):
    """-> (exit code, stats line, hash index)"""
    index = str(tmp_path / "scrub.json")
    capsys.readouterr()
    rc = main(["fsck", vol.meta_url, "--verify-data", "--hash-index", index,
               "--hash-backend", "xla", "--threads", "3"])
    out = capsys.readouterr().out
    with open(index) as f:
        return rc, json.loads(out.strip().splitlines()[-1]), json.load(f)


def dedup_index(vol, tmp_path, name):
    path = str(tmp_path / name)
    assert main(["gc", vol.meta_url, "--dedup", "--hash-backend", "xla",
                 "--dedup-index", path, "--threads", "3"]) == 0
    with open(path) as f:
        return json.load(f)


def test_the_scrub_hashes_the_plaintext_and_opens_every_envelope(
        encrypted, plain, capsys, tmp_path, monkeypatch):
    stored = [open(encrypted.path(k), "rb").read() for k in sorted(encrypted.want)]
    planned = [block_bytes(SEED, encrypted.block_of[k]) for k in sorted(encrypted.want)]
    assert all(raw != want and (len(want) < 4096 or want[:4096] not in raw)
               for raw, want in zip(stored, planned))

    enc = dedup_index(encrypted, tmp_path, "enc.json")
    assert enc == encrypted.want
    assert encrypted.by_content(enc) == plain.by_content(
        dedup_index(plain, tmp_path, "plain.json"))

    how = how_it_opens(monkeypatch, encrypted.cipher)
    before = series("juicefs_")
    rc, stats, index = scrub(encrypted, capsys, tmp_path)
    n = len(encrypted.want)
    assert opened_how(before) == {how}
    assert rc == 0 and index == encrypted.want
    assert (stats["verified"], stats["mismatches"], stats["broken"],
            stats["corrupt"]) == (n, 0, 0, 0)
    spans, unwraps, cpu = decrypts(before)
    assert spans == unwraps == cpu == n
    assert gained(before, "juicefs_object_corrupt") == {}


def test_an_unencrypted_scrub_opens_no_envelope(plain, capsys, tmp_path):
    dedup_index(plain, tmp_path, "plain.json")
    before = series("juicefs_")
    rc, stats, index = scrub(plain, capsys, tmp_path)
    assert rc == 0 and index == plain.want and stats["corrupt"] == 0
    assert decrypts(before) == (0, 0, 0)
    assert gained(before, "juicefs_object_corrupt") == {}


def test_the_tampered_block_alone_is_reported_corrupt(
        encrypted, capsys, tmp_path, caplog, monkeypatch):
    dedup_index(encrypted, tmp_path, "enc.json")
    how = how_it_opens(monkeypatch, encrypted.cipher)
    key = sorted(k for k, b in encrypted.block_of.items() if b.size > 1000)[3]
    path = encrypted.path(key)
    with open(path, "rb") as f:
        sound = f.read()
    rotten = bytearray(sound)
    rotten[len(rotten) // 2] ^= 0x10
    with open(path, "wb") as f:
        f.write(rotten)
    before = series("juicefs_")
    try:
        with caplog.at_level("ERROR", logger="cmd.fsck"):
            rc, stats, index = scrub(encrypted, capsys, tmp_path)
    finally:
        with open(path, "wb") as f:
            f.write(sound)
    n = len(encrypted.want)
    assert opened_how(before) == {how}
    assert rc == 1
    assert (stats["verified"], stats["mismatches"], stats["broken"],
            stats["corrupt"]) == (n - 1, 0, 1, 1)
    assert [r.getMessage() for r in caplog.records] == [f"block {key} corrupt: tag"]
    assert index == {k: d for k, d in encrypted.want.items() if k != key}
    blocks = {k.split('"')[1]: v for k, v in
              gained(before, "juicefs_fsck_blocks").items()}
    assert blocks == {"verified": n - 1, "corrupt": 1}
    assert gained(before, "juicefs_object_corrupt") == {
        'juicefs_object_corrupt{backend="file",reason="tag"}': 1.0}
    assert gained(before, "juicefs_object_request_retries") == {}
    # put back, the volume is sound again
    assert scrub(encrypted, capsys, tmp_path)[0] == 0


class _Counted(FileStorage):
    """A file:// store that counts the GETs that reach it."""

    gets = 0

    def get(self, key, off=0, limit=-1):
        self.gets += 1
        return super().get(key, off, limit)


def _region_at(algo, kind, size, region):
    """Offset of one byte of `region` in a stored object of `size` bytes."""
    base = 8 if "ctr" in algo else 0  # the CRC32C header below a CTR body
    wrapped = 256 if kind == "rsa" else 125
    nonce = 16 if "ctr" in algo else 12
    return {"header": base + 2, "key": base + 4 + 10,
            "nonce": base + 4 + wrapped + 3,
            "body": base + 4 + wrapped + nonce + 1000,
            "tag": size - 1}[region]


@pytest.mark.parametrize("region", ["header", "key", "nonce", "body", "tag", "cut"])
@pytest.mark.parametrize("cipher", OPENS, ids="-".join)
def test_an_envelope_that_does_not_open_costs_one_attempt(tmp_path, cipher, region,
                                                          monkeypatch):
    """Each part of the envelope altered, or the object as stored cut short:
    one GET attempt, a CorruptObjectError naming the key and the check that
    failed, no retry and no failure for the breaker. A CTR body has no tag
    of its own: its CRC32C fails first, whatever was altered."""
    algo, kind = cipher[:2]
    how = how_it_opens(monkeypatch, cipher)
    inner = _Counted(str(tmp_path / "store"))
    store = resilient(new_encrypted(inner, KEYS[kind](), algo=algo.split("-")[0]))
    data = os.urandom(50_000)
    store.put("k", data)
    assert bytes(store.get("k")) == data
    path = os.path.join(inner.root, "k")
    with open(path, "rb") as f:
        raw = bytearray(f.read())
    if region == "cut":
        raw = raw[:40]
    else:
        raw[_region_at(algo, kind, len(raw), region)] ^= 0x10
    with open(path, "wb") as f:
        f.write(raw)
    inner.gets = 0
    before = series("juicefs_object_")
    with pytest.raises(CorruptObjectError) as raised:
        store.get("k")
    assert opened_how(before) == (set() if "ctr" in algo else {how})
    want = "tag" if "ctr" in algo else {"header": "header", "cut": "header",
                                        "key": "key"}.get(region, "tag")
    assert (raised.value.key, raised.value.reason) == ("k", want)
    assert inner.gets == 1
    assert store.breaker.snapshot()["window_failure_rate"] == 0.0
    assert gained(before, "juicefs_object_request_retries") == {}
    store.close()


class _ShortOnce(_Counted):
    """A file:// store whose first GET arrives cut to `keep` bytes."""

    def __init__(self, root, keep):
        super().__init__(root)
        self.keep = keep

    def get(self, key, off=0, limit=-1):
        data = super().get(key, off, limit)
        return data[:self.keep(len(data))] if self.gets == 1 else data


@pytest.mark.parametrize("cut", ["in_header", "in_body"])
@pytest.mark.parametrize("cipher", OPENS, ids="-".join)
def test_a_transfer_cut_short_is_retried_not_corrupt(tmp_path, cipher, cut, monkeypatch):
    """A sound object whose first GET arrives cut short fails to open, but
    fewer bytes arrived than the store holds: a transient fault, retried
    once, and the second GET opens; nothing counts as corrupt."""
    algo, kind = cipher[:2]
    how = how_it_opens(monkeypatch, cipher)
    keep = {"in_header": lambda n: 40, "in_body": lambda n: n - 100}[cut]
    inner = _ShortOnce(str(tmp_path / "store"), keep)
    store = resilient(new_encrypted(inner, KEYS[kind](), algo=algo.split("-")[0]))
    data = os.urandom(50_000)
    store.put("k", data)
    before = series("juicefs_object_")
    assert bytes(store.get("k")) == data
    assert inner.gets == 2
    assert sum(gained(before, "juicefs_object_request_retries").values()) == 1
    assert gained(before, "juicefs_object_corrupt") == {}
    assert opened_how(before) == {how}
    store.close()


class _Kept(MemStorage):
    """An in-memory store that hands out the very bytes object it holds."""

    def get(self, key, off=0, limit=-1):
        return self._data[key][0]


def test_a_scrub_keeps_no_bytes_of_a_corrupt_object(monkeypatch):
    """After the stage is drained, what it says of a corrupt object is text:
    no error, no traceback, nothing that holds the fetched bytes."""
    _keeps_no_bytes_of_a_corrupt_object(monkeypatch, CIPHERS[0])


def test_a_scrub_keeps_no_bytes_of_a_corrupt_object_opened_by_cryptography(monkeypatch):
    _keeps_no_bytes_of_a_corrupt_object(monkeypatch, OPENS[-1])


def _keeps_no_bytes_of_a_corrupt_object(monkeypatch, cipher):
    from juicefs_tpu.cmd.readhash import ReadHash, scan_pipeline

    how = how_it_opens(monkeypatch, cipher)
    inner = _Kept()
    store = new_encrypted(inner, KEYS["rsa"]())
    for key in ("a", "b", "c"):
        store.put(key, os.urandom(5000))
    sound, mtime = inner._data["b"]
    rotten = bytearray(sound)
    rotten[-1] ^= 0x10
    blob = bytes(rotten)
    inner._data["b"] = (blob, mtime)
    held = sys.getrefcount(blob)
    before = series("juicefs_object_decrypt_opens")
    with ThreadPoolExecutor(2) as pool:
        stage = ReadHash(store.get, pool, scan_pipeline("xla", 8192), threads=2)
        got = dict(stage.digests(["a", "b", "c"]))
    gc.collect()
    assert set(got) == {"a", "c"}
    assert stage.corrupt == {"b": "tag"}
    assert all(type(v) is str for v in stage.failed.values())
    assert sys.getrefcount(blob) == held
    assert opened_how(before) == {how}


def test_the_open_is_a_span_with_its_bytes_and_algorithm(tmp_path, monkeypatch):
    _the_open_is_a_span(tmp_path, monkeypatch, CIPHERS[0])


def test_the_open_is_a_span_with_its_bytes_and_algorithm_by_cryptography(
        tmp_path, monkeypatch):
    _the_open_is_a_span(tmp_path, monkeypatch, OPENS[-1])


def _the_open_is_a_span(tmp_path, monkeypatch, cipher):
    how = how_it_opens(monkeypatch, cipher)
    store = new_encrypted(FileStorage(str(tmp_path / "store")), KEYS["rsa"]())
    store.put("k", b"x" * 5000)
    tr, reader = global_tracer(), ("test", "decrypt")
    tr.open_reader(reader)
    before = series("juicefs_object_decrypt_opens")
    try:
        assert store.get("k") == b"x" * 5000
        events = [json.loads(line) for line in
                  tr.read(reader, 1 << 20).decode().splitlines()]
    finally:
        tr.close_reader(reader)
    ev, = [e for e in events if (e["layer"], e["op"]) == ("object", "decrypt")]
    assert (ev["bytes"], ev["algo"]) == (5000, "aes256gcm-rsa")
    assert opened_how(before) == {how}
