"""The envelope opened by libjfscore (`native.EnvelopeKey`) against the
`cryptography` package's open of the same bytes: the same plaintext for every
size and RSA key size, the same reason for every part of the envelope
altered or cut, and the interpreter lock left free while it opens. Without
libjfscore or libcrypto every open falls back to `cryptography`."""

import os
import struct
import threading
import time

import pytest

from juicefs_tpu import native
from juicefs_tpu.metric import global_registry
from juicefs_tpu.object import CorruptObjectError, MemStorage, new_encrypted
from juicefs_tpu.object.encrypt import _Unopened, generate_rsa_key_pem

HAVE_OPEN = native.crypto_available()
needs_open = pytest.mark.skipif(not HAVE_OPEN, reason="libjfscore or libcrypto unavailable")
SIZES = [0, 1, 100_001, 64 << 10, 4 << 20, (4 << 20) + 7]
NATIVE = 'juicefs_object_decrypt_opens{algo="aes256gcm-rsa",how="native"}'
PYTHON = 'juicefs_object_decrypt_opens{algo="aes256gcm-rsa",how="python"}'
_PEMS = {}


def pem(bits):
    if bits not in _PEMS:
        _PEMS[bits] = generate_rsa_key_pem(bits)
    return _PEMS[bits]


def stored(bits, data):
    """-> (encrypted store, the raw envelope `put` wrote for `data`)"""
    inner = MemStorage()
    store = new_encrypted(inner, pem(bits))
    store.put("k", data)
    return store, bytes(inner.get("k"))


class Python:
    """The `cryptography` open of an encryptor: its native key hidden."""

    def __init__(self, enc):
        self.enc = enc

    def __enter__(self):
        self.held, self.enc._native = self.enc._native, None
        return self.enc

    def __exit__(self, *exc):
        self.enc._native = self.held


def reason(enc, blob):
    """-> the plaintext, or the reason the envelope did not open"""
    try:
        return enc.decrypt(blob)
    except _Unopened as e:
        return e.reason


def opens():
    return {line.rpartition(" ")[0]: float(line.rpartition(" ")[2])
            for line in global_registry().render().splitlines()
            if line.startswith("juicefs_object_decrypt_opens{")}


@needs_open
@pytest.mark.parametrize("bits", [2048, 4096])
@pytest.mark.parametrize("size", SIZES)
def test_the_native_open_equals_the_cryptography_open(size, bits):
    data = os.urandom(size)
    store, blob = stored(bits, data)
    enc = store._e
    assert enc._native is not None
    got = enc.decrypt(blob)
    assert type(got) is bytes and got == data
    with Python(enc):
        assert enc.decrypt(blob) == got


def _altered(blob, klen, region):
    raw = bytearray(blob)
    at = {"header": 2, "key": 4 + 10, "nonce": 4 + klen + 3,
          "body": 4 + klen + 12 + 1000, "tag": len(raw) - 1}
    cut = {"cut_in_header": 2, "cut_after_header": 4, "cut_after_key": 4 + klen,
           "cut_after_nonce": 4 + klen + 12, "cut_before_tag": len(raw) - 16,
           "cut_in_tag": len(raw) - 5}
    if region in at:
        raw[at[region]] ^= 0x10
        return bytes(raw)
    return bytes(raw[:cut[region]])


WANT = {"header": "header", "key": "key", "nonce": "tag", "body": "tag", "tag": "tag",
        "cut_in_header": "header", "cut_after_header": "header",
        "cut_after_key": "header", "cut_after_nonce": "header",
        "cut_before_tag": "tag", "cut_in_tag": "tag"}


@needs_open
@pytest.mark.parametrize("region", list(WANT) + ["other_volume_key", "other_key_size"])
def test_an_altered_envelope_fails_for_the_same_reason_both_ways(region):
    """Each part altered, the object cut at each boundary, or opened under
    another volume's key (of the same size: the key does not unwrap; of
    another size: the length header does not fit it)."""
    store, blob = stored(2048, os.urandom(50_000))
    enc = store._e
    if region == "other_volume_key":
        enc = new_encrypted(MemStorage(), generate_rsa_key_pem(2048))._e
        want = "key"
    elif region == "other_key_size":
        enc = new_encrypted(MemStorage(), pem(4096))._e
        want = "header"
    else:
        blob, want = _altered(blob, 256, region), WANT[region]
    assert enc._native is not None
    assert reason(enc, blob) == want
    with Python(enc):
        assert reason(enc, blob) == want


@needs_open
def test_an_envelope_in_the_upstream_format_opens_natively():
    """An envelope made by hand as upstream's `encrypt.go` and this program
    before the native open wrote it: be32 len || RSA-OAEP(SHA-256) data key
    || nonce || AES-256-GCM body || tag."""
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import padding
    from cryptography.hazmat.primitives.ciphers.aead import AESGCM

    key = serialization.load_pem_private_key(pem(2048), None)
    dk, nonce, data = os.urandom(32), os.urandom(12), os.urandom(300_007)
    wrapped = key.public_key().encrypt(dk, padding.OAEP(
        mgf=padding.MGF1(hashes.SHA256()), algorithm=hashes.SHA256(), label=None))
    blob = struct.pack(">I", len(wrapped)) + wrapped + nonce + \
        AESGCM(dk).encrypt(nonce, data, None)
    inner = MemStorage()
    store = new_encrypted(inner, pem(2048))
    inner.put("k", blob)
    before = opens()
    assert store.get("k") == data
    assert opens()[NATIVE] == before[NATIVE] + 1


def _longest_gap(open_once):
    """-> (the call's seconds, the longest stretch within it in which a
    counting thread could not advance)"""
    stamps, stop = [], threading.Event()

    def count():
        while not stop.is_set():
            stamps.append(time.perf_counter())

    counter = threading.Thread(target=count)
    counter.start()
    while len(stamps) < 1000:
        time.sleep(0.001)
    t0 = time.perf_counter()
    open_once()
    t1 = time.perf_counter()
    stop.set()
    counter.join()
    inside = [t0] + [t for t in stamps if t0 < t < t1] + [t1]
    return t1 - t0, max(b - a for a, b in zip(inside, inside[1:]))


@needs_open
def test_the_native_open_leaves_the_interpreter_lock_free():
    """A thread counting in a Python loop advances while one 64 MiB envelope
    opens natively; while `cryptography` opens it, it cannot: that open
    holds the lock for the whole body. (A few tries for the native side: a
    loaded machine may not schedule the counter at once.)"""
    data = os.urandom(64 << 20)
    store, blob = stored(2048, data)
    enc = store._e
    with Python(enc):
        took, gap = _longest_gap(lambda: enc.decrypt(blob))
    assert gap > 0.5 * took, (took, gap)
    for _ in range(5):
        took, gap = _longest_gap(lambda: enc.decrypt(blob))
        if gap < 0.5 * took:
            break
    assert gap < 0.5 * took, (took, gap)


@pytest.mark.parametrize("missing", ["libcrypto", "libjfscore"])
def test_without_the_library_every_open_is_the_cryptography_one(monkeypatch, missing):
    """libcrypto under a name that does not resolve, or no libjfscore at
    all: `crypto_available()` answers False without raising, no key is held
    natively, and the store writes and opens as before."""
    monkeypatch.setattr(native, "_crypto", None)
    if missing == "libcrypto":
        monkeypatch.setattr(native, "_LIBCRYPTO", "/nonexistent/libcrypto.so.3")
        if native.available():  # the library loads; only libcrypto is absent
            assert native._load().jfs_crypto_available(native._LIBCRYPTO.encode()) == 0
    else:
        monkeypatch.setattr(native, "_load", lambda: None)
    assert native.crypto_available() is False
    assert native.envelope_key(b"\x30") is None
    store = new_encrypted(MemStorage(), pem(2048))
    assert store._e._native is None
    before = opens()
    data = os.urandom(100_001)
    store.put("k", data)
    assert store.get("k") == data
    gained = {k: v - before.get(k, 0.0) for k, v in opens().items()}
    assert (gained[NATIVE], gained[PYTHON]) == (0.0, 1.0)
    rotten = bytearray(store._s.get("k"))
    rotten[-1] ^= 1
    store._s.put("k", bytes(rotten))
    with pytest.raises(CorruptObjectError) as raised:
        store.get("k")
    assert raised.value.reason == "tag"


def test_opens_count_by_how_where_each_ran():
    """Both series read from import on; an open counts once, under `native`
    where libjfscore opened it and `python` where `cryptography` did."""
    assert {NATIVE, PYTHON} <= set(opens())
    data = os.urandom(5000)
    store, blob = stored(2048, data)
    before = opens()
    assert store._e.decrypt(blob) == data
    with Python(store._e):
        assert store._e.decrypt(blob) == data
    gained = {k: v - before.get(k, 0.0) for k, v in opens().items()}
    assert gained[NATIVE] == (1.0 if HAVE_OPEN else 0.0)
    assert gained[PYTHON] == (1.0 if HAVE_OPEN else 2.0)


def test_a_caller_that_comes_while_the_library_loads_waits_for_it(monkeypatch):
    """Two threads ask for the library while the first still builds and
    loads it: both get it. (A second caller once took "none yet" for
    "none", and `crypto_available` kept that answer for the process: a
    scrub whose first store open raced the pack buffers' preparer opened
    every envelope by `cryptography`.)"""
    lib, started = object(), threading.Event()

    def slow_open():  # spins: the lock watchdog refuses a sleep under a lock
        started.set()
        end = time.perf_counter() + 0.2
        while time.perf_counter() < end:
            pass
        return lib

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_open", slow_open)
    got = []
    first = threading.Thread(target=lambda: got.append(native._load()))
    first.start()
    started.wait()
    got.append(native._load())
    first.join()
    assert got == [lib, lib]

