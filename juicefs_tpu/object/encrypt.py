"""Envelope encryption wrapper (reference: pkg/object/encrypt.go:136-216).

Scheme (same shape as the reference):
  per-object random 256-bit data key + nonce
  body  = AES-256-GCM(data_key, nonce, plaintext)
  object = len(wrapped_key) || wrapped_key || nonce || body
  wrapped_key = RSA-OAEP(public_key, data_key)

The RSA key pair is the volume's master key (PEM, optionally password
protected — reference encrypt.go:66-123 ParseRsaPrivateKeyFromPem).

An envelope that does not open — a length header that does not fit the
object, a data key that does not unwrap, a body that fails its tag (for the
CTR body: its CRC32C) — raises `CorruptObjectError` where what arrived is the
whole object as its store sizes it: the object as stored is wrong, so the
resilience layer never retries it. Where another number of bytes arrived,
the transfer was cut: a plain IOError, retried. Each open is the span
`object.decrypt` (`juicefs_tpu_stage_seconds{layer="object",op="get",
stage="decrypt"}`), the unwrap alone a stamp (`stage="unwrap"`), its thread
CPU time `juicefs_object_decrypt_cpu_seconds{algo}`; an envelope that did not
open counts in `juicefs_object_corrupt{backend,reason}`.

Where libjfscore found OpenSSL's libcrypto, a GCM body under an RSA key opens
in one native call outside the interpreter lock (`native.EnvelopeKey`), so
GET threads open side by side; the `cryptography` package opens the rest (the
CTR body, the ECIES key, a host without the library) and writes every
envelope. Each open counts in `juicefs_object_decrypt_opens{algo,how}`.
"""

from __future__ import annotations

import os
import struct
import time
from typing import Iterator

try:
    from cryptography.exceptions import InvalidTag
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import padding, rsa
    from cryptography.hazmat.primitives.ciphers.aead import AESGCM

    HAVE_CRYPTOGRAPHY = True
except ImportError:  # gated: encrypted volumes error lazily, everything
    # else (the whole object package imports this module) keeps working
    HAVE_CRYPTOGRAPHY = False
    hashes = serialization = padding = rsa = AESGCM = None

from .. import native
from ..metric import global_registry
from ..metric.trace import global_tracer, stage_hist
from .checksum import ChecksumMismatchError
from .interface import CorruptObjectError, Obj, ObjectStorage

_TR = global_tracer()
_reg = global_registry()
_H_DECRYPT = stage_hist("object", "get", "decrypt")
_H_UNWRAP = stage_hist("object", "get", "unwrap")
# beside the open's wall time, its thread CPU time: the difference is the
# wait for the interpreter lock (the cipher's own calls hold it)
_DECRYPT_CPU = _reg.histogram(
    "juicefs_object_decrypt_cpu_seconds",
    "Thread CPU time of opening one object's envelope",
    ("algo",),
)
_OPENS = _reg.counter(
    "juicefs_object_decrypt_opens",
    "Envelopes opened, by algorithm and by how: native (libjfscore, outside "
    "the interpreter lock) or python (the cryptography package)",
    ("algo", "how"),
)
for _how in ("native", "python"):  # read 0, not missing, before any open
    _OPENS.labels("aes256gcm-rsa", _how)
# jfs_envelope_open's codes for an envelope that did not open
_NATIVE_REASONS = {1: "header", 2: "key", 3: "tag"}
_CORRUPT = _reg.counter(
    "juicefs_object_corrupt",
    "Objects whose envelope did not open, by the check that failed",
    ("backend", "reason"),
)


class _Unopened(Exception):
    """An envelope that does not open; `reason` as CorruptObjectError's."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


def _require_cryptography() -> None:
    if not HAVE_CRYPTOGRAPHY:
        raise RuntimeError(
            "the 'cryptography' package is not installed; encrypted "
            "volumes are unavailable in this environment"
        )


def generate_rsa_key_pem(bits: int = 2048, password: bytes | None = None) -> bytes:
    _require_cryptography()
    key = rsa.generate_private_key(public_exponent=65537, key_size=bits)
    enc = (
        serialization.BestAvailableEncryption(password)
        if password
        else serialization.NoEncryption()
    )
    return key.private_bytes(
        serialization.Encoding.PEM, serialization.PrivateFormat.PKCS8, enc
    )


class RSAEncryptor:
    """Key encryptor: wraps per-object data keys (reference encrypt.go:125-145)."""

    name = "rsa"

    def __init__(self, pem: bytes, password: bytes | None = None,
                 key=None):
        _require_cryptography()
        self._key = key if key is not None else \
            serialization.load_pem_private_key(pem, password)
        # the key held by libjfscore too, for the lock-free open; None
        # where the library or libcrypto is missing
        self.native = native.envelope_key(self._key.private_bytes(
            serialization.Encoding.DER, serialization.PrivateFormat.PKCS8,
            serialization.NoEncryption())) if native.crypto_available() else None
        self._pad = padding.OAEP(
            mgf=padding.MGF1(algorithm=hashes.SHA256()),
            algorithm=hashes.SHA256(),
            label=None,
        )

    def encrypt(self, data_key: bytes) -> bytes:
        return self._key.public_key().encrypt(data_key, self._pad)

    def decrypt(self, wrapped: bytes) -> bytes:
        return self._key.decrypt(wrapped, self._pad)

    @property
    def wrapped_len(self) -> int:
        return self._key.key_size // 8


class AESGCMDataEncryptor:
    """Per-object AES-256-GCM (reference encrypt.go:147-216 dataEncryptor)."""

    NONCE = 12
    TAIL = 16  # the GCM tag: the body is never shorter
    body = "aes256gcm"

    def __init__(self, key_encryptor: RSAEncryptor):
        self._ke = key_encryptor
        self._native = (getattr(key_encryptor, "native", None)
                        if self.body == "aes256gcm" else None)
        self._opens = {how: _OPENS.labels(self.algo, how)
                       for how in ("native", "python")}

    def encrypt(self, plaintext: bytes) -> bytes:
        dk = os.urandom(32)
        nonce = os.urandom(self.NONCE)
        body = AESGCM(dk).encrypt(nonce, plaintext, None)
        wrapped = self._ke.encrypt(dk)
        return struct.pack(">I", len(wrapped)) + wrapped + nonce + body

    @property
    def algo(self) -> str:
        return f"{self.body}-{self._ke.name}"

    def _unwrap(self, blob: bytes) -> tuple[bytes, bytes, bytes]:
        """-> (data key, nonce, body) of an envelope; raises _Unopened."""
        klen = self._ke.wrapped_len
        if (len(blob) < 4 + klen + self.NONCE + self.TAIL
                or struct.unpack_from(">I", blob)[0] != klen):
            raise _Unopened("header")
        wrapped = blob[4 : 4 + klen]
        nonce = blob[4 + klen : 4 + klen + self.NONCE]
        body = memoryview(blob)[4 + klen + self.NONCE :]  # no copy
        t = time.perf_counter()
        try:
            dk = self._ke.decrypt(wrapped)
        except (ValueError, InvalidTag):
            raise _Unopened("key") from None
        if len(dk) != 32:
            raise _Unopened("key")
        _H_UNWRAP.observe(time.perf_counter() - t)
        return dk, nonce, body

    def decrypt(self, blob: bytes) -> bytes:
        if self._native is not None:
            opened = self._native.open(blob, len(blob) - self.overhead)
            if opened is not None:
                self._opens["native"].inc()
                code, data, unwrap_s = opened
                if code in (0, 3):  # the key unwrapped
                    _H_UNWRAP.observe(unwrap_s)
                if code:
                    raise _Unopened(_NATIVE_REASONS[code])
                return data
        self._opens["python"].inc()
        dk, nonce, body = self._unwrap(blob)
        try:
            return AESGCM(dk).decrypt(nonce, body, None)
        except InvalidTag:
            raise _Unopened("tag") from None

    @property
    def overhead(self) -> int:
        # length header + wrapped key + nonce + GCM tag: fixed per volume key
        return 4 + self._ke.wrapped_len + self.NONCE + self.TAIL


class _Encrypted(ObjectStorage):
    def __init__(self, store: ObjectStorage, enc: AESGCMDataEncryptor):
        self._s = store
        self._e = enc
        self._backend = store.string().split("://", 1)[0] or type(store).__name__
        self._cpu = _DECRYPT_CPU.labels(enc.algo)

    @property
    def host_local(self) -> bool:
        return getattr(self._s, "host_local", False)

    def string(self) -> str:
        return self._s.string()

    def create(self) -> None:
        self._s.create()

    def put(self, key, data):
        self._s.put(key, self._e.encrypt(data))

    def _unopened(self, key: str, reason: str, got: int) -> IOError:
        """The error of an envelope that did not open. Corrupt where the
        `got` bytes that arrived are the whole object as stored; a transfer
        cut otherwise, which a second GET may get whole (transient,
        as the chunk layer's TornDataError)."""
        size = self._s.head(key).size
        if got != size:
            return IOError(f"object {key}: {got} bytes arrived of its {size}")
        _CORRUPT.labels(self._backend, reason).inc()
        return CorruptObjectError(key, reason)

    def get(self, key, off=0, limit=-1):
        # ciphertext is not seekable: fetch whole object, slice after decrypt
        # (reference encrypt.go Get does the same). The error is raised
        # outside the handlers, so it drags no frame that held the blob.
        failed = None
        try:
            blob = self._s.get(key)
        except ChecksumMismatchError as e:  # the CTR body's integrity check
            failed = ("tag", e.got)
        else:
            with _TR.span("object", "decrypt", hist=_H_DECRYPT) as sp:
                cpu = time.thread_time()
                try:
                    data = self._e.decrypt(blob)
                except _Unopened as e:
                    failed = (e.reason, len(blob))
                finally:
                    self._cpu.observe(time.thread_time() - cpu)
                if sp.active and failed is None:
                    sp.set(bytes=len(data), algo=self._e.algo)
            del blob
        if failed is not None:
            raise self._unopened(key, *failed)
        if limit < 0:
            return data[off:]
        return data[off : off + limit]

    def delete(self, key):
        self._s.delete(key)

    def head(self, key) -> Obj:
        o = self._s.head(key)
        return Obj(key=o.key, size=max(o.size - self._e.overhead, 0), mtime=o.mtime, is_dir=o.is_dir)

    def list_all(self, prefix: str = "", marker: str = "") -> Iterator[Obj]:
        for o in self._s.list_all(prefix, marker):
            yield Obj(key=o.key, size=max(o.size - self._e.overhead, 0), mtime=o.mtime, is_dir=o.is_dir)


class ECIESEncryptor:
    """EC key encryptor — an EXTENSION of this build, not reference parity
    (reference encrypt.go wraps keys with RSA-OAEP only; it has no ECIES):
    ephemeral-ECDH over P-256 + HKDF-SHA256 derives a wrapping key, the
    data key travels AES-GCM-sealed beside the ephemeral public key.

    wrapped = eph_pub(65B uncompressed) || nonce(12) || GCM(data_key)
    """

    _NONCE = 12
    name = "ecies"

    def __init__(self, pem: bytes, password: bytes | None = None,
                 key=None):
        _require_cryptography()
        from cryptography.hazmat.primitives.asymmetric import ec

        self._key = key if key is not None else \
            serialization.load_pem_private_key(pem, password)
        if not isinstance(self._key, ec.EllipticCurvePrivateKey):
            raise ValueError("ECIES needs an EC private key (P-256 PEM)")
        self._curve = self._key.curve

    def _derive(self, shared: bytes) -> bytes:
        from cryptography.hazmat.primitives.kdf.hkdf import HKDF

        return HKDF(algorithm=hashes.SHA256(), length=32, salt=None,
                    info=b"jfs-ecies-v1").derive(shared)

    def encrypt(self, data_key: bytes) -> bytes:
        from cryptography.hazmat.primitives.asymmetric import ec

        eph = ec.generate_private_key(self._curve)
        shared = eph.exchange(ec.ECDH(), self._key.public_key())
        kek = self._derive(shared)
        nonce = os.urandom(self._NONCE)
        sealed = AESGCM(kek).encrypt(nonce, data_key, None)
        pub = eph.public_key().public_bytes(
            serialization.Encoding.X962,
            serialization.PublicFormat.UncompressedPoint,
        )
        return pub + nonce + sealed

    def decrypt(self, wrapped: bytes) -> bytes:
        from cryptography.hazmat.primitives.asymmetric import ec

        plen = (self._curve.key_size // 8) * 2 + 1  # uncompressed point
        pub = ec.EllipticCurvePublicKey.from_encoded_point(
            self._curve, wrapped[:plen]
        )
        shared = self._key.exchange(ec.ECDH(), pub)
        kek = self._derive(shared)
        nonce = wrapped[plen:plen + self._NONCE]
        sealed = wrapped[plen + self._NONCE:]
        return AESGCM(kek).decrypt(nonce, sealed, None)

    @property
    def wrapped_len(self) -> int:
        # point + nonce + data_key(32) + GCM tag(16)
        return (self._curve.key_size // 8) * 2 + 1 + self._NONCE + 48


def generate_ec_key_pem(password: bytes | None = None) -> bytes:
    _require_cryptography()
    from cryptography.hazmat.primitives.asymmetric import ec

    key = ec.generate_private_key(ec.SECP256R1())
    enc = (
        serialization.BestAvailableEncryption(password)
        if password
        else serialization.NoEncryption()
    )
    return key.private_bytes(
        serialization.Encoding.PEM, serialization.PrivateFormat.PKCS8, enc
    )


class AESCTRDataEncryptor(AESGCMDataEncryptor):
    """AES-256-CTR body variant — an EXTENSION of this build, not reference
    parity (reference encrypt.go offers AEAD bodies only: aes256gcm-rsa and
    chacha20-rsa; no CTR mode exists there). CTR has no per-object auth tag,
    so ciphertext is malleable; `new_encrypted` therefore refuses to build a
    bare-CTR stack and always interposes the CRC32C checksummed wrapper
    between the cipher and the store, so every full-object GET verifies the
    ciphertext before decrypt. That catches corruption and blind bit-flips;
    operators needing cryptographic tamper resistance must use the GCM
    default."""

    NONCE = 16  # full CTR counter block
    TAIL = 0
    body = "aes256ctr"

    def encrypt(self, plaintext: bytes) -> bytes:
        from cryptography.hazmat.primitives.ciphers import Cipher, algorithms, modes

        dk = os.urandom(32)
        nonce = os.urandom(self.NONCE)
        enc = Cipher(algorithms.AES(dk), modes.CTR(nonce)).encryptor()
        body = enc.update(plaintext) + enc.finalize()
        wrapped = self._ke.encrypt(dk)
        return struct.pack(">I", len(wrapped)) + wrapped + nonce + body

    def decrypt(self, blob: bytes) -> bytes:
        from cryptography.hazmat.primitives.ciphers import Cipher, algorithms, modes

        self._opens["python"].inc()
        dk, nonce, body = self._unwrap(blob)
        dec = Cipher(algorithms.AES(dk), modes.CTR(nonce)).decryptor()
        return dec.update(body) + dec.finalize()


def _key_encryptor(pem: bytes, password: bytes | None):
    """RSA or EC PEM -> the matching key encryptor (reference
    encrypt.go:66-123 parses both). One parse: the loaded key object is
    handed to the encryptor (an encrypted PEM's KDF is not cheap)."""
    _require_cryptography()
    key = serialization.load_pem_private_key(pem, password)
    from cryptography.hazmat.primitives.asymmetric import ec

    if isinstance(key, ec.EllipticCurvePrivateKey):
        return ECIESEncryptor(pem, password, key=key)
    return RSAEncryptor(pem, password, key=key)


def new_encrypted(store: ObjectStorage, pem: bytes,
                  password: bytes | None = None,
                  algo: str = "aes256gcm") -> ObjectStorage:
    """Envelope-encrypt a store. algo: aes256gcm (default, reference
    parity) | aes256ctr (extension; forcibly paired with the CRC32C
    checksummed wrapper — see AESCTRDataEncryptor). The key side
    (RSA-OAEP per the reference, or the ECIES extension) follows the
    PEM key type."""
    ke = _key_encryptor(pem, password)
    if algo.startswith("aes256ctr"):
        from .checksum import new_checksummed

        return _Encrypted(new_checksummed(store), AESCTRDataEncryptor(ke))
    return _Encrypted(store, AESGCMDataEncryptor(ke))
