"""Per-object CRC32C transfer checksums (reference: pkg/object/checksum.go:28-88).

The reference attaches a CRC32C of the body as request metadata and verifies
on full-object GET. Here the wrapper stores `crc32c(body)` in a 4-byte
trailer-less sidecar encoding: checksum prepended into an 8-byte header
(magic + crc) so any store can carry it. Ranged reads skip verification,
matching the reference (it only checks full-object reads).
"""

from __future__ import annotations

import struct
from typing import Iterator

from .interface import NotFoundError, Obj, ObjectStorage

_MAGIC = 0x4A464353  # "JFCS"
_HDR = struct.Struct(">II")  # magic, crc32c


def _make_table() -> list[int]:
    poly = 0x82F63B78  # Castagnoli, reflected
    table = []
    for n in range(256):
        c = n
        for _ in range(8):
            c = (c >> 1) ^ poly if c & 1 else c >> 1
        table.append(c)
    return table


_TABLE = _make_table()


class ChecksumMismatchError(IOError):
    """A full-object GET whose body does not match its stored CRC32C;
    `got` is the length of the body that arrived."""

    def __init__(self, key: str, got: int):
        super().__init__(f"checksum mismatch for {key}")
        self.got = got


def crc32c_py(data: bytes, crc: int = 0) -> int:
    """Pure-Python CRC32C (Castagnoli) — the spec/fallback implementation,
    byte-identical to the reference's hash (checksum.go crc32.Castagnoli)."""
    c = crc ^ 0xFFFFFFFF
    for b in data:
        c = _TABLE[(c ^ b) & 0xFF] ^ (c >> 8)
    return c ^ 0xFFFFFFFF


def crc32c(data: bytes, crc: int = 0) -> int:
    """CRC32C via the native library (SSE4.2); native falls back to
    crc32c_py itself when no toolchain is available."""
    from .. import native

    return native.crc32c(data, crc)


class _Checksummed(ObjectStorage):
    def __init__(self, store: ObjectStorage):
        self._s = store

    @property
    def host_local(self) -> bool:
        return getattr(self._s, "host_local", False)

    def string(self) -> str:
        return self._s.string()

    def create(self) -> None:
        self._s.create()

    def put(self, key, data):
        self._s.put(key, _HDR.pack(_MAGIC, crc32c(data)) + data)

    def get(self, key, off=0, limit=-1):
        if off == 0 and limit < 0:
            raw = self._s.get(key)
            if len(raw) >= _HDR.size:
                magic, crc = _HDR.unpack_from(raw)
                if magic == _MAGIC:
                    body = raw[_HDR.size:]
                    if crc32c(body) != crc:
                        raise ChecksumMismatchError(key, len(body))
                    return body
            return raw  # legacy/unwrapped object
        # ranged read: shift past header, skip verification (reference behavior)
        return self._s.get(key, off + _HDR.size, limit)

    def delete(self, key):
        self._s.delete(key)

    def head(self, key) -> Obj:
        o = self._s.head(key)
        return Obj(key=o.key, size=max(o.size - _HDR.size, 0), mtime=o.mtime, is_dir=o.is_dir)

    def list_all(self, prefix: str = "", marker: str = "") -> Iterator[Obj]:
        for o in self._s.list_all(prefix, marker):
            yield Obj(key=o.key, size=max(o.size - _HDR.size, 0), mtime=o.mtime, is_dir=o.is_dir)


def new_checksummed(store: ObjectStorage) -> ObjectStorage:
    return _Checksummed(store)
