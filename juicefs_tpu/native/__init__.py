"""Native data-plane core: ctypes bindings for libjfscore (C++).

The reference's hot data plane is native (cgo zstd/lz4, hardware CRC32C);
this package is the rebuild's equivalent. The shared library builds on
demand from jfscore.cpp with the system toolchain and is cached next to
the source under a name keyed on the source's SHA-256, so a library that
was not built from THIS jfscore.cpp (a stale copy, a checkout with newer
mtimes) is never loaded. Every entry point has a pure-Python fallback so
the framework still runs on hosts without a compiler — orders of
magnitude slower, which is why `available()` is what chip_smoke.py and
the device report print.

Exports:
    crc32c(data, crc=0)            hardware CRC32C (SSE4.2 when available)
    jth256(data) -> 32B digest     C++ JTH-256, byte-identical to the spec
    jth256_batch(blocks, threads)  multithreaded batch hash
    pack_rows(blocks, rows) -> bool  a hash batch's rows in one call
    touch_pages(arr) -> bool       first-touch an array's pages, lock-free
    available() -> bool
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
from typing import Optional, Sequence

from ..utils import get_logger

logger = get_logger("native")

_DIR = os.path.dirname(__file__)
_PAGE = os.sysconf("SC_PAGE_SIZE")
_SRC = os.path.join(_DIR, "jfscore.cpp")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _so_path() -> str:
    """The library path for the jfscore.cpp on disk: keyed on its content,
    not on mtimes (a copied tree keeps neither order nor meaning of
    those)."""
    with open(_SRC, "rb") as f:
        key = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"libjfscore-{key}.so")


def _build(so: str) -> bool:
    # Build to a per-pid temp name and atomically rename: concurrent
    # processes may both compile, but no one ever loads a half-written .so.
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
        _SRC, "-o", tmp,
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.warning("native build unavailable: %s", e)
        return False
    if proc.returncode != 0:
        logger.warning("native build failed: %s", proc.stderr.decode()[:500])
        return False
    try:
        os.replace(tmp, so)
    except OSError as e:
        logger.warning("native build install failed: %s", e)
        return False
    for old in glob.glob(os.path.join(_DIR, "libjfscore*.so")):
        if old != so:  # libraries of other sources: never loadable again
            try:
                os.unlink(old)
            except OSError:
                pass
    return True


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            so = _so_path()
        except OSError as e:
            logger.warning("native source unreadable: %s", e)
            return None
        if not os.path.exists(so) and not _build(so):
            return None
        try:
            lib = ctypes.CDLL(so)
            lib.jfs_crc32c.restype = ctypes.c_uint32
            lib.jfs_crc32c.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint32,
            ]
            lib.jfs_jth256.restype = None
            lib.jfs_jth256.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
            ]
            lib.jfs_jth256_batch.restype = None
            lib.jfs_jth256_batch.argtypes = [
                ctypes.POINTER(ctypes.c_char_p),
                ctypes.POINTER(ctypes.c_size_t),
                ctypes.c_size_t,
                ctypes.c_char_p,
                ctypes.c_int,
            ]
            lib.jfs_pack_rows.restype = None
            lib.jfs_pack_rows.argtypes = [
                ctypes.POINTER(ctypes.c_char_p),
                ctypes.POINTER(ctypes.c_size_t),
                ctypes.c_size_t,
                ctypes.c_void_p,
                ctypes.c_size_t,
            ]
            lib.jfs_touch_pages.restype = ctypes.c_size_t
            lib.jfs_touch_pages.argtypes = [
                ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_int),
            ]
            if lib.jfs_abi_version() != 1:
                raise OSError("jfscore ABI mismatch")
            _lib = lib
        except (OSError, AttributeError) as e:
            # AttributeError: stale .so missing a symbol — fall back too.
            logger.warning("libjfscore load failed: %s", e)
            _lib = None
        return _lib


def available() -> bool:
    return _load() is not None


def crc32c(data: bytes, crc: int = 0) -> int:
    lib = _load()
    if lib is None:
        from ..object.checksum import crc32c_py

        return crc32c_py(data, crc)
    return lib.jfs_crc32c(data, len(data), crc)


def jth256(data: bytes) -> bytes:
    lib = _load()
    if lib is None:
        from ..tpu.jth256 import jth256 as ref

        return ref(data)
    out = ctypes.create_string_buffer(32)
    lib.jfs_jth256(data, len(data), out)
    return out.raw


def _pointers(blocks: Sequence[bytes]):
    """Zero-copy pointers for bytes AND writable buffers (bytearray from
    the WSlice block buffers — the ingest path hashes them in place; the C
    side only reads, bounded by the explicit lengths), their lengths, and
    what has to stay alive across the call. TypeError for a read-only
    buffer that is not `bytes`."""
    n = len(blocks)
    arr = (ctypes.c_char_p * n)()
    keepalive = []
    for i, b in enumerate(blocks):
        if isinstance(b, bytes):
            arr[i] = b
        else:
            view = (ctypes.c_char * len(b)).from_buffer(b)
            keepalive.append(view)
            arr[i] = ctypes.cast(view, ctypes.c_char_p)
    lens = (ctypes.c_size_t * n)(*map(len, blocks))
    return arr, lens, keepalive


def jth256_batch(blocks: Sequence[bytes], threads: int = 0) -> list[bytes]:
    lib = _load()
    if lib is None:
        from ..tpu.jth256 import hash_blocks_np

        return hash_blocks_np(blocks)
    if not blocks:
        return []
    if threads <= 0:
        threads = min(len(blocks), os.cpu_count() or 1)
    n = len(blocks)
    arr, lens, _keepalive = _pointers(blocks)
    outs = ctypes.create_string_buffer(32 * n)
    lib.jfs_jth256_batch(arr, lens, n, outs, threads)
    return [outs.raw[i * 32 : (i + 1) * 32] for i in range(n)]


def pack_rows(blocks: Sequence[bytes], rows) -> bool:
    """Block i into `rows[i]` from its start, zeros to the row's end, for
    every block in ONE call outside the interpreter lock. `rows` is a
    C-contiguous writeable (>= len(blocks), row_bytes) uint8 array and no
    block is longer than a row: the caller's to hold. False, with nothing
    written, when there is no library or a block is a read-only buffer
    that is not `bytes`: the caller copies row by row instead.

    Why one call (ISSUE 32): a numpy copy leaves the lock for its memcpy and
    has to retake it after every block; beside the scan's ten GET threads
    each retaking is a wait in their queue, and a 128 MiB pack took 41 ms
    where it takes 29 alone."""
    lib = _load()
    if lib is None:
        return False
    try:
        arr, lens, _keepalive = _pointers(blocks)
    except TypeError:
        return False
    lib.jfs_pack_rows(arr, lens, len(blocks), rows.ctypes.data, rows.shape[1])
    return True


def touch_pages(arr, stop: "ctypes.c_int | None" = None) -> Optional[int]:
    """One store in every page of `arr` (a C-contiguous writeable numpy
    array whose contents nobody needs), in ONE call outside the interpreter
    lock: afterwards its memory is resident and a pack into it pays no page
    fault. `stop` is a `ctypes.c_int` the call looks at once a MiB: whoever
    sets its `value` to 1 ends the call early. Returns the bytes touched;
    None, with nothing touched, when there is no library."""
    if not (arr.flags.c_contiguous and arr.flags.writeable):
        raise ValueError("touch_pages wants a C-contiguous writeable array")
    lib = _load()
    if lib is None:
        return None
    return lib.jfs_touch_pages(arr.ctypes.data, arr.nbytes, _PAGE,
                               ctypes.byref(stop) if stop is not None else None)
