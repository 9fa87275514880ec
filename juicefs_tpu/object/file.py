"""Local-disk object store (reference: pkg/object/file.go).

Keys map to paths under the root; writes are atomic (temp file + rename) so
a crashed writer never leaves a half-written block visible — the same
guarantee the reference relies on for its disk-backed stores.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
import uuid
from typing import Iterator

from ..metric import global_registry
from .interface import MultipartUpload, NotFoundError, Obj, ObjectStorage, Part

_LISTED = global_registry().counter(
    "juicefs_file_list_objects",
    "Objects file-store listings sized, each by a stat relative to the "
    "directory it was read from",
)


class FileStorage(ObjectStorage):
    # every call is a system call on this host's file system: the
    # resilience layer runs its attempts on the caller (object/resilient.py)
    host_local = True

    def __init__(self, root: str):
        # file:///abs/path arrives as "/abs/path"; relative allowed for tests
        self.root = root if root.endswith("/") else root + "/"
        # ensured-directory cache (ISSUE 8 upload pipelining): the block
        # namespace reuses a handful of chunks/a/b dirs, and the
        # per-PUT makedirs walk costs 3+ stats per call — expensive on
        # network filesystems. delete()'s empty-dir pruning invalidates;
        # put() additionally retries once on a lost race.
        self._dirs: set[str] = set()
        self._dirs_lock = threading.Lock()

    def _ensure_dir(self, d: str) -> None:
        with self._dirs_lock:
            if d in self._dirs:
                return
        os.makedirs(d, exist_ok=True)
        with self._dirs_lock:
            if len(self._dirs) >= 4096:
                self._dirs.clear()  # unbounded key space: cheap reset
            self._dirs.add(d)

    def _forget_dir(self, d: str) -> None:
        with self._dirs_lock:
            self._dirs.discard(d)

    def string(self) -> str:
        return f"file://{self.root}"

    def create(self) -> None:
        os.makedirs(self.root, exist_ok=True)

    def _path(self, key: str) -> str:
        return os.path.join(self.root, key)

    def get(self, key: str, off: int = 0, limit: int = -1) -> bytes:
        try:
            with open(self._path(key), "rb") as f:
                if off:
                    f.seek(off)
                return f.read() if limit < 0 else f.read(limit)
        except FileNotFoundError:
            raise NotFoundError(key) from None
        except IsADirectoryError:
            raise NotFoundError(key) from None

    def put(self, key: str, data: bytes) -> None:
        path = self._path(key)
        d = os.path.dirname(path)
        for attempt in (0, 1):
            self._ensure_dir(d)
            try:
                fd, tmp = tempfile.mkstemp(dir=d, prefix=".tmp.")
            except FileNotFoundError:
                # lost the race against delete()'s empty-dir pruning:
                # the cached dir vanished between check and create —
                # recreate and retry once (once the temp file exists the
                # dir is non-empty, so rmdir cannot take it again)
                self._forget_dir(d)
                if attempt:
                    raise
                continue
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(data)
                os.replace(tmp, path)
                return
            except BaseException:
                try:
                    os.unlink(tmp)
                except FileNotFoundError:
                    pass
                raise

    def delete(self, key: str) -> None:
        try:
            os.unlink(self._path(key))
        except (FileNotFoundError, IsADirectoryError):
            pass
        # opportunistically prune empty parent dirs up to the root
        d = os.path.dirname(self._path(key))
        root = self.root.rstrip("/")
        while len(d) > len(root):
            try:
                os.rmdir(d)
            except OSError:
                break
            self._forget_dir(d)
            d = os.path.dirname(d)

    def head(self, key: str) -> Obj:
        try:
            st = os.stat(self._path(key))
        except FileNotFoundError:
            raise NotFoundError(key) from None
        if os.path.isdir(self._path(key)):
            raise NotFoundError(key)
        return Obj(key=key, size=st.st_size, mtime=st.st_mtime)

    def list_all(self, prefix: str = "", marker: str = "") -> Iterator[Obj]:
        """Every object under `prefix` after `marker`, in key order.

        One directory at a time (`os.fwalk`): each entry is sized by a stat
        relative to the directory it was read from, so no path is resolved
        again from `/` per object (ISSUE 39). As `os.walk` did: a symlinked
        directory is not descended, a symlinked file is sized by its target,
        `.tmp.` files are skipped."""
        parts = prefix.split("/")
        rest = parts.pop()  # the beginning of a name, not a directory
        try:
            rootfd = os.open(self.root, os.O_RDONLY | os.O_DIRECTORY)
        except OSError:
            return
        found: list[tuple[str, int, float]] = []
        try:
            for dirpath, dirnames, filenames, dirfd in os.fwalk(".", dir_fd=rootfd):
                pre = dirpath[2:] + "/" if dirpath != "." else ""
                depth = pre.count("/")
                if depth < len(parts):  # above the prefix: only its path
                    want = parts[depth]
                    dirnames[:] = [want] if want in dirnames else []
                    continue
                if depth == len(parts):
                    dirnames[:] = [d for d in dirnames if d.startswith(rest)]
                    filenames = [f for f in filenames if f.startswith(rest)]
                for name in filenames:
                    key = pre + name
                    if name.startswith(".tmp.") or key <= marker:
                        continue
                    try:
                        st = os.stat(name, dir_fd=dirfd)
                    except FileNotFoundError:
                        continue  # removed since the directory was read
                    found.append((key, st.st_size, st.st_mtime))
        finally:
            os.close(rootfd)
        found.sort()
        _LISTED.inc(len(found))
        for key, size, mtime in found:
            yield Obj(key=key, size=size, mtime=mtime)

    def create_multipart_upload(self, key: str):
        uid = uuid.uuid4().hex
        os.makedirs(os.path.join(self.root, ".uploads", uid), exist_ok=True)
        return MultipartUpload(min_part_size=1 << 20, max_count=10000, upload_id=uid)

    def upload_part(self, key: str, upload_id: str, num: int, data: bytes) -> Part:
        path = os.path.join(self.root, ".uploads", upload_id, str(num))
        with open(path, "wb") as f:
            f.write(data)
        return Part(num=num, etag=str(num), size=len(data))

    def complete_upload(self, key: str, upload_id: str, parts: list[Part]) -> None:
        updir = os.path.join(self.root, ".uploads", upload_id)
        buf = []
        for p in sorted(parts, key=lambda p: p.num):
            with open(os.path.join(updir, str(p.num)), "rb") as f:
                buf.append(f.read())
        self.put(key, b"".join(buf))
        self.abort_upload(key, upload_id)

    def abort_upload(self, key: str, upload_id: str) -> None:
        import shutil

        shutil.rmtree(os.path.join(self.root, ".uploads", upload_id), ignore_errors=True)
