"""FileStorage.list_all walks one directory at a time (ISSUE 39).

Every case holds the walk to a plain reference: the listing as it was before, `os.walk` + `relpath` + a stat by
full path, on the sequence of (key, size, mtime).
"""

from __future__ import annotations

import os

import pytest

from juicefs_tpu.metric import global_registry
from juicefs_tpu.object.file import FileStorage


def reference_list(root: str, prefix: str = "", marker: str = "") -> list:
    if not os.path.isdir(root):
        return []
    keys = []
    for dirpath, _, filenames in os.walk(root):
        for fn in filenames:
            if fn.startswith(".tmp."):
                continue
            key = os.path.relpath(os.path.join(dirpath, fn), root).replace(os.sep, "/")
            if key.startswith(prefix) and key > marker:
                keys.append(key)
    out = []
    for key in sorted(keys):
        try:
            st = os.stat(os.path.join(root, key))
        except FileNotFoundError:
            continue
        out.append((key, st.st_size, st.st_mtime))
    return out


def _write(root: str, key: str, size: int) -> None:
    path = os.path.join(root, key)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(b"x" * size)


def nested(root):
    for i, key in enumerate([
        "chunks/0/0/1_0_4194304", "chunks/0/0/2_0_100001", "chunks/0/1/9_0_1",
        "chunks/1/0/10_0_7", "chunks/10/0/11_0_5", "chunks1", "top", "meta.json",
        ".tmp.top", "chunks/.tmp.abc", "chunks/0/0/.tmp.x", "chunks/1/0/.tmp.y",
        ".tmp.dir/kept",  # a directory named like a temp file is walked
    ]):
        _write(root, key, i + 1)


def sorts_around_slash(root):
    for i, key in enumerate(["a-b", "a.b", "a_b", "a0", "a/b", "a/c/d", "a-c/x/y",
                             "a.c/x", "a!", "a~", "A/z", "a b/q"]):
        _write(root, key, 3 * i)


def uploads(root):
    s = FileStorage(root)
    s.create()
    s.put("vol/chunks/0/0/1_0_3", b"abc")
    up = s.create_multipart_upload("big")
    s.upload_part("big", up.upload_id, 1, b"p" * 10)
    s.upload_part("big", up.upload_id, 2, b"q" * 20)


def links(root, outside):
    nested(root)
    os.makedirs(outside, exist_ok=True)
    _write(outside, "target", 12345)
    _write(outside, "d/inner", 7)
    os.symlink(os.path.join(outside, "target"), os.path.join(root, "chunks/0/filelink"))
    os.symlink(os.path.join(outside, "d"), os.path.join(root, "chunks/dirlink"))
    os.symlink(os.path.join(root, "chunks/0"), os.path.join(root, "loop0"))
    os.symlink(os.path.join(outside, "nothing"), os.path.join(root, "chunks/broken"))


def missing_root(root):
    os.rmdir(root)


CASES = {
    "nested-all": (nested, "", ""),
    "nested-chunks": (nested, "chunks/", ""),
    "prefix-mid-name": (nested, "chunks/1", ""),
    "prefix-dir-no-slash": (nested, "chunks", ""),
    "prefix-deep": (nested, "chunks/0/0/", ""),
    "prefix-file": (nested, "chunks/0/0/1_0_4194304", ""),
    "marker": (nested, "chunks/", "chunks/0/0/2_0_100001"),
    "marker-before-prefix": (nested, "chunks/1/", "a"),
    "marker-past-all": (nested, "", "zzz"),
    "names-around-slash": (sorts_around_slash, "", ""),
    "names-around-slash-prefix": (sorts_around_slash, "a", ""),
    "names-around-slash-marker": (sorts_around_slash, "a", "a-c/x/y"),
    "missing-root": (missing_root, "", ""),
    "missing-prefix-dir": (nested, "nope/", ""),
    "prefix-below-a-file": (nested, "top/x/", ""),
    "prefix-not-a-key-path": (nested, "/chunks/", ""),
    "prefix-leading-slash": (nested, "/chunks", ""),
    "prefix-empty-component": (nested, "chunks//0/", ""),
    "prefix-dot": (nested, "./chunks/", ""),
    "uploads": (uploads, "", ""),
    "links": (links, "", ""),
    "prefix-through-dir-link": (links, "chunks/dirlink/", ""),
    "prefix-through-root-link": (links, "loop0/", ""),
    "prefix-through-root-link-no-slash": (links, "loop0", ""),
}


def _tree(tmp_path, build):
    root = str(tmp_path / "store")
    os.makedirs(root)
    if build is links:
        links(root, str(tmp_path / "outside"))
    else:
        build(root)
    return root


def _open_fds() -> int:
    return len(os.listdir("/proc/self/fd")) if os.path.isdir("/proc/self/fd") else 0


@pytest.mark.parametrize("case", sorted(CASES) + ["removed-before-stat"])
def test_list_all_matches_reference(tmp_path, monkeypatch, case):
    fds = _open_fds()
    if case == "removed-before-stat":
        root = _tree(tmp_path, nested)
        prefix = marker = ""
        # the file is in the directory read, and gone by the time its entry
        # is handed over to be sized
        gone = []
        real = os.scandir

        def removing(fd):
            for e in real(fd):
                if e.name == "2_0_100001":
                    os.unlink(e.name, dir_fd=fd)
                    gone.append(e.name)
                yield e

        monkeypatch.setattr(os, "scandir", removing)
        got = list(FileStorage(root).list_all(prefix, marker))
        monkeypatch.setattr(os, "scandir", real)
        assert gone == ["2_0_100001"]
    else:
        build, prefix, marker = CASES[case]
        root = _tree(tmp_path, build)
        got = list(FileStorage(root).list_all(prefix, marker))
    assert _open_fds() == fds  # every directory the walk opened is closed
    want = reference_list(root, prefix, marker)
    assert [(o.key, o.size, o.mtime) for o in got] == want
    if case in ("nested-all", "links", "uploads", "names-around-slash"):
        assert want, "the case lists nothing: it proves nothing"
    if case == "removed-before-stat":
        assert "chunks/0/0/2_0_100001" not in [o.key for o in got]
        assert "chunks/0/0/1_0_4194304" in [o.key for o in got]
    if case == "links":
        sizes = dict((k, s) for k, s, _ in want)
        assert sizes["chunks/0/filelink"] == 12345  # the target's stat
        assert not any(k.startswith(("chunks/dirlink/", "loop0/")) for k in sizes)
    if case == "uploads":
        assert any(k.startswith(".uploads/") for k, _, _ in want)


def test_list_all_counts_what_it_sized(tmp_path):
    root = str(tmp_path / "store")
    nested(root)
    counter = global_registry()._metrics["juicefs_file_list_objects"]
    before = counter.value
    listed = list(FileStorage(root).list_all("chunks/"))
    assert len(listed) == 5
    assert counter.value - before == 5  # once a listing
    # a listing that finds nothing adds nothing
    assert list(FileStorage(root).list_all("nope/")) == []
    assert counter.value - before == 5
