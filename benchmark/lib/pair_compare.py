"""The plain reference of the mirror check: a byte compare of two directory
trees, one pair of files after another. Imports nothing of the program, reads
no digest and no listing of its: what `sync --check-all` says of a pair of
stores is held to what this walk says of the two directories behind them.

`compare(src_root, dst_root)` -> {key: verdict}, the key a file's path below
its root with `/` between the parts, the verdict one of `equal`, `differ`
(both there, sizes or bytes differ), `only_src`, `only_dst`.
"""

from __future__ import annotations

import os

CHUNK = 1 << 20


def files_of(root: str) -> dict[str, str]:
    """{key: path} of every regular file below `root`."""
    found = {}
    for base, _dirs, files in os.walk(root):
        for name in files:
            path = os.path.join(base, name)
            found[os.path.relpath(path, root).replace(os.sep, "/")] = path
    return found


def same_bytes(a: str, b: str) -> bool:
    if os.path.getsize(a) != os.path.getsize(b):
        return False
    with open(a, "rb") as fa, open(b, "rb") as fb:
        while True:
            x, y = fa.read(CHUNK), fb.read(CHUNK)
            if x != y:
                return False
            if not x:
                return True


def compare(src_root: str, dst_root: str) -> dict[str, str]:
    src, dst = files_of(src_root), files_of(dst_root)
    verdicts = {}
    for key in sorted(src.keys() | dst.keys()):
        if key not in dst:
            verdicts[key] = "only_src"
        elif key not in src:
            verdicts[key] = "only_dst"
        else:
            verdicts[key] = "equal" if same_bytes(src[key], dst[key]) else "differ"
    return verdicts
