"""What does a listed object cost: its bytes, or the path walked to it?

PR 39's question (PERF.md, section 6). 517 files, the benchmark's block
count, in one directory at the benchmark's depth (`<checkout>/.bench_work/
probe-*/blob/vol/chunks/0/0/`) and four components deeper. Per object, in
microseconds: a stat by full path; `fstatat` against the directory's fd; a
bare read of the directory (no stat); an `open` + `close` by full path and
by directory fd (what a GET's `open` pays); the listing as it was before
PR 39 (`os.walk`, `relpath`, a full-path stat a key: copied here) and
`FileStorage.list_all` as it is. Each alone and beside ten threads of this
process reading 4 MiB files, as a scan's GETs do. The depth's difference
over four components is what one component costs. Host times from whatever
machine runs it: they say nothing about a device.

    python tools/list_probe.py [--objects 517] [--readers 10] [--rounds 9]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from juicefs_tpu.object.file import FileStorage  # noqa: E402

MIB4 = 4 << 20


def old_list_all(root: str, prefix: str) -> list:
    """`FileStorage.list_all` before PR 39 (object/file.py at 9a2ec5f)."""
    keys = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for fn in filenames:
            if fn.startswith(".tmp."):
                continue
            key = os.path.relpath(os.path.join(dirpath, fn), root).replace(os.sep, "/")
            if key.startswith(prefix):
                keys.append(key)
    keys.sort()
    out = []
    for key in keys:
        try:
            st = os.stat(os.path.join(root, key))
        except FileNotFoundError:
            continue
        out.append((key, st.st_size, st.st_mtime))
    return out


def read_forever(paths, stop) -> None:
    i = 0
    while not stop.is_set():
        with open(paths[i % len(paths)], "rb") as f:
            f.read()
        i += 1


def start_readers(paths, n):
    stop = threading.Event()
    for k in range(n):
        threading.Thread(target=read_forever, args=(paths[k::n], stop),
                         daemon=True).start()
    return stop


def measures(vol: str, names: list[str]) -> dict:
    """Callables that each do one thing once for every object."""
    d = os.path.join(vol, "chunks", "0", "0")
    paths = [os.path.join(d, n) for n in names]
    flags = os.O_RDONLY | os.O_DIRECTORY

    def by_path():
        for p in paths:
            os.stat(p)

    def by_dirfd():
        fd = os.open(d, flags)
        try:
            for n in names:
                os.stat(n, dir_fd=fd)
        finally:
            os.close(fd)

    def dir_read():
        fd = os.open(d, flags)
        try:
            with os.scandir(fd) as it:
                for _ in it:
                    pass
        finally:
            os.close(fd)

    def open_path():
        for p in paths:
            os.close(os.open(p, os.O_RDONLY))

    def open_dirfd():
        fd = os.open(d, flags)
        try:
            for n in names:
                os.close(os.open(n, os.O_RDONLY, dir_fd=fd))
        finally:
            os.close(fd)

    store = FileStorage(vol)
    return {
        "stat_full_path": by_path,
        "fstatat_dirfd": by_dirfd,
        "dir_read": dir_read,
        "open_full_path": open_path,
        "open_dirfd": open_dirfd,
        "list_all_old": lambda: old_list_all(vol + "/", "chunks/"),
        "list_all_new": lambda: list(store.list_all("chunks/")),
    }


def per_object_us(fn, n: int, rounds: int) -> float:
    fn()  # the dentries and the page cache warm, as in a window
    took = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        fn()
        took.append(time.perf_counter() - t0)
    return statistics.median(took) / n * 1e6


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--objects", type=int, default=517)
    ap.add_argument("--readers", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=9)
    ap.add_argument("--out", help="also write the result as JSON here")
    a = ap.parse_args()

    os.makedirs(os.path.join(ROOT, ".bench_work"), exist_ok=True)
    work = tempfile.mkdtemp(prefix="probe-", dir=os.path.join(ROOT, ".bench_work"))
    try:
        names = [f"{i + 1}_0_4194304" for i in range(a.objects)]
        vols = {"bench": os.path.join(work, "blob", "vol"),
                "bench+4": os.path.join(work, "blob", "p", "q", "r", "s", "vol")}
        for vol in vols.values():
            d = os.path.join(vol, "chunks", "0", "0")
            os.makedirs(d)
            for n in names:
                with open(os.path.join(d, n), "wb") as f:
                    f.write(b"\0" * 4096)
        assert (len(old_list_all(vols["bench"] + "/", "chunks/"))
                == len(list(FileStorage(vols["bench"]).list_all("chunks/"))) == a.objects)
        data = os.path.join(work, "data")
        os.makedirs(data)
        block = os.urandom(MIB4)
        readers = []
        for i in range(4 * a.readers):
            readers.append(os.path.join(data, f"{i:03d}"))
            with open(readers[-1], "wb") as f:
                f.write(block)

        depth = len(os.path.join(vols["bench"], "chunks", "0", "0", names[0]).split("/")) - 1
        result = {"objects": a.objects, "readers": a.readers, "rounds": a.rounds,
                  "cores": os.cpu_count(), "components": {"bench": depth, "bench+4": depth + 4},
                  "us_per_object": {}}
        for beside in ("alone", "readers"):
            stop = start_readers(readers, a.readers) if beside == "readers" else None
            try:
                if stop:
                    time.sleep(1.0)
                for where, vol in vols.items():
                    row = {k: round(per_object_us(fn, a.objects, a.rounds), 3)
                           for k, fn in measures(vol, names).items()}
                    result["us_per_object"][f"{beside}.{where}"] = row
            finally:
                if stop:
                    stop.set()
                    time.sleep(0.2)
        us = result["us_per_object"]
        for beside in ("alone", "readers"):
            a0, a4 = us[f"{beside}.bench"], us[f"{beside}.bench+4"]
            result[f"{beside}.component_us"] = round(
                (a4["stat_full_path"] - a0["stat_full_path"]) / 4, 3)
            result[f"{beside}.stat_path_over_dirfd"] = round(
                a0["stat_full_path"] / a0["fstatat_dirfd"], 2)
            result[f"{beside}.list_old_over_new"] = round(
                a0["list_all_old"] / a0["list_all_new"], 2)
        for k, row in us.items():
            print(f"{k:16s} " + "  ".join(f"{m} {v:9.2f}" for m, v in row.items()))
        print("RESULT " + json.dumps(result, sort_keys=True))
        if a.out:
            os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
            with open(a.out, "w") as f:
                json.dump(result, f, indent=1, sort_keys=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
