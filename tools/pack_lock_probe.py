"""Who does a hash batch's pack wait for: the memory bus or the interpreter lock?

PR 32's question (PERF.md, section 6). One 32 x 4 MiB batch is packed by
`pack_blocks`, numpy row by row and through `native.pack_rows`, three ways:
alone; beside ten reader threads of ANOTHER process (same cores, same bus,
another interpreter lock); beside ten reader threads of its OWN process.
The readers do what a file-store GET does: open, read 4 MiB, close.  Host
times from whatever machine runs it: they say who waits for whom, nothing
about a device.

    python tools/pack_lock_probe.py [--readers 10] [--rounds 12]
"""

from __future__ import annotations

import argparse
import os
import statistics
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MIB4 = 4 << 20


def read_forever(paths, stop) -> None:
    i = 0
    while not stop.is_set():
        with open(paths[i % len(paths)], "rb") as f:
            f.read()
        i += 1


def start_readers(paths, n):
    stop = threading.Event()
    ts = [threading.Thread(target=read_forever, args=(paths[k::n], stop),
                           daemon=True) for k in range(n)]
    for t in ts:
        t.start()
    return stop


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--readers", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--serve", help=argparse.SUPPRESS)
    a = ap.parse_args()
    if a.serve:  # the other process: read until killed
        paths = sorted(os.path.join(a.serve, p) for p in os.listdir(a.serve))
        start_readers(paths, a.readers)
        time.sleep(3600)
        return

    import numpy as np

    from juicefs_tpu import native
    from juicefs_tpu.tpu.jth256 import COLS, ROWS, pack_blocks

    blocks = [os.urandom(MIB4) for _ in range(32)]
    out = np.zeros((32, 64, ROWS, COLS), dtype="<u4")
    real = native.pack_rows

    def pack_ms(use_native: bool) -> float:
        native.pack_rows = real if use_native else (lambda b, r: False)
        try:
            took = []
            for _ in range(a.rounds):
                t0 = time.perf_counter()
                pack_blocks(blocks, pad_lanes=64, out=out)
                took.append((time.perf_counter() - t0) * 1e3)
            return statistics.median(took)
        finally:
            native.pack_rows = real

    with tempfile.TemporaryDirectory() as d:
        paths = []
        for i in range(4 * a.readers):
            paths.append(os.path.join(d, f"{i:03d}"))
            with open(paths[-1], "wb") as f:
                f.write(blocks[i % 32])
        print(f"library={int(native.available())} readers={a.readers} "
              f"cores={os.cpu_count()}; median ms of {a.rounds} packs of 128 MiB")
        rows = [("alone", lambda: None, lambda h: None)]
        rows.append(("readers in another process",
                     lambda: subprocess.Popen(
                         [sys.executable, __file__, "--serve", d,
                          "--readers", str(a.readers)]),
                     lambda p: (p.kill(), p.wait())))
        rows.append(("readers in this process",
                     lambda: start_readers(paths, a.readers),
                     lambda stop: stop.set()))
        for name, start, end in rows:
            h = start()
            time.sleep(1.0)
            try:
                print(f"{name:28s} numpy row by row {pack_ms(False):8.2f}   "
                      f"one native call {pack_ms(True):8.2f}")
            finally:
                end(h)


if __name__ == "__main__":
    main()
