"""What is the cheapest way to have a 128 MiB pack buffer resident before the
pack needs it?

PR 34's question (PERF.md, section 6). A hash stream's pack buffer is
32 x 4 MiB of fresh, `mmap`ed memory: 32,768 pages nobody has touched. The
pack that first writes them pays a page fault a page. This probe makes such a
buffer resident in several ways, each on a buffer of its own and timed from
the request to the last page, then times two native packs into it, one after
the other (the first says whether the pages really were there: on the chip
machine's sandbox kernel a page touched once, by one thread alone, is paid
for a second time by whoever writes it next):

  (a) the pack itself into `np.empty` (what a stream's first batch paid);
  (b) one helper thread touching a byte a page through `native.touch_pages`
      in one call, alone and beside a busy Python thread (the listing),
      whose own loss of pace is printed with it; in 4 MiB slices, back for
      the interpreter lock after each; and touching twice;
  (c) `mmap(MAP_POPULATE)`, through Python's `mmap` and through libc;
  (d) `madvise(MADV_HUGEPAGE)`, then (b);
  (e) two and four threads on disjoint halves and quarters;
  and two buffers at once: one thread each, one thread for both in turn,
  two threads each, and four threads on the first, then four on the second
  (what `HashPipeline.prepare()` does), alone and beside the Python thread.

Host times from whatever machine runs it; nothing about a device.

    python tools/prefault_probe.py [--rounds 7]
"""

from __future__ import annotations

import argparse
import ctypes
import mmap
import os
import statistics
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MIB4 = 4 << 20
NBYTES = 32 * MIB4
SLICE = MIB4


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=7)
    a = ap.parse_args()

    import numpy as np

    from juicefs_tpu import native
    from juicefs_tpu.tpu.jth256 import COLS, ROWS, pack_blocks
    from juicefs_tpu.utils.malloc import keep_freed_blocks

    keep_freed_blocks()  # the scan's allocator policy: 128 MiB stays mmap'ed
    shape = (32, 64, ROWS, COLS)
    blocks = [os.urandom(MIB4) for _ in range(32)]

    def fresh():
        return np.empty(shape, dtype="<u4")

    def touch(buf, lo=0, hi=NBYTES, step=NBYTES):
        flat = buf.reshape(-1).view(np.uint8)
        for at in range(lo, hi, step):
            part = flat[at:min(at + step, hi)]
            if native.touch_pages(part) is None:
                part[::4096] = 0

    def sliced(buf):  # back for the interpreter lock every 4 MiB
        touch(buf, step=SLICE)

    def in_threads(jobs):
        ts = [threading.Thread(target=f, args=args) for f, *args in jobs]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

    def split(buf, n):
        step = NBYTES // n
        return [(touch, buf, k * step, (k + 1) * step) for k in range(n)]

    def populate_python():
        mm = mmap.mmap(-1, NBYTES, flags=mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS
                       | mmap.MAP_POPULATE)
        return np.frombuffer(mm, dtype="<u4").reshape(shape)

    libc = ctypes.CDLL(None, use_errno=True)
    libc.mmap.restype = ctypes.c_void_p
    libc.mmap.argtypes = [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int,
                          ctypes.c_int, ctypes.c_int, ctypes.c_long]
    libc.munmap.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
    mapped = []

    def populate_libc():
        got = []

        def call():  # off the caller's thread, outside the interpreter lock
            got.append(libc.mmap(None, NBYTES, mmap.PROT_READ | mmap.PROT_WRITE,
                                 mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS
                                 | mmap.MAP_POPULATE, -1, 0))
        in_threads([(call,)])
        if got[0] in (None, ctypes.c_void_p(-1).value):
            raise OSError(ctypes.get_errno(), "mmap")
        mapped.append(got[0])
        raw = (ctypes.c_uint8 * NBYTES).from_address(got[0])
        return np.frombuffer(raw, dtype="<u4").reshape(shape)

    def hugepage():
        mm = mmap.mmap(-1, NBYTES)
        mm.madvise(mmap.MADV_HUGEPAGE)
        buf = np.frombuffer(mm, dtype="<u4").reshape(shape)
        in_threads([(touch, buf)])
        return buf

    def threaded(n):
        def make():
            buf = fresh()
            in_threads(split(buf, n))
            return buf
        return make

    def twice():
        buf = fresh()
        in_threads([(lambda: (touch(buf), touch(buf)),)])
        return buf

    def the_pack():
        return pack_blocks(blocks, pad_lanes=64)[0]

    def pack_ms(buf) -> float:
        t0 = time.perf_counter()
        pack_blocks(blocks, pad_lanes=64, out=buf)
        return (time.perf_counter() - t0) * 1e3

    def timed(make):
        """Median ms to have one buffer from `make`, and of two packs after."""
        took, packs, again = [], [], []
        for _ in range(a.rounds):
            t0 = time.perf_counter()
            try:
                buf = make()
            except (OSError, ValueError, AttributeError) as e:
                return f"refused here: {e}"
            took.append((time.perf_counter() - t0) * 1e3)
            packs.append(pack_ms(buf))
            again.append(pack_ms(buf))
            del buf
            while mapped:
                libc.munmap(mapped.pop(), NBYTES)
        return (f"{statistics.median(took):8.2f} ms resident   "
                f"{statistics.median(packs):7.2f} ms the pack after   "
                f"{statistics.median(again):7.2f} the next")

    spins = [0]
    busy_stop = threading.Event()

    def spin():  # what the listing is: one thread of pure Python
        d = {}
        while not busy_stop.is_set():
            for i in range(1000):
                d[f"chunks/{i}"] = i
            spins[0] += 1

    def pace(seconds=0.5) -> float:
        n0, t0 = spins[0], time.perf_counter()
        time.sleep(seconds)
        return (spins[0] - n0) / (time.perf_counter() - t0)

    print(f"library={int(native.available())} cores={os.cpu_count()} "
          f"page={os.sysconf('SC_PAGE_SIZE')}; median of {a.rounds}, one "
          f"{NBYTES >> 20} MiB buffer a round")
    kept = fresh()
    pack_ms(kept)
    print(f"{'a pack into a kept buffer':44s} "
          f"{statistics.median(pack_ms(kept) for _ in range(a.rounds)):8.2f} ms")
    del kept
    rows = [
        ("(a) the pack itself into np.empty", the_pack),
        ("(b) one thread touching, alone", threaded(1)),
        ("(b) one thread touching twice", twice),
        ("(c) MAP_POPULATE, Python's mmap", populate_python),
        ("(c) MAP_POPULATE, libc off the thread", populate_libc),
        ("(d) MADV_HUGEPAGE, then one thread", hugepage),
        ("(e) two threads, halves", threaded(2)),
        ("(e) four threads, quarters", threaded(4)),
    ]
    for name, make in rows:
        print(f"{name:44s} {timed(make)}")

    def two_at_once():
        bufs = [fresh(), fresh()]
        in_threads([(touch, b) for b in bufs])
        return bufs[1]

    def two_in_turn():
        bufs = [fresh(), fresh()]
        in_threads([(lambda: [touch(b) for b in bufs],)])
        return bufs[1]

    def two_by_two():
        bufs = [fresh(), fresh()]
        in_threads(split(bufs[0], 2) + split(bufs[1], 2))
        return bufs[1]

    def four_in_order():  # what HashPipeline.prepare() does
        bufs = [fresh(), fresh()]
        in_threads([(lambda: [in_threads(split(b, 4)) for b in bufs],)])
        return bufs[1]

    def one_sliced():
        buf = fresh()
        in_threads([(sliced, buf)])
        return buf

    print(f"{'two buffers, a thread each':44s} {timed(two_at_once)}")
    print(f"{'two buffers, one thread in turn':44s} {timed(two_in_turn)}")
    print(f"{'two buffers, two threads each':44s} {timed(two_by_two)}")
    print(f"{'two buffers, four threads on each in turn':44s} "
          f"{timed(four_in_order)}")

    busy = threading.Thread(target=spin, daemon=True)
    busy.start()
    alone = pace()
    name = "(b) one thread touching, beside Python"
    print(f"{name:44s} {timed(threaded(1))}")
    name = "(b) the same in 4 MiB slices, beside Python"
    print(f"{name:44s} {timed(one_sliced)}")
    name = "two buffers, a thread each, beside Python"
    print(f"{name:44s} {timed(two_at_once)}")
    name = "two buffers, four in turn, beside Python"
    print(f"{name:44s} {timed(four_in_order)}")
    # the busy thread's pace while buffers are being touched, back to back
    stop = threading.Event()

    def touch_forever():
        while not stop.is_set():
            touch(fresh())
    t = threading.Thread(target=touch_forever)
    t.start()
    beside = pace(1.5)
    stop.set()
    t.join()
    busy_stop.set()
    print(f"the Python thread's pace beside a touching thread: "
          f"{100 * beside / alone:.1f}% of its pace alone")


if __name__ == "__main__":
    main()
