"""The allocator policy of a bulk-scan process, stated once at its entry.

A scan's download threads allocate a block-sized `bytes` (4 MiB) for every
GET and drop it a batch later. glibc serves an allocation at or above its
mmap threshold (128 KiB at start) by `mmap` and frees it by `munmap`, and
gives an arena's free top back to the kernel beyond its trim threshold:
either way the next block is first-touched again, a thousand page faults
on each of ten threads at once. The thresholds are dynamic: freeing an
mmapped chunk of up to 32 MiB raises the mmap threshold to its size and the
trim threshold to twice that. The first freed block so makes them 4 and
8 MiB, under which most of a fetch window is still trimmed and faulted in
again; only a freed chunk of tens of MiB lifts them far enough that freed
GET buffers stay in the arenas, and whether a scan ever frees one is an
accident of its batch sizes (PERF.md §6, PR 27). `keep_freed_blocks()`
states that end state from the first block on.

Process-wide and for good, so it belongs to the commands whose process *is*
a scan — `gc --dedup`, `fsck --verify-data`, `sync` comparing by digest
(`--hash-backend`) — and is never called from a
library function, `mount` or the gateway: a long-lived server's memory
profile is not a scan's. What it costs: freed blocks stay resident (bounded
by the fetch window and one batch) plus up to 64 MiB of untrimmed heap top.
"""

from __future__ import annotations

import ctypes

from ..metric import global_registry
from . import get_logger

logger = get_logger("utils.malloc")

# <malloc.h>
M_TRIM_THRESHOLD = -1
M_MMAP_THRESHOLD = -3
# the largest mmap threshold glibc takes (HEAP_MAX / 2 on 64 bit), and the
# trim threshold its own dynamic adjustment pairs with it
MMAP_THRESHOLD = 32 << 20
TRIM_THRESHOLD = 64 << 20

_POLICY = global_registry().gauge(
    "juicefs_malloc_policy",
    "1 once the bulk-scan allocator policy is in force in this process "
    "(glibc mallopt; the labels are the thresholds in bytes)",
    ("mmap_threshold", "trim_threshold"),
)
_in_force: bool | None = None  # None: nobody has asked yet


def _glibc_mallopt():
    """glibc's `mallopt`, or None with the reason: another libc (musl,
    macOS), or a preloaded allocator standing in for glibc's malloc, whose
    thresholds would then govern nothing."""
    try:
        process = ctypes.CDLL(None)
        process.gnu_get_libc_version  # AttributeError off glibc
        mallopt = process.mallopt
        mallocs = {ctypes.cast(lib.malloc, ctypes.c_void_p).value
                   for lib in (process, ctypes.CDLL("libc.so.6"))}
        if len(mallocs) != 1:
            return None, "malloc is not glibc's (a preloaded allocator)"
    except (OSError, AttributeError) as e:
        return None, f"no glibc mallopt: {e}"
    mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
    mallopt.restype = ctypes.c_int
    return mallopt, ""


def keep_freed_blocks() -> bool:
    """`mallopt(M_MMAP_THRESHOLD, 32 MiB)` and `mallopt(M_TRIM_THRESHOLD,
    64 MiB)`: block-sized buffers come from malloc's arenas and stay there
    when freed. Idempotent (a later call returns what the first found: the
    same two values, so a race of first calls is harmless); a logged no-op
    where there is no glibc malloc to tell; never raises. Returns whether
    the policy is in force."""
    global _in_force
    if _in_force is None:
        mallopt, why_not = _glibc_mallopt()
        # mallopt returns 1 on success
        if mallopt is not None and not (
                mallopt(M_MMAP_THRESHOLD, MMAP_THRESHOLD)
                and mallopt(M_TRIM_THRESHOLD, TRIM_THRESHOLD)):
            mallopt, why_not = None, "mallopt refused the thresholds"
        _in_force = mallopt is not None
        if _in_force:
            _POLICY.labels(MMAP_THRESHOLD, TRIM_THRESHOLD).set(1)
        else:
            logger.info("allocator policy not set: %s", why_not)
    return _in_force
