"""Content-addressed dedup scan over digest batches.

The reference's gc classifies blocks by *name* diff only (cmd/gc.go:253-330);
dedup-by-content is the new TPU capability (BASELINE.md north star). Given a
batch of JTH-256 digests, find duplicate contents via a lexicographic
multi-key sort (jax.lax.sort with num_keys=8 maps onto XLA's sort, which TPU
executes as a bitonic network) and an adjacent-equality pass, then scatter
flags back to input order.

Output convention: for each group of equal digests, the occurrence with the
lowest original index is the *representative* (kept); the rest are marked
duplicate (reclaimable). first_idx maps every block to its representative.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .hash_jax import hash_packed_jax, named_jit


def dedup_scan_jax(digests: jax.Array):
    """digests (N, 8) uint32 -> (dup_mask (N,) bool, first_idx (N,) int32).

    dup_mask[i] is True iff block i's content equals an earlier (lower
    original index) block; first_idx[i] is that representative's index
    (i itself when unique or first occurrence).
    """
    n = digests.shape[0]
    if n == 0:
        return jnp.zeros((0,), dtype=bool), jnp.zeros((0,), dtype=jnp.int32)
    idx = jnp.arange(n, dtype=jnp.int32)
    cols = [digests[:, k] for k in range(8)]
    # Tie-break on original index so each group is ordered by appearance.
    *scols, sidx = lax.sort([*cols, idx], num_keys=9)
    sorted_d = jnp.stack(scols, axis=1)
    same_as_prev = jnp.concatenate(
        [
            jnp.zeros((1,), dtype=bool),
            jnp.all(sorted_d[1:] == sorted_d[:-1], axis=1),
        ]
    )
    # Representative (in sorted order) = last position where same_as_prev
    # was False; propagate it forward with a cummax over masked indices.
    group_start = jnp.where(same_as_prev, 0, jnp.arange(n, dtype=jnp.int32))
    group_start = lax.associative_scan(jnp.maximum, group_start)
    first_sorted = sidx[group_start]
    dup = jnp.zeros((n,), dtype=bool).at[sidx].set(same_as_prev)
    first_idx = jnp.zeros((n,), dtype=jnp.int32).at[sidx].set(first_sorted)
    return dup, first_idx


def scan_step_jax(words, lane_counts, lengths):
    """Full single-device scan step: hash the packed batch, dedup it.

    Returns (digests (B,8) uint32, dup_mask (B,), first_idx (B,)). This is
    the flagship jittable forward step exposed by __graft_entry__.entry().
    """
    digests = hash_packed_jax(words, lane_counts, lengths)
    dup, first = dedup_scan_jax(digests)
    return digests, dup, first


# fixed program names (tpu/hash_jax.py:named_jit): `jit_dedup_scan`,
# `jit_jth256_scan` in the profiler's XLA Modules line
dedup_scan_jax = named_jit("dedup_scan", dedup_scan_jax)
scan_step_jax = named_jit("jth256_scan", scan_step_jax)


def dedup_digests(digests: list[bytes]):
    """Host-side helper over 32-byte digests (numpy; used by CPU backend).

    Same output convention as dedup_scan_jax.
    """
    n = len(digests)
    dup = np.zeros(n, dtype=bool)
    first = np.arange(n, dtype=np.int32)
    seen: dict[bytes, int] = {}
    for i, d in enumerate(digests):
        j = seen.setdefault(d, i)
        if j != i:
            dup[i] = True
            first[i] = j
    return dup, first
