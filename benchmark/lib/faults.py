"""Faults planted under the timed path, from outside, to show that the
comparison that decides `correct` fails when it should (tests/benchmark and
benchmark/control.py; a benchmark run never plants one).

  digest_altered      an answer altered where it is produced: one bit of the
                      first digest of every batch, as the pipeline drains it
  rows_not_committed  a step that returns its state unchanged: the scan
                      answers, its index rows are never written
  half_left_out       half of the batch left out: the scan walks every other
                      live block and reports on those
  exchange_left_out   the exchange between chips left out: each lane shard
                      combines its own lanes' accumulators twice instead of
                      gathering its neighbour's (mesh path only)

The control — the program's own nearest lower path, the host hash under the
device's name — is not planted here: it is the configuration's
`hash_backend` set to `cpu`.
"""

from __future__ import annotations

import contextlib
import importlib


def _digest_altered():
    import numpy as np

    def make(old):
        def digests_to_bytes(digests):
            d = np.array(digests, dtype=np.uint32, copy=True)
            if d.size:
                d[0, 0] ^= np.uint32(1)
            return old(d)
        return digests_to_bytes
    return [("juicefs_tpu.tpu.pipeline", None, "digests_to_bytes", make)]


def _rows_not_committed():
    def make(old):
        return lambda self, entries: None
    return [("juicefs_tpu.meta.sql", "SQLMeta", "set_block_digests", make),
            ("juicefs_tpu.meta.kv", "KVMeta", "set_block_digests", make)]


def _half_left_out():
    def make(old):
        def dedup_scan(meta, store, live, *a, **kw):
            return old(meta, store, dict(list(live.items())[::2]), *a, **kw)
        return dedup_scan
    return [("juicefs_tpu.cmd.gc", None, "dedup_scan", make)]


def _exchange_left_out():
    def make(lax):
        import jax.numpy as jnp

        class NoExchange:
            def __getattr__(self, name):
                return getattr(lax, name)

            @staticmethod
            def all_gather(x, axis_name, *, axis=0, tiled=False):
                n = lax.psum(1, axis_name)  # static: the axis size
                return jnp.concatenate([x] * n, axis=axis)
        return NoExchange()
    return [("juicefs_tpu.tpu.sharding", None, "lax", make)]


FAULTS = {"digest_altered": _digest_altered,
          "rows_not_committed": _rows_not_committed,
          "half_left_out": _half_left_out,
          "exchange_left_out": _exchange_left_out}


@contextlib.contextmanager
def plant(name: str):
    undo = []
    mesh_path = name == "exchange_left_out"
    try:
        for module, cls, attr, make in FAULTS[name]():
            owner = importlib.import_module(module)
            if cls is not None:
                owner = getattr(owner, cls)
            old = getattr(owner, attr)
            setattr(owner, attr, make(old))
            undo.append((owner, attr, old))
        if mesh_path:  # the plane keeps its compiled step: build it anew
            importlib.import_module("juicefs_tpu.tpu.sharding")._reset_plane_for_tests()
        yield
    finally:
        for owner, attr, old in reversed(undo):
            setattr(owner, attr, old)
        if mesh_path:
            importlib.import_module("juicefs_tpu.tpu.sharding")._reset_plane_for_tests()
