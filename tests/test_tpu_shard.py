"""Multichip sharding plane drills (ISSUE 20).

Two tiers:

* In-process tests ride conftest's suite-wide forced-host environment
  (``XLA_FLAGS=--xla_force_host_platform_device_count=8`` + cpu
  platform): mesh geometry, the ONE-sharded-transfer-per-batch counter,
  byte-identity of digests/dedup verdicts/estimator advisories against
  the single-device plane, and the degrade ladder (odd device counts,
  mesh-init failure, indivisible batches) — counted, never an error.

* ``forced_host`` tests spawn their OWN subprocess per device count
  (1/2/4/8 and odd 3) with the flag set before jax initializes, so the
  count is real for that interpreter and cannot leak into other tests.
  Each subprocess asserts digests, dedup verdicts and advisories are
  byte-identical to the numpy/single-device references over the full
  shape suite (ragged / empty / 1-byte / exactly-4MiB).
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from juicefs_tpu.tpu import dedup_digests, jth256, pack_blocks  # noqa: E402
from juicefs_tpu.tpu.jth256 import digests_to_bytes  # noqa: E402
from juicefs_tpu.tpu import sharding  # noqa: E402
from juicefs_tpu.tpu.pipeline import HashPipeline, PipelineConfig  # noqa: E402

REPO = Path(__file__).resolve().parent.parent


def _blocks(rng, block_bytes=1 << 20):
    """The acceptance shape suite: ragged sizes, 1-byte, a cross-batch
    duplicate, and an exactly-full block."""
    return [
        rng.integers(0, 256, size=block_bytes, dtype=np.uint8).tobytes(),
        b"\x07",
        rng.integers(0, 256, size=100_000, dtype=np.uint8).tobytes(),
        b"\x07",
        rng.integers(0, 256, size=block_bytes - 1, dtype=np.uint8).tobytes(),
    ]


@pytest.fixture
def plane():
    p = sharding.get_plane()
    if p.mesh is None or len(jax.devices()) < 8:
        pytest.skip("needs the 8 forced host devices")
    return p


def test_plane_mesh_over_all_devices(plane):
    snap = plane.snapshot()
    assert snap["devices"] == 8
    assert snap["mesh"] == {"data": 4, "lane": 2}
    assert not snap["degraded"]


def test_put_packed_counts_one_sharded_transfer_and_pads(plane):
    rng = np.random.default_rng(1)
    packed = pack_blocks(_blocks(rng), pad_lanes=16)
    before = sharding._H2D_BATCHES.value
    sp = plane.put_packed(*packed)
    # ONE sharded host->device transfer per batch, counter-asserted
    assert sharding._H2D_BATCHES.value == before + 1
    assert isinstance(sp, sharding.ShardedPack)
    assert sp.batch == 5
    # 5 ragged blocks pad up to the data-axis extent (4 -> 8 rows)
    assert sp[0].shape[0] == 8 and sp[1].shape[0] == 8
    # placed with the mesh sharding, not replicated on one device
    assert getattr(sp[0].sharding, "mesh", None) is not None
    # hashing the placed pack does NOT transfer again
    mid = sharding._H2D_BATCHES.value
    dig = plane.hash_packed(*sp, n=sp.batch)
    assert sharding._H2D_BATCHES.value == mid
    assert dig.shape == (5, 8)


def test_hash_byte_identity_every_shape(plane):
    rng = np.random.default_rng(2)
    blocks = _blocks(rng)
    refs = [jth256(b) for b in blocks]
    got = digests_to_bytes(plane.hash_packed(*pack_blocks(blocks,
                                                          pad_lanes=16)))
    assert got == refs
    # empty batch: no device work, shape (0, 8)
    empty = plane.hash_packed(*pack_blocks([], pad_lanes=16))
    assert empty.shape == (0, 8)
    # single 1-byte block (B=1 is indivisible by data=4: single-device
    # rung, still byte-identical)
    one = digests_to_bytes(plane.hash_packed(*pack_blocks([b"x"],
                                                          pad_lanes=16)))
    assert one == [jth256(b"x")]


def test_scan_packed_dedup_matches_reference(plane):
    rng = np.random.default_rng(3)
    blocks = _blocks(rng)
    refs = [jth256(b) for b in blocks]
    rdup, rfirst = dedup_digests(refs)
    d, dup, first = plane.scan_packed(*pack_blocks(blocks, pad_lanes=16))
    assert digests_to_bytes(d) == refs
    assert list(dup) == list(rdup)
    assert list(first) == list(rfirst)


def test_estimator_advisory_identity_sharded_vs_single(plane):
    from juicefs_tpu.tpu.compress_batch import _make_estimator

    rng = np.random.default_rng(4)
    packed = pack_blocks(_blocks(rng), pad_lanes=16)
    single = np.asarray(_make_estimator()(packed[0], packed[1]))
    sp = plane.put_packed(*packed)
    pred = np.asarray(plane.make_estimator()(sp[0], sp[1]))[: sp.batch]
    # the integer-valued histogram psum is exact, so the advisory is not
    # merely close — it is bit-identical to the single-device plane
    assert np.array_equal(single, pred)


def test_pipeline_stream_routes_through_plane(plane):
    rng = np.random.default_rng(5)
    blocks = _blocks(rng) + [b"tail"]
    pipe = HashPipeline(PipelineConfig(backend="xla", batch_blocks=4,
                                       pad_lanes=16))
    assert pipe.device_backend and pipe._plane is plane
    before = sharding._H2D_BATCHES.value
    got = pipe.hash_blocks(blocks)
    assert got == [jth256(b) for b in blocks]
    # 6 blocks at batch_blocks=4 -> exactly 2 sharded transfers
    assert sharding._H2D_BATCHES.value == before + 2


def test_shard_packed_then_hash_packed_slices_to_n(plane):
    rng = np.random.default_rng(6)
    blocks = _blocks(rng)
    pipe = HashPipeline(PipelineConfig(backend="xla", pad_lanes=16))
    packed = pipe.shard_packed(pack_blocks(blocks, pad_lanes=16))
    assert isinstance(packed, sharding.ShardedPack)
    got = pipe.hash_packed(*packed, n=len(blocks))
    assert got == [jth256(b) for b in blocks]


def test_degrade_odd_device_counts_counted_never_error():
    devs = jax.devices()
    if len(devs) < 5:
        pytest.skip("needs the 8 forced host devices")
    rng = np.random.default_rng(7)
    blocks = _blocks(rng)
    refs = [jth256(b) for b in blocks]
    for n in (3, 5):
        before = sharding._DEGRADED.value
        p = sharding.ShardPlane(devices=devs[:n])
        assert p.mesh is None
        assert sharding._DEGRADED.value == before + 1
        assert p.snapshot()["degraded"]
        assert "odd" in p.snapshot()["reason"]
        got = digests_to_bytes(p.hash_packed(*pack_blocks(blocks,
                                                          pad_lanes=16)))
        assert got == refs


def test_degrade_mesh_init_failure_counted_never_error(monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("no mesh for you")

    monkeypatch.setattr(sharding, "make_mesh", boom)
    before = sharding._DEGRADED.value
    p = sharding.ShardPlane()
    assert p.mesh is None
    assert sharding._DEGRADED.value == before + 1
    assert "mesh init failed" in p.snapshot()["reason"]
    got = digests_to_bytes(p.hash_packed(*pack_blocks([b"a", b"bb"],
                                                      pad_lanes=4)))
    assert got == [jth256(b"a"), jth256(b"bb")]


def test_indivisible_lane_batch_degrades_counted(plane):
    # pad_lanes=1 (64 KiB blocks) cannot split across lane=2: the plane
    # takes the single-device placement for THAT batch, counts it, and
    # stays byte-identical
    blocks = [b"a" * 100, b"z" * 65536]
    packed = pack_blocks(blocks, pad_lanes=1)
    before = sharding._DEGRADED.value
    sp = plane.put_packed(*packed)
    assert sharding._DEGRADED.value == before + 1
    got = digests_to_bytes(plane.hash_packed(*sp, n=sp.batch))
    assert got == [jth256(b) for b in blocks]


def test_single_device_plane_degrades_uncounted():
    # one device is the natural cpu-fallback rung (SNIPPETS [1]), not a
    # fault: no degrade count
    before = sharding._DEGRADED.value
    p = sharding.ShardPlane(devices=jax.devices()[:1])
    assert p.mesh is None
    assert sharding._DEGRADED.value == before
    assert p.snapshot() == {"devices": 1, "mesh": None, "degraded": True,
                            "reason": "single device"}


def test_pipeline_defaults_pinned():
    # survivor drills (mutation round 1): the documented perf contract —
    # 32-block batches padded to a full 4 MiB block's 64 lanes, classic
    # double buffering, 64-block batcher queue
    from juicefs_tpu.tpu.pipeline import HashBatcher

    cfg = PipelineConfig()
    assert cfg.batch_blocks == 32
    assert cfg.pad_lanes == 64
    assert cfg.max_inflight_batches == 2
    hb = HashBatcher(HashPipeline(PipelineConfig(backend="cpu")))
    assert hb._q.maxsize == 64
    hb.close()


def test_dispatch_boundary_exact_batch_count(plane):
    # 9 blocks at batch_blocks=4 dispatch as 4+4+1 — a boundary mutant
    # (dispatch past instead of at the batch size) ships 5+4 and the
    # sharded-transfer counter catches it
    blocks = [b"block-%d" % i for i in range(9)]
    pipe = HashPipeline(PipelineConfig(backend="xla", batch_blocks=4,
                                       pad_lanes=16))
    before = sharding._H2D_BATCHES.value
    assert pipe.hash_blocks(blocks) == [jth256(b) for b in blocks]
    assert sharding._H2D_BATCHES.value == before + 3


def test_mesh_policy_exact_shapes():
    # survivor drills (mutation round 1): the lane-axis policy term by
    # term — n=4 exercises the >= boundary (a `> 4` mutant drops to
    # lane=1), n=6 the conjunction (an `or` mutant splits 3x2)
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8 forced host devices")
    assert sharding.ShardPlane(devices=devs[:4]).snapshot()["mesh"] == \
        {"data": 2, "lane": 2}
    assert sharding.ShardPlane(devices=devs[:6]).snapshot()["mesh"] == \
        {"data": 6, "lane": 1}
    # make_mesh's n_data default derives by floor-division of the device
    # count (a `*` mutant asks for 16 devices and raises)
    assert dict(sharding.make_mesh(n_lane=2, devices=devs).shape) == \
        {"data": 4, "lane": 2}


def test_empty_batch_put_is_not_a_degrade(plane):
    before = sharding._DEGRADED.value
    sp = plane.put_packed(*pack_blocks([], pad_lanes=16))
    assert sp.batch == 0
    assert sharding._DEGRADED.value == before


def test_preplaced_indivisible_batch_takes_single_path(plane):
    # arrays placed OUTSIDE put_packed (so unpadded: B=5 does not divide
    # data=4) must route to the single-device program — an inverted
    # divisibility check would feed shard_map an unsplittable batch
    rng = np.random.default_rng(8)
    blocks = _blocks(rng)
    refs = [jth256(b) for b in blocks]
    packed = tuple(jax.device_put(a)
                   for a in pack_blocks(blocks, pad_lanes=16))
    got = digests_to_bytes(plane.hash_packed(*packed))
    assert got == refs
    d, dup, first = plane.scan_packed(*packed)
    rdup, rfirst = dedup_digests(refs)
    assert digests_to_bytes(d) == refs
    assert list(dup) == list(rdup) and list(first) == list(rfirst)


# ---------------------------------------------------------------------------
# forced_host subprocess tier: real device counts, one interpreter each
# ---------------------------------------------------------------------------

_WORKER = r"""
import os, sys
import numpy as np

n = int(sys.argv[1])
assert os.environ["XLA_FLAGS"].endswith(str(n))
import jax
assert len(jax.devices()) == n, (len(jax.devices()), n)

from juicefs_tpu.tpu import dedup_digests, jth256, pack_blocks
from juicefs_tpu.tpu.jth256 import digests_to_bytes
from juicefs_tpu.tpu import sharding
from juicefs_tpu.tpu.compress_batch import _make_estimator

plane = sharding.get_plane()
snap = plane.snapshot()
if n in (1, 2, 4, 8):
    want_mesh = {1: None, 2: {"data": 2, "lane": 1},
                 4: {"data": 2, "lane": 2}, 8: {"data": 4, "lane": 2}}[n]
    assert snap["mesh"] == want_mesh, snap
    assert sharding._DEGRADED.value == 0, snap
else:
    assert snap["degraded"] and sharding._DEGRADED.value == 1, snap

rng = np.random.default_rng(42)
BB = 1 << 22  # exactly-4MiB block
shapes = [
    [rng.integers(0, 256, size=BB, dtype=np.uint8).tobytes(),  # full 4MiB
     b"\x07",                                                  # 1 byte
     rng.integers(0, 256, size=100_000, dtype=np.uint8).tobytes(),
     b"\x07",                                                  # duplicate
     rng.integers(0, 256, size=BB - 1, dtype=np.uint8).tobytes()],  # ragged
    [],                                                        # empty
    [b"x"],                                                    # single
]
for blocks in shapes:
    refs = [jth256(b) for b in blocks]
    packed = pack_blocks(blocks, pad_lanes=64)
    assert digests_to_bytes(plane.hash_packed(*packed)) == refs
    d, dup, first = plane.scan_packed(*packed)
    rdup, rfirst = dedup_digests(refs)
    assert digests_to_bytes(d) == refs
    assert list(dup) == list(rdup) and list(first) == list(rfirst)
    if blocks:
        single = np.asarray(_make_estimator()(packed[0], packed[1]))
        sp = plane.put_packed(*packed)
        pred = np.asarray(plane.make_estimator()(sp[0], sp[1]))[: sp.batch]
        assert np.array_equal(single, pred), (single, pred)
print("OK devices=%d mesh=%s" % (n, snap["mesh"]))
"""


def _run_forced(n: int) -> str:
    """Run the worker on n forced-host CPU devices. The parent (pytest)
    has usually initialised a JAX backend already; spawning from it is
    right only because the child is pinned to `JAX_PLATFORMS=cpu` and so
    can never need a chip the parent holds."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env.pop("JFS_DRYRUN_REAL_TPU", None)
    out = subprocess.run(
        [sys.executable, "-c", _WORKER, str(n)],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, f"n={n}\n{out.stdout}\n{out.stderr}"
    return out.stdout


@pytest.mark.parametrize("n", [1, 2, 4, 8])
def test_forced_host_byte_identity(n):
    assert f"OK devices={n}" in _run_forced(n)


def test_forced_host_odd_count_degrades():
    out = _run_forced(3)
    assert "OK devices=3 mesh=None" in out
