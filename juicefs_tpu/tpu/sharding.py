"""Multi-chip sharding for the scan pipeline (SURVEY.md §2.3, §7).

The reference scales its scans with goroutine pools on one host and an
ssh-launched manager/worker cluster for sync (pkg/sync/cluster.go:132,237).
The TPU-native equivalent is SPMD over a jax.sharding.Mesh with two axes:

  data — blocks of the batch (the DP analog): embarrassingly parallel,
         no communication until the final dedup, which all_gathers only
         32-byte digests (not block data) over ICI.
  lane — 64 KiB lanes *within* a block (the SP/sequence-parallel analog):
         the heavy row chains run sharded, then an all_gather of the tiny
         per-lane digests (B x M x 8 words) precedes the short sequential
         combine, which every device replays identically.

So the bytes that cross ICI are ~1/2048th of the bytes hashed; the design
follows the scaling-book recipe: annotate shardings, let XLA insert the
collectives, keep them on ICI.

ISSUE 20 promotes this module from bench helpers to the process-wide
*sharding plane* (`ShardPlane` / `get_plane()`): the single seam through
which every device consumer above tpu/ — the hash pipeline, the dedup
scan, the compress estimator, inline ingest's shared pack — places data
on devices and runs sharded programs. Degrade ladder (never an error):

  all local devices, even count >= 2   -> (data, lane) mesh, pjit-sharded
  one device / odd count / mesh-init   -> single-device jit (counted in
  failure                                 juicefs_tpu_shard_degraded)

Ragged batches pad B up to the data-axis extent by repeating the last
block (self-duplicating pad rows cannot perturb dup_mask/first_idx of
real rows); outputs are gathered replicated and sliced back, so digests,
dedup verdicts and estimator advisories are byte-identical to the
single-device plane at every batch shape.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..metric import global_registry
from ..metric.trace import global_tracer, stage_hist
from ..utils import get_logger
from .dedup import dedup_scan_jax
from .device import init_span
from .hash_jax import (
    _combine_accs,
    _lane_accs,
    _lane_states,
    _row_chain_scan,
    make_hash_fn,
    named_jit,
)

logger = get_logger("tpu.shard")

_reg = global_registry()
_DEVICES = _reg.gauge(
    "juicefs_tpu_shard_devices",
    "Devices in the sharding plane's mesh (1 = single-device jit)",
)
_H2D_BATCHES = _reg.counter(
    "juicefs_tpu_shard_h2d_batches",
    "Packed batches placed on devices by the sharding plane (ONE "
    "host->device transfer per batch feeds hash + estimator)",
)
_DEGRADED = _reg.counter(
    "juicefs_tpu_shard_degraded",
    "Sharding-plane degrades to single-device jit (odd device count, "
    "mesh-init failure, or an indivisible batch at call time)",
)
_TR = global_tracer()
_H_H2D = stage_hist("tpu", "hash", "h2d")
_H_ENQUEUE = stage_hist("tpu", "hash", "enqueue")


# Every shard_map below passes check_vma=False: the per-device bodies
# all_gather to fully replicated outputs themselves, which the
# varying-manual-axes check cannot see through.


def make_mesh(
    n_data: int | None = None, n_lane: int = 1, devices=None
) -> Mesh:
    """Build a (data, lane) mesh over the given (default: all) devices."""
    devices = list(devices if devices is not None else jax.devices())
    if n_data is None:
        n_data = len(devices) // n_lane
    used = n_data * n_lane
    if used > len(devices):
        raise ValueError(f"mesh {n_data}x{n_lane} needs {used} devices, have {len(devices)}")
    arr = np.array(devices[:used]).reshape(n_data, n_lane)
    return Mesh(arr, ("data", "lane"))


def _scan_body(words, lane_counts, lengths):
    """The per-device scan body: row chains on local lanes, gather tiny
    per-lane digests across the lane axis, combine, gather 32 B/block
    digests across data, dedup."""
    all_digests = _hash_body(words, lane_counts, lengths)
    dup, first = dedup_scan_jax(all_digests)
    return all_digests, dup, first


def _hash_body(words, lane_counts, lengths):
    """Per-device hash: row chains on the local lanes, the tiny per-lane
    digests gathered across the lane axis, the combine every device
    replays, the 32 B/block digests gathered across data."""
    local_m = words.shape[1]
    loff = lax.axis_index("lane") * local_m
    s = _row_chain_scan(words, _lane_states(words, loff))
    accs = _lane_accs(s, loff)
    with jax.named_scope("lane_all_gather"):
        acc = lax.all_gather(accs, "lane", axis=1, tiled=True)
    digests = _combine_accs(acc, lane_counts, lengths)
    with jax.named_scope("data_all_gather"):
        return lax.all_gather(digests, "data", axis=0, tiled=True)


def sharded_scan_step(mesh: Mesh):
    """Compile the full multi-chip scan step over `mesh`.

    Returns a jitted fn (words (B,M,128,128), lane_counts (B,), lengths (B,))
    -> (digests (B,8), dup_mask (B,), first_idx (B,)); B must divide by the
    data axis and M by the lane axis. Outputs are fully replicated.
    """

    mapped = jax.shard_map(
        _scan_body,
        mesh=mesh,
        in_specs=(P("data", "lane", None, None), P("data"), P("data")),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return named_jit("jth256_scan_sharded", mapped)


def shard_batch(mesh: Mesh, words, lane_counts, lengths):
    """Device_put a packed batch with the scan step's input shardings.

    Ragged batches (B not divisible by the data axis — the tail of any
    real scan) are padded by repeating the LAST block: padded rows are
    valid hash inputs, and because they duplicate an earlier block they
    can only mark THEMSELVES as duplicates — dup_mask/first_idx for the
    original rows are unchanged.  Callers slice outputs back to their
    input length (`digests[:B]`, `dup[:B]`).
    """
    n_data = mesh.shape["data"]
    b = int(words.shape[0])
    pad = (-b) % n_data
    if pad:
        words = np.concatenate(
            [np.asarray(words)] + [np.asarray(words[-1:])] * pad, axis=0)
        lane_counts = np.concatenate(
            [np.asarray(lane_counts)] + [np.asarray(lane_counts[-1:])] * pad)
        lengths = np.concatenate(
            [np.asarray(lengths)] + [np.asarray(lengths[-1:])] * pad)
    ws = NamedSharding(mesh, P("data", "lane", None, None))
    bs = NamedSharding(mesh, P("data"))
    return (
        jax.device_put(words, ws),
        jax.device_put(lane_counts, bs),
        jax.device_put(lengths, bs),
    )


# ---------------------------------------------------------------------------
# The sharding plane (ISSUE 20): the one seam above which no caller touches
# jax.device_put / jax.jit directly (enforced by the tpu-shard-seam analyzer
# rule for chunk/).
# ---------------------------------------------------------------------------


class ShardedPack(tuple):
    """A packed (words, lane_counts, lengths) triple placed by the plane.

    Behaves as the plain tuple the PR 8 shared-pack contract passes
    around (``*packed`` unpacking, ``words, counts, lengths = packed``),
    but carries ``batch`` — the ORIGINAL block count before data-axis
    padding — so downstream consumers (hash metrics, estimator advisory)
    can slice gathered outputs back without re-deriving it.
    """

    def __new__(cls, arrays, batch: int):
        self = tuple.__new__(cls, arrays)
        self.batch = batch
        return self


def sharded_hash_step(mesh: Mesh):
    """Hash-only sharded step: (words, lane_counts, lengths) -> digests
    (B, 8), fully replicated. Same body as `sharded_scan_step` minus the
    dedup tail — the pipeline dedups on host against the meta index."""

    mapped = jax.shard_map(
        _hash_body,
        mesh=mesh,
        in_specs=(P("data", "lane", None, None), P("data"), P("data")),
        out_specs=P(),
        check_vma=False,
    )
    return named_jit("jth256_hash_sharded", mapped)


def sharded_estimate_step(mesh: Mesh):
    """Sharded compressibility estimator, byte-identical to the
    single-device `compress_batch._make_estimator` math.

    Each device histograms its local lanes' sampled bytes (lane offsets
    keep the padded-lane mask global), then `psum` merges histograms over
    the lane axis. The histogram bins are integer-valued float32 counts
    (<= 16384 per bin, exactly representable), so the psum is exact in
    any order and the downstream entropy math sees bit-identical inputs.
    """

    def est(words, lane_counts):
        b, m = words.shape[0], words.shape[1]
        loff = lax.axis_index("lane") * m
        sub = words[:, :, ::16, ::16].reshape(b, -1)  # (B, m_local*64)
        by = jnp.stack(
            [(sub >> jnp.uint32(8 * i)) & jnp.uint32(0xFF) for i in range(4)],
            axis=-1,
        ).reshape(b, -1).astype(jnp.int32)
        lanes = loff + jnp.arange(m, dtype=jnp.int32)
        mask = (lanes[None, :] < lane_counts[:, None]).astype(jnp.float32)
        w = jnp.repeat(mask, 256, axis=1)  # 256 sampled bytes per lane

        def hist(v, wt):
            return jnp.zeros((256,), jnp.float32).at[v].add(wt)

        h = lax.psum(jax.vmap(hist)(by, w), "lane")
        p = h / jnp.maximum(h.sum(-1, keepdims=True), 1.0)
        ent = -jnp.sum(jnp.where(p > 0, p * jnp.log2(p), 0.0), axis=-1)
        pred = jnp.minimum(ent / 8.0, 1.0)
        return lax.all_gather(pred, "data", axis=0, tiled=True)

    mapped = jax.shard_map(
        est,
        mesh=mesh,
        in_specs=(P("data", "lane", None, None), P("data")),
        out_specs=P(),
        check_vma=False,
    )
    return named_jit("compress_estimate_sharded", mapped)


class ShardPlane:
    """Process-wide multichip plane: mesh policy, sharded placement, and
    the hash/dedup/estimator programs every consumer routes through.

    Construction NEVER raises past backend init: any mesh failure lands
    on the single-device-jit rung with `juicefs_tpu_shard_degraded`
    counted and the reason in `snapshot()` (which every device report
    carries, tpu/device.py). A backend that cannot initialise at all
    raises, and the hash pipeline lets it.
    """

    def __init__(self, devices=None):
        devs = list(devices if devices is not None else jax.devices())
        self.devices = devs
        self.n_devices = max(1, len(devs))
        self.mesh: Mesh | None = None
        self.degrade_reason = ""
        self._hash_single = None  # built lazily on the degrade rung
        self._hash_sharded = None
        self._scan_sharded = None
        self._est_sharded = None
        n = len(devs)
        if n >= 2 and n % 2 == 0:
            try:
                n_lane = 2 if (n >= 4 and n % 4 == 0) else 1
                self.mesh = make_mesh(
                    n_data=n // n_lane, n_lane=n_lane, devices=devs
                )
            except Exception as e:  # mesh init failure -> single-device
                self.mesh = None
                self.degrade_reason = f"mesh init failed: {e}"
                _DEGRADED.inc()
                logger.warning(
                    "shard plane degraded to single-device jit: %s", e)
        elif n > 1:  # odd device count: no even (data, lane) factoring
            self.degrade_reason = f"odd device count {n}"
            _DEGRADED.inc()
            logger.warning(
                "shard plane degraded to single-device jit: %d devices",
                n)
        else:
            self.degrade_reason = "single device"
        _DEVICES.set(self.n_data * self.n_lane if self.mesh else 1)

    # -- mesh geometry ----------------------------------------------------
    @property
    def n_data(self) -> int:
        return self.mesh.shape["data"] if self.mesh is not None else 1

    @property
    def n_lane(self) -> int:
        return self.mesh.shape["lane"] if self.mesh is not None else 1

    def snapshot(self) -> dict:
        """Advisory stats block (gc --dedup, bench output, tests)."""
        return {
            "devices": self.n_data * self.n_lane if self.mesh else 1,
            "mesh": (
                {"data": self.n_data, "lane": self.n_lane}
                if self.mesh is not None else None
            ),
            "degraded": self.mesh is None,
            "reason": self.degrade_reason,
        }

    # -- placement --------------------------------------------------------
    def _shardable(self, words) -> bool:
        return (
            self.mesh is not None
            and words.shape[0] > 0
            and words.shape[1] % self.n_lane == 0
        )

    def put_packed(self, words, lane_counts, lengths) -> ShardedPack:
        """The ONE host->device transfer of the shared-pack contract.

        Pads B to a multiple of the data-axis extent (repeat-last-block,
        see `shard_batch`), places the triple with the scan's
        PartitionSpecs, and returns a `ShardedPack` remembering the
        original batch size. Indivisible shapes (lane axis not dividing
        M, empty batch) take the single-device placement instead —
        still exactly one transfer, still counted.
        """
        b = int(words.shape[0])
        sharded = self._shardable(words)
        with _TR.span("tpu", "hash", stage="h2d", hist=_H_H2D) as sp:
            if sp.active:
                sp.set(bytes=int(words.nbytes), sharded=sharded)
            if not sharded:
                if self.mesh is not None and b > 0:
                    _DEGRADED.inc()  # sharded plane active, batch can't split
                arrays = tuple(
                    jax.device_put(a) for a in (words, lane_counts, lengths))
            else:
                arrays = shard_batch(self.mesh, words, lane_counts, lengths)
        _H2D_BATCHES.inc()
        return ShardedPack(arrays, b)

    # -- programs ---------------------------------------------------------
    def hash_async(self, words, lane_counts, lengths):
        """Dispatch the hash program and return the (still-async) device
        array of gathered digests, padded length included — the streaming
        pipeline's double buffering needs dispatch to not block. Accepts
        host arrays (placed here: one counted transfer) or arrays already
        placed by `put_packed` (no second transfer)."""
        if not isinstance(words, jax.Array):
            words, lane_counts, lengths = self.put_packed(
                words, lane_counts, lengths)
        if (
            self._shardable(words)
            and int(words.shape[0]) % self.n_data == 0
        ):
            if self._hash_sharded is None:
                self._hash_sharded = sharded_hash_step(self.mesh)
            program = self._hash_sharded
        else:
            if self._hash_single is None:
                self._hash_single = make_hash_fn("xla")
            program = self._hash_single
        # the async call alone: a retrace or a recompile lands here
        with _TR.span("tpu", "hash", stage="enqueue", hist=_H_ENQUEUE):
            return program(words, lane_counts, lengths)

    def hash_packed(self, words, lane_counts, lengths, n: int | None = None):
        """(B, M, 128, 128) -> (n, 8) uint32 digests, byte-identical to
        the single-device plane. `n` slices gathered outputs back past
        any data-axis padding; defaults to the input batch size."""
        if n is None:
            n = int(words.shape[0])
        if n == 0:
            return np.zeros((0, 8), dtype=np.uint32)
        out = self.hash_async(words, lane_counts, lengths)
        return np.asarray(jax.device_get(out))[:n]

    def scan_packed(self, words, lane_counts, lengths, n: int | None = None):
        """Full scan step (digests + dedup verdicts), sliced back to the
        original batch. Pad rows only ever self-duplicate, so dup/first
        for real rows match the single-device `dedup_scan_jax` exactly."""
        if n is None:
            n = int(words.shape[0])
        if n == 0:
            e = np.zeros((0,), dtype=np.int32)
            return np.zeros((0, 8), dtype=np.uint32), e.astype(bool), e
        if not isinstance(words, jax.Array):
            words, lane_counts, lengths = self.put_packed(
                words, lane_counts, lengths)
        if (
            self._shardable(words)
            and int(words.shape[0]) % self.n_data == 0
        ):
            if self._scan_sharded is None:
                self._scan_sharded = sharded_scan_step(self.mesh)
            d, dup, first = self._scan_sharded(words, lane_counts, lengths)
        else:
            digests = self.hash_packed(words, lane_counts, lengths)
            d, dup, first = digests, *dedup_scan_jax(jnp.asarray(digests))
        return (
            np.asarray(jax.device_get(d))[:n],
            np.asarray(jax.device_get(dup))[:n],
            np.asarray(jax.device_get(first))[:n],
        )

    def make_estimator(self):
        """Estimator callable for the compress plane: (words, lane_counts)
        -> predicted ratio per block. Sharded over the mesh when the
        input divides; single-device jit otherwise. Backend-init errors
        propagate — raising is the CompressPlane's degrade signal."""
        from .compress_batch import _make_estimator

        single = _make_estimator()  # may raise -> caller degrades to cpu

        def est(words, lane_counts):
            if (
                self._shardable(words)
                and int(words.shape[0]) % self.n_data == 0
            ):
                if self._est_sharded is None:
                    self._est_sharded = sharded_estimate_step(self.mesh)
                return self._est_sharded(words, lane_counts)
            return single(words, lane_counts)

        return est


_plane_lock = threading.Lock()
_plane: ShardPlane | None = None


def get_plane() -> ShardPlane:
    """The process-wide plane, built over all local devices on first use.
    Backend-init failures propagate to the caller, which fails with
    them (tpu/pipeline.py: no silent host hash)."""
    global _plane
    with _plane_lock:
        if _plane is None:
            # once a process: jax.devices() (backend init, unless
            # tpu/device.py's resolver paid it already) and the mesh
            with init_span() as sp:
                _plane = ShardPlane()
                if sp.active:
                    sp.set(**_plane.snapshot())
        return _plane


def _reset_plane_for_tests() -> None:
    global _plane
    with _plane_lock:
        _plane = None
