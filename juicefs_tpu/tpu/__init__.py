"""TPU data plane (the new, TPU-first capability; SURVEY.md §7 stage 4).

The reference's block data plane is CPU-side cgo (zstd/lz4 compression,
CRC32C checksums — pkg/compress/compress.go:31-49, pkg/object/checksum.go:28)
and its gc/fsck scans diff block *names* only (cmd/gc.go:253-296,
cmd/fsck.go:174-200). This package adds the north-star TPU capability:
content hashing and content-addressed dedup scanning as batched JAX/Pallas
programs, behind the chunk-store boundary, selected by --hash-backend=tpu.

Modules:
  jth256    — normative JTH-256 hash spec + numpy reference (byte-identical bar)
  hash_jax  — batched jit/pallas implementations of the same spec
  dedup     — sort-based duplicate scan over digest batches
  pipeline  — double-buffered host->device streaming hash pipeline
  sharding  — the multichip plane: (data x lane) mesh over all local
              devices, sharded placement + hash/dedup/estimator programs,
              single-device-jit degrade ladder (ISSUE 20)
  device    — the one resolver: requested backend -> what runs, the device
              report every device-path output prints, the compile cache
"""

from .device import configure_compile_cache, count_compiles

# The ONE place the persistent compile cache is set: importing any module
# of this package runs this before that process's first compilation, and
# counts every program compiled or loaded from then on.
configure_compile_cache()
count_compiles()

from .jth256 import (
    BLOCK_BYTES,
    LANE_BYTES,
    digest_hex,
    hash_blocks_np,
    jth256,
    pack_blocks,
)
from .hash_jax import hash_blocks_jax, hash_packed_jax, make_hash_fn
from .dedup import dedup_digests, dedup_scan_jax
from .pipeline import HashPipeline, PipelineConfig
from .sharding import (
    ShardedPack,
    ShardPlane,
    get_plane,
    make_mesh,
    shard_batch,
    sharded_hash_step,
    sharded_scan_step,
)

__all__ = [
    "BLOCK_BYTES",
    "LANE_BYTES",
    "jth256",
    "digest_hex",
    "pack_blocks",
    "hash_blocks_np",
    "hash_blocks_jax",
    "hash_packed_jax",
    "make_hash_fn",
    "dedup_digests",
    "dedup_scan_jax",
    "HashPipeline",
    "PipelineConfig",
    "make_mesh",
    "shard_batch",
    "sharded_hash_step",
    "sharded_scan_step",
    "ShardedPack",
    "ShardPlane",
    "get_plane",
]
