"""Device resolution: the one place that answers, for this process, which
device a hash backend runs on — and refuses when it is not the one asked
for.

Every consumer above tpu/ (HashPipeline and through it the write-path
indexer, inline ingest, `gc --dedup`, `fsck --verify-data`, `format`'s
probe, the gateway's /metrics) resolves its backend name here and prints
the report built here, so a CPU run can never be read as a chip run:

  resolve_backend(name)   requested name -> the HashPipeline backend that
                          will run ("tpu" means a TPU, or DeviceUnavailable)
  device_report(...)      platform, device_kind, device counts, mesh,
                          degraded + reason, resolved backend, Pallas mode
  configure_compile_cache the persistent XLA compile cache, placed from
                          outside by JAX_COMPILATION_CACHE_DIR

Policy for `--hash-backend tpu` without a TPU (README "Hash backends"): the
command fails and names the platform JAX found. That includes a chip-less
client of a volume formatted with `tpu`: it refuses to start the write-path
indexer rather than fingerprint somewhere else; the operator who wants the
host hash there says so with `config META --hash-backend cpu` (digests are
byte-identical across backends, so the index stays valid).
"""

from __future__ import annotations

import os

# Names a user may give; "tpu" is a requirement on the platform, the rest
# name an implementation and say which platform they ran on.
HASH_BACKENDS = ("cpu", "xla", "pallas", "tpu")

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_MIN_COMPILE_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"


class DeviceUnavailable(RuntimeError):
    """The requested hash backend cannot run on the device it names."""


def default_compile_cache_dir() -> str:
    """`<checkout>/.jax_cache`: fixed, derived from the package location
    (the path is part of the cache key, so it must never move)."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def configure_compile_cache(environ=None, update=None) -> str:
    """Point JAX's persistent compile cache at a placeable directory and
    return it. With JAX_COMPILATION_CACHE_DIR set, JAX honours the
    variable itself and no directory is set in code. Called once, from
    `juicefs_tpu/tpu/__init__.py`, so every process that uses the TPU
    plane is configured before its first compilation. `environ`/`update`
    are injection points for tests."""
    environ = os.environ if environ is None else environ
    if update is None:
        import jax

        update = jax.config.update
    if _MIN_COMPILE_ENV not in environ:
        # the hash programs compile in about a second on a v5e — at the
        # default 1 s threshold some of them would never be written
        update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = environ.get(_CACHE_ENV)
    if path:
        return path
    path = default_compile_cache_dir()
    update("jax_compilation_cache_dir", path)
    return path


def resolve_backend(requested: str) -> str:
    """Map a requested hash backend (volume Format value or command-line
    flag) to the HashPipeline backend that will run: cpu | xla | pallas.

    "" (a volume with no write-path fingerprinting) scans on `cpu`.
    "tpu" requires `jax.devices()[0].platform == "tpu"` and resolves to
    the mesh-sharded XLA program; anywhere else it raises
    DeviceUnavailable naming the platform found. A failed backend init
    propagates — it is never turned into the host hash."""
    if requested in ("", "cpu"):
        return "cpu"
    if requested in ("xla", "pallas"):
        return requested
    if requested != "tpu":
        raise ValueError(
            f"unknown hash backend {requested!r} "
            f"(want {'|'.join(HASH_BACKENDS)})")
    import jax

    try:
        platform = jax.devices()[0].platform
    except Exception as e:
        raise DeviceUnavailable(
            f"hash backend 'tpu' needs a TPU, but JAX could not "
            f"initialise a backend: {e}") from e
    if platform != "tpu":
        raise DeviceUnavailable(
            f"hash backend 'tpu' needs a TPU, but JAX found platform "
            f"{platform!r}; use --hash-backend cpu for the host hash "
            f"(xla|pallas run on whatever platform JAX initialised)")
    return "xla"


def device_report(backend: str, requested: str | None = None) -> dict:
    """The device report every device-path output prints.

    `backend` is the RESOLVED backend. `devices` counts the devices that
    backend's program runs on, `visible_devices` what JAX sees: an xla
    pipeline rides the process-wide ShardPlane (mesh, degraded + reason
    come from its snapshot), while the Pallas kernel bypasses the plane
    and runs on the first device — the report says so instead of
    claiming the whole host."""
    requested = backend if requested is None else requested
    if backend == "cpu":
        from .. import native

        return {
            "platform": "host",
            "device_kind": "libjfscore" if native.available() else "numpy",
            "devices": 0, "visible_devices": 0,
            "requested": requested, "backend": backend,
            "pallas_mode": None, "mesh": None, "degraded": False,
            "reason": "host hash; no device touched",
        }
    import jax

    devs = jax.devices()
    report = {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "devices": 1, "visible_devices": len(devs),
        "requested": requested, "backend": backend,
        "pallas_mode": None, "mesh": None, "degraded": False,
        "reason": "", "jax": jax.__version__,
    }
    if backend == "pallas":
        from .hash_jax import pallas_interpret_active

        report["pallas_mode"] = (
            "interpret" if pallas_interpret_active() else "compiled")
        report["reason"] = "pallas kernel runs on the first device"
    else:
        from . import sharding

        report.update(sharding.get_plane().snapshot())
        # every counted degrade so far (mesh init, odd count, or a batch
        # the mesh could not split): 0 is what a healthy host reports
        report["shard_degraded"] = int(sharding._DEGRADED.value)
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs
    ]
    peaks = [p for p in peaks if p is not None]
    if peaks:
        report["peak_bytes_in_use"] = max(peaks)
    return report
