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
  count_compiles          juicefs_tpu_compiles / _compile_seconds: every
                          program JAX built or loaded from that cache

Policy for `--hash-backend tpu` without a TPU (README "Hash backends"): the
command fails and names the platform JAX found. That includes a chip-less
client of a volume formatted with `tpu`: it refuses to start the write-path
indexer rather than fingerprint somewhere else; the operator who wants the
host hash there says so with `config META --hash-backend cpu` (digests are
byte-identical across backends, so the index stays valid).
"""

from __future__ import annotations

import os
import threading

# No import of the package at module level: chip_smoke.py loads this file by
# path, outside `juicefs_tpu`, for `default_compile_cache_dir` alone. What
# needs the metrics registry or the tracer imports it where it runs.

# Names a user may give; "tpu" is a requirement on the platform, the rest
# name an implementation and say which platform they ran on.
HASH_BACKENDS = ("cpu", "xla", "pallas", "tpu")

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_MIN_COMPILE_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"


# JAX 0.9.0 (jax/_src/compiler.py, pxla.py): one duration event for every
# program made ready, built or loaded. On the way, on the same thread and
# before the duration: a plain event when the request goes to the persistent
# cache at all, and another if the cache had the program
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


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


def count_compiles(monitoring=None) -> None:
    """Feed `juicefs_tpu_compiles{source}` and `_compile_seconds{source}`
    from JAX's monitoring events. Called once, beside the compile cache's
    configuration (`juicefs_tpu/tpu/__init__.py`); `monitoring` is an
    injection point for tests."""
    from ..metric import global_registry

    if monitoring is None:
        import jax.monitoring as monitoring
    reg = global_registry()
    compiles = reg.counter(
        "juicefs_tpu_compiles",
        "Programs JAX made ready to run, by where they came from: built "
        "by the compiler, or loaded from the persistent compile cache",
        ("source",),
    )
    compile_seconds = reg.histogram(
        "juicefs_tpu_compile_seconds",
        "Wall time of making one program ready (compile, or cache load)",
        ("source",),
        buckets=(0.01, 0.05, 0.1, 0.5, 1, 2, 5, 10, 30, 60, 300),
    )
    local = threading.local()
    series = {source: (compiles.labels(source),
                       compile_seconds.labels(source))
              for source in ("built", "cache")}

    def on_event(event: str, **kw) -> None:
        if event == _CACHE_REQUEST_EVENT:
            # every request starts as a build: a hit that no duration
            # followed on this thread must not mark the next program
            local.hit = False
        elif event == _CACHE_HIT_EVENT:
            local.hit = True

    def on_duration(event: str, duration: float, **kw) -> None:
        if event == _COMPILE_EVENT:
            hit, local.hit = getattr(local, "hit", False), False
            count, seconds = series["cache" if hit else "built"]
            count.inc()
            seconds.observe(duration)

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


def init_span():
    """The `tpu.device.init` span: what a process pays once to have its
    devices — the backend coming up here, the plane in tpu/sharding.py."""
    from ..metric.trace import global_tracer, stage_hist

    return global_tracer().span("tpu", "device", stage="init",
                                hist=stage_hist("tpu", "device", "init"))


_backend_up = False


def _devices():
    """`jax.devices()`; the call that brings the backend up (the first
    one this module makes) runs under the `tpu.device.init` span."""
    global _backend_up
    import jax

    if _backend_up:
        return jax.devices()
    with init_span() as sp:
        devs = jax.devices()
        if sp.active:
            sp.set(platform=devs[0].platform, devices=len(devs))
    _backend_up = True
    return devs


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
    try:
        platform = _devices()[0].platform
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
