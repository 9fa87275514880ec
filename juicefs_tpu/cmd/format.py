"""`format`: create/overwrite a volume (reference cmd/format.go).

Writes the Format JSON into the meta engine and smoke-tests the object
store with a put/get/delete round trip, as the reference does.
"""

from __future__ import annotations

from ..meta import new_client
from ..meta.types import Format
from ..utils import get_logger

logger = get_logger("cmd.format")


def add_parser(sub):
    p = sub.add_parser("format", help="format a volume")
    p.add_argument("meta_url", help="meta engine address (sqlite3://..., mem://)")
    p.add_argument("name", help="volume name")
    p.add_argument("--storage", default="file", help="object store scheme")
    p.add_argument("--bucket", default="", help="bucket / base path")
    p.add_argument("--block-size", type=int, default=4096, help="block size KiB")
    p.add_argument("--compress", default="", choices=["", "none", "lz4", "zstd"])
    p.add_argument("--shards", type=int, default=0)
    p.add_argument("--capacity", type=int, default=0, help="capacity GiB (0=unlimited)")
    p.add_argument("--inodes", type=int, default=0)
    p.add_argument("--trash-days", type=int, default=1)
    p.add_argument("--enable-acl", action="store_true",
                   help="enable POSIX ACLs (system.posix_acl_* xattrs)")
    p.add_argument("--hash-backend", default="",
                   choices=["", "none", "cpu", "tpu", "xla", "pallas"],
                   help="fingerprint every written block into the meta "
                        "content index using this hash plane")
    p.add_argument("--encrypt-rsa-key", default="",
                   help="PEM private key path (RSA -> OAEP wrap, EC P-256 "
                        "-> ECIES wrap)")
    p.add_argument("--encrypt-algo", default=None,
                   choices=["aes256gcm-rsa", "aes256ctr-rsa"],
                   help="object body cipher (reference encrypt.go variants); "
                        "requires --encrypt-rsa-key")
    p.add_argument("--force", action="store_true", help="overwrite existing format")
    p.set_defaults(func=run)


def run(args) -> int:
    fmt = Format(
        name=args.name,
        storage=args.storage,
        bucket=args.bucket,
        block_size=args.block_size,
        compression="" if args.compress == "none" else args.compress,
        shards=args.shards,
        capacity=args.capacity << 30,
        inodes=args.inodes,
        trash_days=args.trash_days,
        enable_acl=args.enable_acl,
        hash_backend="" if args.hash_backend == "none" else args.hash_backend,
    )
    if args.encrypt_algo and not args.encrypt_rsa_key:
        logger.error("--encrypt-algo has no effect without --encrypt-rsa-key")
        return 1
    if args.encrypt_rsa_key:
        with open(args.encrypt_rsa_key) as f:
            fmt.encrypt_key = f.read()
        fmt.encrypt_algo = args.encrypt_algo or "aes256gcm-rsa"

    from . import storage_for

    store = storage_for(fmt)
    store.create()
    # object store smoke test (reference format.go test() round trip)
    probe = "testing/probe"
    store.put(probe, b"juicefs-tpu")
    if bytes(store.get(probe)) != b"juicefs-tpu":
        raise IOError("object storage probe read mismatch")
    store.delete(probe)

    if fmt.hash_backend in ("tpu", "xla", "pallas"):
        _probe_device_bandwidth(fmt.hash_backend)

    m = new_client(args.meta_url)
    st = m.init(fmt, force=args.force)
    if st != 0:
        logger.error("init meta: errno %d", st)
        return 1
    print(f"volume {args.name} formatted: meta={args.meta_url} "
          f"storage={fmt.storage}://{fmt.bucket} block={fmt.block_size}KiB")
    return 0


def _probe_device_bandwidth(backend: str, probe_mb: int = 16) -> None:
    """Resolve the device hash backend and measure host->device bandwidth
    before a volume is opted into it: write-path fingerprinting streams
    every block to the accelerator, so the operator should see at format
    time which device that is and how fast the host reaches it.

    `tpu` without a TPU, or a backend that cannot initialise, raises
    (tpu/device.py) and the volume is not formatted — the same answer
    every later command on that volume would give."""
    import json
    import time

    import jax
    import numpy as np

    from ..tpu.device import device_report, resolve_backend

    resolved = resolve_backend(backend)
    dev = jax.devices()[0]
    gibs = None  # a host-to-host copy is not a device figure
    if dev.platform != "cpu":
        buf = np.zeros(probe_mb << 20, dtype=np.uint8)
        jax.device_put(buf, dev).block_until_ready()  # allocator, first use
        t0 = time.perf_counter()
        jax.device_put(buf, dev).block_until_ready()
        gibs = round(probe_mb / 1024 / (time.perf_counter() - t0), 3)
        if gibs < 1.0:
            logger.warning(
                "--hash-backend %s: host->device bandwidth measured at "
                "%.3f GiB/s (%s) — below block-write rates, so the "
                "write-path indexer will mostly drop-and-backfill; "
                "consider --hash-backend cpu for this host",
                backend, gibs, dev.device_kind,
            )
    print("hash backend: " + json.dumps({
        **device_report(resolved, backend), "h2d_probe_gibs": gibs,
    }))
