"""Object storage abstraction (reference: pkg/object, SURVEY.md §2.1).

Drivers register by scheme; `create_storage` composes the optional wrappers
exactly like the reference mount path (cmd/mount.go NewReloadableStorage →
prefix/shard/encrypt):

    create_storage("file:///var/jfs/vol/")       local-disk store
    create_storage("mem://")                     in-proc store (tests)
    sharded(...)  with_prefix(...)  new_encrypted(...)  new_checksummed(...)
"""

from __future__ import annotations

from typing import Callable

from .interface import (
    CorruptObjectError,
    Obj,
    ObjectStorage,
    NotFoundError,
    PermanentError,
    ThrottleError,
)
from .file import FileStorage
from .mem import MemStorage
from .metered import MeteredStorage, metered
from .resilient import (
    BreakerOpenError,
    CircuitBreaker,
    DeadlineExceeded,
    ResilientStorage,
    RetryPolicy,
    resilient,
)
from .prefix import with_prefix
from .sharding import sharded
from .checksum import new_checksummed, crc32c
from .encrypt import (
    AESGCMDataEncryptor,
    RSAEncryptor,
    new_encrypted,
    generate_rsa_key_pem,
)

_registry: dict[str, Callable[[str], ObjectStorage]] = {}


def register(scheme: str, factory: Callable[[str], ObjectStorage]) -> None:
    _registry[scheme] = factory


def create_storage(uri: str) -> ObjectStorage:
    """Open an object store by URI (reference object_storage.go CreateStorage)."""
    if "://" not in uri:
        uri = "file://" + uri
    scheme, addr = uri.split("://", 1)
    scheme = scheme.lower()
    if scheme not in _registry:
        raise ValueError(f"invalid object storage: {scheme}")
    return _registry[scheme](addr)


def _s3_factory(addr: str) -> ObjectStorage:
    from .s3 import S3Storage

    return S3Storage(addr)


def _gs_factory(addr: str) -> ObjectStorage:
    from .gs import GSStorage

    return GSStorage(addr)


def _azure_factory(addr: str) -> ObjectStorage:
    from .azure import AzureBlobStorage

    return AzureBlobStorage(addr)


def _webdav_factory(addr: str) -> ObjectStorage:
    from .webdav import WebDAVStorage

    return WebDAVStorage(addr)


def _sqlite_factory(addr: str) -> ObjectStorage:
    from .dbstore import SqliteStorage

    return SqliteStorage(addr)


def _redis_obj_factory(addr: str) -> ObjectStorage:
    from .dbstore import RedisStorage

    return RedisStorage(addr)


register("file", lambda addr: FileStorage(addr))
register("mem", lambda addr: MemStorage(addr))
register("s3", _s3_factory)
register("minio", _s3_factory)
register("webdav", _webdav_factory)
register("azure", _azure_factory)
register("wasb", _azure_factory)
register("gs", _gs_factory)
register("sqlite3", _sqlite_factory)
register("sqlite", _sqlite_factory)
register("redis", _redis_obj_factory)

__all__ = [
    "Obj",
    "ObjectStorage",
    "NotFoundError",
    "PermanentError",
    "CorruptObjectError",
    "ThrottleError",
    "FileStorage",
    "MemStorage",
    "create_storage",
    "register",
    "metered",
    "MeteredStorage",
    "resilient",
    "ResilientStorage",
    "RetryPolicy",
    "CircuitBreaker",
    "BreakerOpenError",
    "DeadlineExceeded",
    "with_prefix",
    "sharded",
    "new_checksummed",
    "crc32c",
    "new_encrypted",
    "AESGCMDataEncryptor",
    "RSAEncryptor",
    "generate_rsa_key_pem",
]
