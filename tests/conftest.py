"""Test harness: force JAX onto a virtual 8-device CPU mesh so sharding
paths are exercised hermetically (multi-chip TPU hardware is validated
separately by __graft_entry__.dryrun_multichip).

Set JFS_TEST_REAL_TPU=1 to run the suite against the real accelerator
instead (sharded-mesh tests then skip if fewer than 8 devices exist).
"""

import os
import sys

if not os.environ.get("JFS_TEST_REAL_TPU"):
    # Hard-set (not setdefault): the host may have an accelerator, but unit
    # tests must be hermetic and multi-device. Nothing has initialised a
    # JAX backend yet at conftest time, so the environment is enough.
    os.environ["JAX_PLATFORMS"] = "cpu"
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla_flags:
        os.environ["XLA_FLAGS"] = (
            xla_flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    # No persistent compile cache under test (tpu/device.py would put it
    # in <checkout>/.jax_cache): a test must not pass because an earlier
    # run left a compiled program behind. CLI children inherit this.
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Lock watchdog (ISSUE 7): instrument every juicefs lock across the whole
# suite — acquisition-order inversions and holds-while-blocking become
# test failures (the lockwatch_guard fixture below).  Installed BEFORE
# any juicefs_tpu module creates a lock; set JUICEFS_LOCK_WATCHDOG=0 to
# run uninstrumented.
os.environ.setdefault("JUICEFS_LOCK_WATCHDOG", "1")
# Txn rerun harness (ISSUE 12): every successful meta txn closure runs
# TWICE with the first run's writes discarded, asserting byte-identical
# reruns across kv and sql engines — non-idempotent closures (the
# double-apply bugs conflict retry triggers in production) become test
# failures (txnwatch_guard below).  JUICEFS_TXN_RERUN=0 to disable.
os.environ.setdefault("JUICEFS_TXN_RERUN", "1")
from juicefs_tpu.utils import lockwatch, txnwatch  # noqa: E402

lockwatch.install()
txnwatch.install()


import contextlib

import pytest


@pytest.fixture(autouse=True)
def lockwatch_guard():
    """Fail any test during which the lock watchdog recorded a new
    violation (lock-order inversion or a blocking call made while a
    watched lock is held)."""
    before = len(lockwatch.violations())
    yield
    new = lockwatch.violations()[before:]
    assert not new, "lock watchdog violations:\n" + "\n\n".join(
        f"[{v['kind']}] {v['detail']} (thread {v['thread']})\n{v['stack']}"
        for v in new
    )


@pytest.fixture(autouse=True)
def txnwatch_guard():
    """Fail any test during which the txn rerun harness caught a
    non-idempotent transaction closure (result/write-set divergence
    between the doubled runs)."""
    before = len(txnwatch.violations())
    yield
    new = txnwatch.violations()[before:]
    assert not new, "txn rerun violations:\n" + "\n\n".join(
        f"[{v['engine']}] {v['closure']}: {v['detail']} "
        f"(thread {v['thread']})"
        for v in new
    )


@pytest.fixture(autouse=True)
def thread_leak_guard(request):
    """Fail any test that leaves NEW non-daemon worker threads running
    (ISSUE 2): an unclosed executor keeps its pool threads alive into
    every later test, where they alias metrics, hold cache-dir locks,
    and mask real shutdown bugs.  Daemon helpers (prefetcher, writer
    flusher, indexer) are exempt — they die with the process by design.

    CachedStores a test forgot are closed here first (they register in
    the module's live-store weak set), so the assertion is about
    everything ELSE: VFS spools, ad-hoc executors, servers.  A short
    grace period absorbs pools that are mid-shutdown when the test body
    returns."""
    import threading
    import time

    from juicefs_tpu.chunk.cached_store import _LIVE_STORES

    before = set(threading.enumerate())
    stores_before = set(_LIVE_STORES)
    yield
    for s in list(_LIVE_STORES):
        if s not in stores_before:
            try:
                s.close()
            except Exception:
                pass

    def leaked():
        return [
            t for t in threading.enumerate()
            if t not in before and t.is_alive() and not t.daemon
        ]

    deadline = time.time() + 3.0
    left = leaked()
    while left and time.time() < deadline:
        time.sleep(0.05)
        left = leaked()
    assert not left, (
        f"test leaked non-daemon threads: {sorted(t.name for t in left)} "
        "(close the store/VFS/executor it belongs to)"
    )


@contextlib.contextmanager
def fuse_mount(tmp_path, block_size=1 << 20, cache_dirs=("memory",),
               meta_url="mem://", vfs_conf=None, **format_kw):
    """Shared FUSE loop-mount lifecycle (used by test_fuse / test_fsx /
    test_posix_oracle): build the full stack on mem:// meta + mem://
    objects, mount, wait for the kernel INIT handshake, yield the
    mountpoint, and tear down. One copy so readiness/teardown fixes land
    everywhere at once."""
    import os
    import shutil
    import time

    import pytest

    if not os.path.exists("/dev/fuse") or shutil.which("fusermount") is None:
        pytest.skip("FUSE not available")
    from juicefs_tpu.chunk import CachedStore, ChunkConfig
    from juicefs_tpu.fuse import Server
    from juicefs_tpu.meta import Format, new_client
    from juicefs_tpu.object import create_storage
    from juicefs_tpu.vfs import VFS

    format_kw.setdefault("name", "fusetest")
    format_kw.setdefault("storage", "mem")
    m = new_client(meta_url)
    m.init(Format(block_size=block_size >> 10, **format_kw), force=False)
    m.load()
    m.new_session()
    store = CachedStore(
        create_storage("mem://"),
        ChunkConfig(block_size=block_size, cache_dirs=tuple(cache_dirs)),
    )
    v = VFS(m, store, conf=vfs_conf)
    mp = tmp_path / "mnt"
    mp.mkdir(exist_ok=True)
    srv = Server(v, str(mp))
    try:
        srv.serve_background()
    except OSError as e:
        pytest.skip(f"cannot mount: {e}")
    deadline = time.time() + 5
    while time.time() < deadline:
        try:
            os.statvfs(mp)
            break
        except OSError:
            time.sleep(0.05)
    try:
        yield str(mp)
    finally:
        srv.unmount()
        time.sleep(0.1)
        v.close()
        store.close()  # stop upload/download pools + prefetch workers
