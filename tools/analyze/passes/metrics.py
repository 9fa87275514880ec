"""Metric-registry lint as a framework pass (rule ``metric-registry``).

The one RUNTIME pass: it imports the metric-registering modules and
walks the live registry (naming/help/conflict hygiene plus the pinned
per-subsystem series sets from PRs 4/5/6).  It takes no source files and
emits registry-level findings (no file:line — these are fixed, never
suppressed).  Skipped when the runner is asked for AST-only analysis
(fixture trees, unit tests).
"""

from __future__ import annotations

from ..core import Finding, Pass, SourceFile

# pinned per-subsystem series (ISSUE 4/5/6 contracts): tests
# counter-assert these — a rename must fail CI, not silently zero a
# dashboard
CACHE_GROUP_PREFIX = "juicefs_cache_group_"
CACHE_GROUP_EXPECTED = {
    "juicefs_cache_group_peer_hits",
    "juicefs_cache_group_peer_misses",
    "juicefs_cache_group_peer_errors",
    "juicefs_cache_group_ring_size",
    "juicefs_cache_group_peer_get_seconds",
    "juicefs_cache_group_served",
    "juicefs_cache_group_served_bytes",
    "juicefs_cache_group_serve_misses",
    # ring-aware warm placement (ISSUE 11): hints sent / accepted
    "juicefs_cache_group_warm_hints",
    "juicefs_cache_group_warm_requests",
}
PREFETCH_PREFIX = "juicefs_prefetch_"
PREFETCH_EXPECTED = {
    # speculative-warming effectiveness (chunk/prefetch.py); used/issued
    # is the readahead window feedback signal (ISSUE 11)
    "juicefs_prefetch_issued",
    "juicefs_prefetch_duplicates",
    "juicefs_prefetch_dropped",
    "juicefs_prefetch_used",
    "juicefs_prefetch_warmed",
}
READAHEAD_PREFIX = "juicefs_readahead_"
READAHEAD_EXPECTED = {
    # epoch-streaming read path (ISSUE 11, vfs/reader.py)
    "juicefs_readahead_plans",
    "juicefs_readahead_plan_shed",
    "juicefs_readahead_streaming",
    "juicefs_readahead_epoch_warms",
    "juicefs_readahead_window_bytes",
    "juicefs_readahead_streaming_handles",
}
INGEST_PREFIX = "juicefs_ingest_"
INGEST_EXPECTED = {
    "juicefs_ingest_blocks",
    "juicefs_ingest_bytes",
    "juicefs_ingest_put_elided",
    "juicefs_ingest_put_elided_bytes",
    "juicefs_ingest_uploaded",
    "juicefs_ingest_passthrough",
    "juicefs_ingest_race_collapsed",
    "juicefs_ingest_errors",
    "juicefs_ingest_queue_blocks",
    # adaptive elision bypass (ISSUE 8, chunk/bypass.py)
    "juicefs_ingest_bypass",
    "juicefs_ingest_bypass_probes",
}
COMPRESS_PREFIX = "juicefs_compress_"
COMPRESS_EXPECTED = {
    # batched compression plane (ISSUE 8, tpu/compress_batch.py)
    "juicefs_compress_batch_blocks",
    "juicefs_compress_bytes_in",
    "juicefs_compress_bytes_out",
    "juicefs_compress_ratio",
    "juicefs_compress_degraded",
}
QOS_PREFIX = "juicefs_qos_"
QOS_EXPECTED = {
    "juicefs_qos_submitted",
    "juicefs_qos_completed",
    "juicefs_qos_shed",
    "juicefs_qos_wait_seconds",
    "juicefs_qos_queue_depth",
    "juicefs_qos_throttle_wait_seconds",
    "juicefs_qos_throttled_bytes",
}
META_CACHE_PREFIX = "juicefs_meta_cache_"
META_CACHE_EXPECTED = {
    # lease cache + replica routing (ISSUE 9, meta/cache.py + redis_kv.py)
    "juicefs_meta_cache_hits",
    "juicefs_meta_cache_misses",
    "juicefs_meta_cache_invalidates",
    "juicefs_meta_cache_lease_expired",
    "juicefs_meta_cache_replica_reads",
    "juicefs_meta_cache_replica_stale",
}
META_THROTTLE_PREFIX = "juicefs_meta_throttle_"
META_THROTTLE_EXPECTED = {
    # per-tenant meta-op token buckets (ISSUE 9, --meta-op-limit)
    "juicefs_meta_throttle_waits",
    "juicefs_meta_throttle_wait_seconds",
}
META_FAULT_PREFIX = "juicefs_meta_fault_"
META_FAULT_EXPECTED = {
    # meta-plane fault contract (ISSUE 14, meta/resilient.py): retry/
    # failure accounting per error class + hung-read abandonment
    "juicefs_meta_fault_retries",
    "juicefs_meta_fault_failures",
    "juicefs_meta_fault_abandoned",
}
META_BREAKER_PREFIX = "juicefs_meta_breaker_"
META_BREAKER_EXPECTED = {
    # per-engine-connection circuit breaker (ISSUE 14)
    "juicefs_meta_breaker_state",
    "juicefs_meta_breaker_trips",
    "juicefs_meta_breaker_resets",
}
META_STALE_PREFIX = "juicefs_meta_stale_"
META_STALE_EXPECTED = {
    # degraded-mode stale-lease serves, bounded by
    # --meta-degraded-max-stale (ISSUE 14, meta/cache.py)
    "juicefs_meta_stale_served",
}
GATEWAY_PREFIX = "juicefs_gateway_"
GATEWAY_EXPECTED = {
    # gateway serving plane (ISSUE 15, gateway/serve.py): admission,
    # tenancy and streaming-buffer accounting — the shed counter and the
    # stream-buffer gauge are acceptance counters (503-not-500 overload,
    # bounded per-request buffering)
    "juicefs_gateway_requests",
    "juicefs_gateway_shed",
    "juicefs_gateway_errors",
    "juicefs_gateway_auth_failures",
    "juicefs_gateway_bytes_in",
    "juicefs_gateway_bytes_out",
    "juicefs_gateway_request_seconds",
    "juicefs_gateway_inflight",
    "juicefs_gateway_stream_buffer_bytes",
}
TPU_SHARD_PREFIX = "juicefs_tpu_shard_"
TPU_SHARD_EXPECTED = {
    # multichip sharding plane (ISSUE 20, tpu/sharding.py): device/mesh
    # geometry, the ONE-sharded-transfer-per-batch counter the shared-pack
    # contract asserts, and the single-device-jit degrade counter
    "juicefs_tpu_shard_devices",
    "juicefs_tpu_shard_h2d_batches",
    "juicefs_tpu_shard_degraded",
}
INDEX_PREFIX = "juicefs_index_"
INDEX_EXPECTED = {
    # write-path content indexer (chunk/indexer.py): backlog, and the
    # three series that account for every submitted block — persisted,
    # dropped under overload, failed. chip_smoke.py (ISSUE 21) asserts
    # errors == 0 and persisted + dropped == blocks written.
    "juicefs_index_queue_blocks",
    "juicefs_index_blocks",
    "juicefs_index_dropped_blocks",
    "juicefs_index_errors",
}
OBJECT_PREFIX = "juicefs_object_"
OBJECT_EXPECTED = {
    # the object plane: per-backend meters (object/metered.py), shard
    # routing (object/sharding.py), the resilience family
    # (object/resilient.py) and the envelope's (object/encrypt.py) — the
    # benchmark's road metrics read these
    "juicefs_object_request_durations_histogram_seconds",
    "juicefs_object_request_cpu_seconds",
    "juicefs_object_request_errors",
    "juicefs_object_request_data_bytes",
    "juicefs_object_shard_ops",
    "juicefs_object_request_retries",
    "juicefs_object_retries_by_class",
    "juicefs_object_deadline_abandoned",
    "juicefs_object_hedged_requests",
    "juicefs_object_hedge_wins",
    "juicefs_object_inline_requests",
    "juicefs_object_breaker_state",
    "juicefs_object_breaker_trips",
    "juicefs_object_breaker_resets",
    "juicefs_object_shed_limit",
    "juicefs_object_decrypt_cpu_seconds",
    "juicefs_object_decrypt_opens",
    "juicefs_object_corrupt",
}
META_WBATCH_PREFIX = "juicefs_meta_wbatch_"
META_WBATCH_EXPECTED = {
    # checkpoint write plane (ISSUE 13, meta/wbatch.py): the
    # batched/drained ratio is the group-commit amortization (the
    # same two counts tests/test_wbatch.py holds through stats())
    "juicefs_meta_wbatch_batched",
    "juicefs_meta_wbatch_drained",
    "juicefs_meta_wbatch_barrier_flushes",
    "juicefs_meta_wbatch_overlay_hits",
    "juicefs_meta_wbatch_passthrough",
}


def populate_registry() -> None:
    """Import the modules whose metrics register at import time, and the
    runtime registrations that are cheap to trigger."""
    import juicefs_tpu.cache.group          # noqa: F401  peer hit/miss/ring
    import juicefs_tpu.cache.server         # noqa: F401  peer served counters
    import juicefs_tpu.chunk.bypass         # noqa: F401  elision-bypass counters
    import juicefs_tpu.chunk.cached_store   # noqa: F401  staging gauges
    import juicefs_tpu.chunk.disk_cache     # noqa: F401  disk tier counters
    import juicefs_tpu.chunk.indexer        # noqa: F401  content-index gauges
    import juicefs_tpu.chunk.ingest         # noqa: F401  inline-dedup counters
    import juicefs_tpu.chunk.mem_cache      # noqa: F401  cache hit/miss/evict
    import juicefs_tpu.chunk.parallel       # noqa: F401  fetch_inflight gauge
    import juicefs_tpu.chunk.prefetch       # noqa: F401  prefetch effectiveness
    import juicefs_tpu.chunk.singleflight   # noqa: F401  dedup counters
    import juicefs_tpu.gateway.serve        # noqa: F401  serving-plane counters
    import juicefs_tpu.meta.cache           # noqa: F401  lease cache + throttle
    import juicefs_tpu.meta.resilient       # noqa: F401  meta fault contract
    import juicefs_tpu.meta.wbatch          # noqa: F401  write-batch plane
    import juicefs_tpu.metric.trace         # noqa: F401  stage rollup histogram
    import juicefs_tpu.object.metered       # noqa: F401  per-backend op meters
    import juicefs_tpu.object.resilient     # noqa: F401  retry/hedge/breaker
    import juicefs_tpu.object.sharding      # noqa: F401  shard routing counter
    import juicefs_tpu.qos.limiter          # noqa: F401  bandwidth throttling
    import juicefs_tpu.qos.scheduler        # noqa: F401  scheduler classes
    import juicefs_tpu.tpu.compress_batch   # noqa: F401  compression plane
    import juicefs_tpu.tpu.pipeline         # noqa: F401  batch metrics
    import juicefs_tpu.tpu.sharding         # noqa: F401  multichip plane
    import juicefs_tpu.vfs.reader           # noqa: F401  readahead/streaming
    from juicefs_tpu.metric import register_process_metrics

    register_process_metrics()


def _registry(registry=None):
    from juicefs_tpu.metric import global_registry

    if registry is None:
        populate_registry()
    return registry or global_registry()


def lint_registry(registry=None) -> list[str]:
    """Naming/help/conflict hygiene over the registry (legacy `lint()`
    contract: returns problem strings, empty = clean)."""
    reg = _registry(registry)
    problems: list[str] = []
    for m in reg.walk():
        if not m.name.startswith("juicefs_"):
            problems.append(f"{m.name}: metric name lacks the juicefs_ prefix")
        if not m.help.strip():
            problems.append(f"{m.name}: missing help string")
        if m.kind not in ("counter", "gauge", "histogram"):
            problems.append(f"{m.name}: unknown metric kind {m.kind!r}")
    problems.extend(reg.conflicts)
    return problems


def lint_pinned(prefix: str, expected: set[str], what: str,
                registry=None) -> list[str]:
    """Pin a subsystem's registry: every expected series exists, and no
    stray metric squats under the prefix unreviewed."""
    reg = _registry(registry)
    names = {m.name for m in reg.walk()}
    problems = [
        f"{name}: {what} metric missing from the registry"
        for name in sorted(expected - names)
    ]
    problems += [
        f"{name}: unreviewed metric under {prefix} (add it to "
        "the pinned set in tools/analyze/passes/metrics.py)"
        for name in sorted(n for n in names
                           if n.startswith(prefix) and n not in expected)
    ]
    return problems


def run(files: list[SourceFile]) -> list[Finding]:
    problems = (
        lint_registry()
        + lint_pinned(CACHE_GROUP_PREFIX, CACHE_GROUP_EXPECTED, "cache-group")
        + lint_pinned(INGEST_PREFIX, INGEST_EXPECTED, "ingest")
        + lint_pinned(QOS_PREFIX, QOS_EXPECTED, "qos")
        + lint_pinned(COMPRESS_PREFIX, COMPRESS_EXPECTED, "compress")
        + lint_pinned(META_CACHE_PREFIX, META_CACHE_EXPECTED, "meta-cache")
        + lint_pinned(META_THROTTLE_PREFIX, META_THROTTLE_EXPECTED,
                      "meta-throttle")
        + lint_pinned(META_FAULT_PREFIX, META_FAULT_EXPECTED, "meta-fault")
        + lint_pinned(META_BREAKER_PREFIX, META_BREAKER_EXPECTED,
                      "meta-breaker")
        + lint_pinned(META_STALE_PREFIX, META_STALE_EXPECTED, "meta-stale")
        + lint_pinned(META_WBATCH_PREFIX, META_WBATCH_EXPECTED,
                      "meta-wbatch")
        + lint_pinned(TPU_SHARD_PREFIX, TPU_SHARD_EXPECTED, "tpu-shard")
        + lint_pinned(INDEX_PREFIX, INDEX_EXPECTED, "index")
        + lint_pinned(PREFETCH_PREFIX, PREFETCH_EXPECTED, "prefetch")
        + lint_pinned(READAHEAD_PREFIX, READAHEAD_EXPECTED, "readahead")
        + lint_pinned(GATEWAY_PREFIX, GATEWAY_EXPECTED, "gateway")
        + lint_pinned(OBJECT_PREFIX, OBJECT_EXPECTED, "object-plane")
    )
    return [Finding("", 0, "metric-registry", p) for p in problems]


PASS = Pass(
    name="metric-registry",
    rules=("metric-registry",),
    run=run,
    doc="metric naming/help/conflict hygiene + pinned per-subsystem series",
)
