"""Compatibility shim over the unified analysis framework (ISSUE 7).

The registry lint and the three seam checks that accreted here across
PRs 1-6 now live in ``tools/analyze/`` (one shared AST walk, one
findings model, one CLI).  This module keeps the historical ``lint*()``
/ CLI contract so existing tests and CI invocations don't break; the
duplicated AST-walking helpers are gone.

Run ``python -m tools.analyze`` for the full analysis (lock-order,
blocking-under-lock, lane-graph, thread lints, seams, registry).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.analyze.core import SourceFile, load_files  # noqa: E402
from tools.analyze.passes import metrics as _metrics  # noqa: E402
from tools.analyze.passes import seams as _seams  # noqa: E402

# re-exported pinned sets (legacy import surface)
CACHE_GROUP_PREFIX = _metrics.CACHE_GROUP_PREFIX
CACHE_GROUP_EXPECTED = _metrics.CACHE_GROUP_EXPECTED
INGEST_PREFIX = _metrics.INGEST_PREFIX
INGEST_EXPECTED = _metrics.INGEST_EXPECTED
QOS_PREFIX = _metrics.QOS_PREFIX
QOS_EXPECTED = _metrics.QOS_EXPECTED
META_WBATCH_PREFIX = _metrics.META_WBATCH_PREFIX
META_WBATCH_EXPECTED = _metrics.META_WBATCH_EXPECTED
COMPRESS_PREFIX = _metrics.COMPRESS_PREFIX
COMPRESS_EXPECTED = _metrics.COMPRESS_EXPECTED
GATEWAY_PREFIX = _metrics.GATEWAY_PREFIX
GATEWAY_EXPECTED = _metrics.GATEWAY_EXPECTED
INDEX_PREFIX = _metrics.INDEX_PREFIX
INDEX_EXPECTED = _metrics.INDEX_EXPECTED
OBJECT_PREFIX = _metrics.OBJECT_PREFIX
OBJECT_EXPECTED = _metrics.OBJECT_EXPECTED

_PKG_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "juicefs_tpu"
)


def lint(registry=None) -> list[str]:
    """Registry hygiene problems (empty = clean).  With an explicit
    registry, lint it as-is; only the global registry needs the
    metric-registering modules imported first."""
    return _metrics.lint_registry(registry)


def lint_cache_group(registry=None) -> list[str]:
    return _metrics.lint_pinned(CACHE_GROUP_PREFIX, CACHE_GROUP_EXPECTED,
                                "cache-group", registry)


def lint_ingest(registry=None) -> list[str]:
    return _metrics.lint_pinned(INGEST_PREFIX, INGEST_EXPECTED,
                                "ingest", registry)


def lint_qos(registry=None) -> list[str]:
    return _metrics.lint_pinned(QOS_PREFIX, QOS_EXPECTED, "qos", registry)


def lint_wbatch(registry=None) -> list[str]:
    return _metrics.lint_pinned(META_WBATCH_PREFIX, META_WBATCH_EXPECTED,
                                "meta-wbatch", registry)


def lint_compress(registry=None) -> list[str]:
    return _metrics.lint_pinned(COMPRESS_PREFIX, COMPRESS_EXPECTED,
                                "compress", registry)


def lint_gateway(registry=None) -> list[str]:
    return _metrics.lint_pinned(GATEWAY_PREFIX, GATEWAY_EXPECTED,
                                "gateway", registry)


def lint_index(registry=None) -> list[str]:
    return _metrics.lint_pinned(INDEX_PREFIX, INDEX_EXPECTED,
                                "index", registry)


def lint_object(registry=None) -> list[str]:
    return _metrics.lint_pinned(OBJECT_PREFIX, OBJECT_EXPECTED,
                                "object-plane", registry)


def lint_compress_seam(root: str | None = None) -> list[str]:
    """No-bare-compress check (ISSUE 8), framework-backed."""
    files = load_files(root or _PKG_ROOT)
    return [f.render() for f in _seams.run_compress_seam(files)]


def lint_ingest_seam(path: str | None = None) -> list[str]:
    """No-bare-upload check (ISSUE 5), framework-backed."""
    path = path or os.path.join(_PKG_ROOT, "chunk", "cached_store.py")
    with open(path) as f:
        sf = SourceFile(path, path, f.read())
    return [f.render() for f in _seams.check_ingest_seam(sf)]


def lint_qos_seam(root: str | None = None) -> list[str]:
    """No-bare-pool check (ISSUE 6), framework-backed."""
    files = load_files(root or _PKG_ROOT)
    return [f.render() for f in _seams.run_qos_seam(files)]


def lint_resilience(root: str | None = None) -> list[str]:
    """No-bare-store check (ISSUE 3), framework-backed."""
    files = load_files(root or _PKG_ROOT)
    return [f.render() for f in _seams.run_resilience_seam(files)]


def main() -> int:
    problems = (lint() + lint_cache_group() + lint_ingest()
                + lint_ingest_seam() + lint_resilience()
                + lint_qos() + lint_qos_seam()
                + lint_compress() + lint_compress_seam()
                + lint_wbatch() + lint_gateway() + lint_index()
                + lint_object())
    if problems:
        for p in problems:
            print(f"lint_metrics: {p}", file=sys.stderr)
        return 1
    from juicefs_tpu.metric import global_registry

    print(f"lint_metrics: {len(global_registry().walk())} metrics OK "
          "(+ resilience wiring clean)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
