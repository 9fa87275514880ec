"""The per-layer metrics that read the program's own spans and counters
(ISSUE 25): each resolves by its files and reads a number from a traced
rehearsal window on the CPU; none of them depends on a function the
benchmark patches by name; `registry_before` gives nothing for a series
the program does not register."""

import json
import os

import pytest

from benchmark import run
from benchmark.lib.spans import Spans

import manifest_checks as checks
from test_benchmark_run import (  # noqa: F401 (fixtures)
    any_device, argv, make_root, process_as_new)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = ["tpu.hash_pack_ms_per_batch", "tpu.h2d_ms_per_batch",
       "tpu.enqueue_ms_per_batch", "chunk.fetch_wait_ms",
       "chunk.fetch_ready_share", "entry.open_ms_per_op",
       "entry.list_ms_per_op", "entry.reconcile_ms_per_op",
       "entry.compile_s", "entry.programs_built"]


def reader(name):
    return run.load_by_path(os.path.join(REPO, "benchmark", "readers", name + ".py"))


def spec_of(metric):
    with open(os.path.join(REPO, "benchmark", "layer_metrics", metric + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traced_line(tmp_path_factory):
    """One traced `scan-cold` rehearsal for every test of this module."""
    import contextlib
    import io

    import jax

    from juicefs_tpu.tpu import sharding

    root = make_root(str(tmp_path_factory.mktemp("spans") / "root"))
    sharding._reset_plane_for_tests()
    degraded, sharding._DEGRADED.value = sharding._DEGRADED.value, 0.0
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = run.main(argv("scan-cold", trace=1), root=root,
                          device_check=lambda chips: run.device_info(jax.devices()))
    finally:
        sharding._DEGRADED.value = degraded
        sharding._reset_plane_for_tests()
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("metric", NEW)
def test_new_metric_reads_a_number_from_a_rehearsal_window(traced_line, metric):
    entry = checks.check_accepted_metric_lists_its_cells(REPO, metric)
    assert entry["source"] == ("program_span" if spec_of(metric)["args"].get(
        "kind") == "histogram_mean" else "program_counter")
    got = traced_line["metrics"][metric]
    assert got["unit"] == entry["unit"] and got["value"] >= 0
    assert traced_line["correct"] is True


def test_the_new_span_metrics_add_up_inside_the_old_ones(traced_line):
    m = {k: v["value"] for k, v in traced_line["metrics"].items()}
    parts = (m["tpu.hash_pack_ms_per_batch"] + m["tpu.h2d_ms_per_batch"]
             + m["tpu.enqueue_ms_per_batch"])
    assert 0 < parts <= m["tpu.dispatch_ms_per_batch"]
    assert 0 <= m["chunk.fetch_ready_share"] <= 100
    # what the main thread does outside `dedup_scan`; the listing itself
    # runs on a thread of its own, beside the scan, and is no part of it
    assert (m["entry.open_ms_per_op"] + m["entry.live_ms_per_op"]
            + m["entry.list_wait_ms_per_op"] + m["entry.reconcile_ms_per_op"]
            <= m["entry.listing_ms_per_op"])
    # the test run keeps the persistent cache off: whatever set-up
    # compiled, the compiler built
    assert m["entry.programs_built"] >= 1 and m["entry.compile_s"] > 0
    # the program's stages now reach the breakdown's span names by themselves
    assert 0 < m["meta.index_load_ms_per_op"] < 1000


@pytest.mark.parametrize("metric", NEW)
def test_new_metric_reads_the_registry_and_no_patched_span(metric):
    """Not the `span` reader (the benchmark's own spans, put on functions
    by name): a series the program's own site feeds."""
    spec = spec_of(metric)
    assert spec["reader"] in ("registry", "registry_before")
    assert spec["args"]["series"].startswith(("juicefs_tpu_", "juicefs_fetch_"))


def test_pack_metric_outlives_the_function_the_benchmark_patches(monkeypatch):
    """What a feed PR may do: pack through another function. The
    benchmark's span, put on `pipeline.pack_blocks` by name, then times a
    function nobody calls and its metric reads nothing; the program's
    `tpu.hash.pack` site is still passed and its metric reads."""
    from juicefs_tpu.tpu import pipeline

    pack = pipeline.pack_blocks  # the program's own, before any patch

    spans = Spans(enabled=True)
    spans.wrap_call("juicefs_tpu.tpu.pipeline:pack_blocks", "jfs.tpu.pack_blocks")
    try:
        def pack_into_reused_buffer(blocks, pad_lanes=None):
            return pack(blocks, pad_lanes=pad_lanes)

        monkeypatch.setattr(pipeline, "pack_blocks", pack_into_reused_buffer)
        before = run.registry_snapshot()
        pipe = pipeline.HashPipeline(pipeline.PipelineConfig(
            backend="xla", batch_blocks=4, pad_lanes=1))
        assert len(pipe.hash_blocks([os.urandom(999) for _ in range(9)])) == 9
        ctx = {"registry_before": before, "spans": spans.durations,
               "registry_after": run.registry_snapshot(), "work": {}}
    finally:
        monkeypatch.undo()
        spans.restore()
    old, new = spec_of("tpu.pack_ms_per_batch"), spec_of("tpu.hash_pack_ms_per_batch")
    assert reader(old["reader"]).read(ctx, **old["args"]) is None
    assert reader(new["reader"]).read(ctx, **new["args"]) > 0
    count = 'juicefs_tpu_stage_seconds_count{layer="tpu",op="hash",stage="pack"}'
    # one observation for each batch packed, however many the program made
    # of nine blocks (its batching is tests/test_pack_buffers.py's to hold)
    assert ctx["registry_after"][count] - before.get(count, 0.0) >= 1


S = "juicefs_tpu_compile_seconds_sum"
BEFORE = {"registry_before": {
    S + '{source="built"}': 2.5, S + '{source="cache"}': 0.75,
    'juicefs_tpu_compiles{source="built"}': 3.0,
    'juicefs_tpu_compiles{source="cache"}': 5.0,
    "juicefs_tpu_h2d_bytes": 640.0,
    "juicefs_tpu_h2d_bytes_more": 1.0}}


@pytest.mark.parametrize("args,want", [
    ({"series": S}, 3.25),
    ({"series": S, "labels": {"source": "cache"}}, 0.75),
    ({"series": "juicefs_tpu_compiles", "labels": {"source": "built"}}, 3.0),
    ({"series": "juicefs_tpu_compiles"}, 8.0),
    ({"series": "juicefs_tpu_h2d_bytes", "scale": 0.5}, 320.0),
    ({"series": "juicefs_tpu_compiles", "labels": {"source": "absent"}}, None),
    ({"series": "juicefs_absent_series"}, None),
    ({"series": "juicefs_tpu_compile_seconds"}, None),
])
def test_registry_before(args, want):
    got = reader("registry_before").read(BEFORE, **args)
    assert got is None if want is None else got == pytest.approx(want)
