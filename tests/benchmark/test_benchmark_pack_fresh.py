"""`tpu.pack_fresh_share` and `entry.scan_faults_per_block` (ISSUE 27): how
often `hash_stream`'s kept pack buffers did not engage, and what the scans'
read+hash stage first-touched, by the block. Each resolves by its files, reads
a counter of the program's own and no patched span, and reads a number from a
traced rehearsal window on the CPU; against a program without the counter (the
parent of the PR that brought it) its reader gives nothing and does not
raise."""

import pytest

import manifest_checks as checks
from test_benchmark_program_spans import (  # noqa: F401 (fixtures)
    REPO, reader, spec_of, traced_line)
from test_benchmark_run import process_as_new  # noqa: F401 (fixture)

METRICS = {
    "tpu.pack_fresh_share": {
        "entry": {"unit": "%", "layer": "tpu: pack (tpu/jth256.py:pack_blocks)"},
        "args": {"kind": "counter_gain", "series": "juicefs_tpu_pack_fresh_bytes",
                 "per_work": "hashed_user_bytes", "scale": 100},
        "work": {"hashed_user_bytes": 3}, "gain": 6.0, "reads": 200.0},
    "entry.scan_faults_per_block": {
        "entry": {"unit": "faults/block", "layer": "entry (cmd/)"},
        "args": {"kind": "counter_gain", "series": "juicefs_scan_minor_faults",
                 "per_work": "hashed_blocks"},
        "work": {"hashed_blocks": 4}, "gain": 4100.0, "reads": 1025.0},
}


@pytest.mark.parametrize("metric", METRICS)
def test_manifest_entry_names_its_layer_and_the_accepted_cells(metric):
    """The accepted cells first and in order, in the manifest and in the
    metric's list; a later PR's cells come after them."""
    manifest = checks.manifest(REPO)
    checks.check_accepted_cells_come_first(REPO)
    entry = checks.check_accepted_metric_lists_its_cells(REPO, metric)
    assert entry == {
        "name": metric, "better": "lower", "source": "program_counter",
        "moves": "scan_gibs", "workloads": entry["workloads"],
        **METRICS[metric]["entry"]}
    # a layer the accepted benchmark already names, under that name
    assert entry["layer"] in {e["layer"] for e in manifest["per_layer"]
                              if e["name"] not in METRICS}


@pytest.mark.parametrize("metric", METRICS)
def test_it_reads_the_registry_and_no_patched_span(metric):
    spec = spec_of(metric)
    assert spec["reader"] == "registry"
    assert spec["args"] == METRICS[metric]["args"]


@pytest.mark.parametrize("metric", METRICS)
def test_its_reader_gives_nothing_where_the_program_lacks_the_counter(metric):
    case = METRICS[metric]
    ctx = {"registry_before": {}, "work": case["work"],
           "registry_after": {"juicefs_tpu_h2d_bytes": 9.0}}
    assert reader("registry").read(ctx, **case["args"]) is None
    ctx["registry_after"][case["args"]["series"]] = case["gain"]
    assert reader("registry").read(ctx, **case["args"]) == case["reads"]
    ctx["work"] = {}  # a window that hashed nothing: no share of nothing
    assert reader("registry").read(ctx, **case["args"]) is None


def test_pack_fresh_share_reads_from_a_rehearsal_window(traced_line):
    """What the metric means, however the program batches a rehearsal op:
    a stream's first batch is always packed into a buffer on its first use,
    so the share is above 0; and bytes packed fresh cannot exceed bytes
    shipped, so it is at most 100 x `tpu.h2d_bytes_per_user_byte` (which the
    padding of ragged blocks lifts above 1)."""
    got = traced_line["metrics"]["tpu.pack_fresh_share"]
    assert got["unit"] == "%"
    h2d = traced_line["metrics"]["tpu.h2d_bytes_per_user_byte"]["value"]
    assert 0 < got["value"] <= 100 * h2d * (1 + 1e-9)
    assert traced_line["correct"] is True


def test_scan_faults_read_from_a_rehearsal_window(traced_line):
    got = traced_line["metrics"]["entry.scan_faults_per_block"]
    assert got["unit"] == "faults/block" and got["value"] > 0
