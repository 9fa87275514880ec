"""`tpu.pack_fresh_share` and `entry.scan_faults_per_block` (ISSUE 27): how
often `hash_stream`'s kept pack buffers did not engage, and what the scans'
read+hash stage first-touched, by the block. Each resolves by its files, reads
a counter of the program's own and no patched span, and reads a number from a
traced rehearsal window on the CPU; against a program without the counter (the
parent of the PR that brought it) its reader gives nothing and does not
raise."""

import json
import os

import pytest

from test_benchmark_program_spans import (  # noqa: F401 (fixtures)
    REPO, reader, spec_of, traced_line)
from test_benchmark_run import process_as_new  # noqa: F401 (fixture)

CELLS = ["scan-cold", "scan-incr", "scan-cold-x4"]
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
def test_manifest_entry_names_its_layer_and_the_three_cells(metric):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [e for e in manifest["per_layer"] if e["name"] == metric]
    assert entry == {
        "name": metric, "better": "lower", "source": "program_counter",
        "moves": "scan_gibs", "workloads": CELLS, **METRICS[metric]["entry"]}
    assert CELLS == [w["name"] for w in manifest["workloads"]]
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
    """A rehearsal op is 37 blocks: one full batch packed fresh, and a 5-block
    tail that comes while the first is still pending, so packed fresh too:
    100%, and the padding of the ragged blocks."""
    got = traced_line["metrics"]["tpu.pack_fresh_share"]
    assert got["unit"] == "%"
    h2d = traced_line["metrics"]["tpu.h2d_bytes_per_user_byte"]["value"]
    assert got["value"] == pytest.approx(100 * h2d) and got["value"] >= 100
    assert traced_line["correct"] is True


def test_scan_faults_read_from_a_rehearsal_window(traced_line):
    got = traced_line["metrics"]["entry.scan_faults_per_block"]
    assert got["unit"] == "faults/block" and got["value"] > 0
