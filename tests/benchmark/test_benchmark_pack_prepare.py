"""`tpu.pack_unready_share` and `tpu.pack_prepare_ms_per_buffer` (ISSUE 34):
how much of what a stream packed went into a buffer that was not resident
when the pack came, and what making one pack buffer resident off the feeding
thread takes. Each resolves by its files, reads a series of the program's own
registry and no patched span, and reads a number from a traced rehearsal
window on the CPU; against a program without the series (the parent of the
PR that brought them) its reader gives nothing and does not raise."""

import pytest

import manifest_checks as checks
from test_benchmark_grows import manifest_root  # noqa: F401 (fixture)
from test_benchmark_program_spans import (  # noqa: F401 (fixtures)
    reader, spec_of, traced_line)
from test_benchmark_run import process_as_new  # noqa: F401 (fixture)

LAYER = "tpu: pack (tpu/jth256.py:pack_blocks)"
PREPARE = 'juicefs_tpu_stage_seconds%s{layer="tpu",op="pack",stage="prepare"}'
METRICS = {
    "tpu.pack_unready_share": {
        "entry": {"unit": "%", "source": "program_counter"},
        "args": {"kind": "counter_gain",
                 "series": "juicefs_tpu_pack_unready_bytes",
                 "per_work": "hashed_user_bytes", "scale": 100},
        "work": {"hashed_user_bytes": 4},
        "after": {"juicefs_tpu_pack_unready_bytes": 1.0}, "reads": 25.0},
    "tpu.pack_prepare_ms_per_buffer": {
        "entry": {"unit": "ms", "source": "program_span"},
        "args": {"kind": "histogram_mean",
                 "series": "juicefs_tpu_stage_seconds",
                 "labels": {"layer": "tpu", "op": "pack", "stage": "prepare"},
                 "scale": 1000},
        "work": {},
        "after": {PREPARE % "_sum": 0.25, PREPARE % "_count": 2.0},
        "reads": 125.0},
}


@pytest.mark.parametrize("metric", METRICS)
def test_manifest_entry_names_its_layer_and_the_accepted_cells(
        manifest_root, metric):
    manifest = checks.manifest(manifest_root)
    entry = checks.check_accepted_metric_lists_its_cells(manifest_root, metric)
    assert entry == {
        "name": metric, "better": "lower", "layer": LAYER,
        "moves": "scan_gibs", "workloads": entry["workloads"],
        **METRICS[metric]["entry"]}
    # the layer's name as the accepted benchmark already has it
    assert LAYER in {e["layer"] for e in manifest["per_layer"]
                     if e["name"] not in METRICS}
    # appended after everything that was there, the two in this order;
    # what a later PR appends comes after them
    names = [e["name"] for e in manifest["per_layer"]]
    at = names.index("tpu.blocks_per_batch") + 1
    assert names[at:at + len(METRICS)] == list(METRICS)


@pytest.mark.parametrize("metric", METRICS)
def test_it_reads_the_registry_and_no_patched_span(metric):
    spec = spec_of(metric)
    assert spec["reader"] == "registry"
    assert spec["args"] == METRICS[metric]["args"]


@pytest.mark.parametrize("metric", METRICS)
def test_its_reader_gives_nothing_where_the_program_lacks_the_series(metric):
    case = METRICS[metric]
    # the parent's registry: the pack's own histogram and counters, not ours
    parent = {"juicefs_tpu_pack_fresh_bytes": 9.0,
              (PREPARE % "_sum").replace("prepare", "dispatch"): 1.0,
              (PREPARE % "_count").replace("prepare", "dispatch"): 1.0}
    ctx = {"registry_before": {}, "work": case["work"],
           "registry_after": dict(parent)}
    assert reader("registry").read(ctx, **case["args"]) is None
    ctx["registry_after"].update(case["after"])
    assert reader("registry").read(ctx, **case["args"]) == case["reads"]
    ctx["registry_before"] = dict(ctx["registry_after"])  # nothing gained
    assert not reader("registry").read(ctx, **case["args"])


def test_both_read_from_a_rehearsal_window(traced_line):
    """Every op of the rehearsal announces its stream, so a buffer was
    prepared and the span has observations; whatever of the packed bytes
    was unready is part of what was packed on a buffer's first use."""
    m = traced_line["metrics"]
    unready, fresh = m["tpu.pack_unready_share"], m["tpu.pack_fresh_share"]
    assert unready["unit"] == "%"
    assert 0 <= unready["value"] <= fresh["value"] * (1 + 1e-9)
    prepare = m["tpu.pack_prepare_ms_per_buffer"]
    assert prepare["unit"] == "ms" and prepare["value"] > 0
    assert traced_line["correct"] is True
