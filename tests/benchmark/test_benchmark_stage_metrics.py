"""The per-layer metrics that read the stages the program already times: `gc`'s
wait for its lister and its slice walk, the scrub's listing, index load and
round trips to the meta server, the scrub's Pallas program, the mirror check's
four stages, and the objects a `file://` listing sized. Each is a data file on
a reader that is there, appended after what was accepted and listing only the
cells in which its code finds something to read.

Here, over one table: each entry and its place in the manifest, each file's
reader and arguments, the number each reads from a hand-made window, nothing
where the program has no such series (a program without the span or counter)
or there is no device trace (off the chip), and every registry series read is
one today's program registers. The traced rehearsals of `scan-incr`,
`fsck-verify-redis` and `sync-check-all` read them from a window (their own
modules)."""

import pytest

import manifest_checks as checks
from benchmark import run
from test_benchmark_grows import manifest_root  # noqa: F401 (fixture)
from test_benchmark_program_spans import reader, spec_of

GC = checks.ACCEPTED_CELLS
SCRUB, PASS = ["fsck-verify-redis"], ["sync-check-all"]
ENTRY = "entry (cmd/)"
STAGE = 'juicefs_tpu_stage_seconds_%s{layer="%s",op="%s",stage="%s"}'
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def stage_mean(layer, op, stage, cells, entry_layer=ENTRY):
    """The mean of one stage of the program's stage histogram, in ms."""
    return {
        "entry": {"unit": "ms", "better": "lower", "source": "program_span",
                  "layer": entry_layer, "moves": "op_p50_ms", "workloads": cells},
        "reader": "registry",
        "args": {"kind": "histogram_mean", "series": "juicefs_tpu_stage_seconds",
                 "labels": {"layer": layer, "op": op, "stage": stage},
                 "scale": 1000},
        "gained": {STAGE % ("sum", layer, op, stage): 0.25,
                   STAGE % ("count", layer, op, stage): 2.0},
        "work": {}, "reads": 125.0}


def per_op(series, key, layer, cells, gained):
    """A counter's gain over the window (`key`, as the exposition text has
    it) per op that answered (two here)."""
    return {
        "entry": {"unit": "count", "better": "lower", "source": "program_counter",
                  "layer": layer, "moves": "op_p50_ms", "workloads": cells},
        "reader": "registry",
        "args": {"kind": "counter_gain", "per_work": "ops", **series},
        "gained": {key: gained}, "work": {"ops": 2}, "reads": gained / 2}


# the scrub's program: 10 runs in 2.9 ms of device time; 4.095 GB hashed in
# 0.01 s, at 819 GB/s 0.005 s: 50% of the roofline
TRACE = {"busy_by_device": {"/device:TPU:0": 0.012}, "window_s": 30.0,
         "busiest_busy_s": 0.012, "program_s": 0.0029, "programs": 10}
ROOFLINE_TRACE = dict(TRACE, program_s=0.01)

THIRTEEN = {
    "entry.list_wait_ms_per_op": stage_mean("cmd", "gc", "list_wait", GC),
    "entry.live_ms_per_op": stage_mean("cmd", "gc", "live", GC),
    "entry.fsck_list_ms_per_op": stage_mean("cmd", "fsck", "list", SCRUB),
    "entry.fsck_index_load_ms_per_op": stage_mean("cmd", "fsck", "index_load", SCRUB),
    "meta.kv_roundtrip_ms": stage_mean("meta", "kv", "roundtrip", SCRUB, "meta"),
    "meta.kv_roundtrips_per_op": per_op(
        {"series": "juicefs_tpu_stage_seconds_count",
         "labels": {"layer": "meta", "op": "kv", "stage": "roundtrip"}},
        STAGE % ("count", "meta", "kv", "roundtrip"), "meta", SCRUB, 24.0),
    "kernel.pallas_ms_per_batch": {
        "entry": {"unit": "ms", "better": "lower", "source": "device_trace",
                  "layer": "kernel", "moves": "scan_gibs", "workloads": SCRUB},
        "reader": "trace",
        "args": {"field": "program_s", "per": "programs", "scale": 1000},
        "trace": TRACE, "work": {}, "reads": 0.29},
    "jth256_pallas_roofline": {
        "entry": {"unit": "%", "better": "higher", "source": "device_trace",
                  "layer": "kernel", "moves": "scan_gibs", "workloads": SCRUB},
        "reader": "roofline", "args": {"work": "hashed_lane_bytes"},
        "trace": ROOFLINE_TRACE, "work": {"hashed_lane_bytes": 4.095e9},
        "reads": 50.0},
    "entry.sync_open_ms_per_op": stage_mean("cmd", "sync", "open", PASS),
    "entry.sync_list_ms_per_op": stage_mean("cmd", "sync", "list", PASS),
    "entry.sync_check_ms_per_op": stage_mean("cmd", "sync", "check", PASS),
    "entry.sync_report_ms_per_op": stage_mean("cmd", "sync", "report", PASS),
    "object.list_objects_per_op": per_op(
        {"series": "juicefs_file_list_objects"}, "juicefs_file_list_objects",
        "object", GC + SCRUB + PASS, 1034.0),
}
# the kernel pair of the XLA program: the same readers, another program's name
AS_THE_XLA_PAIR = {"kernel.pallas_ms_per_batch": "kernel.hash_ms_per_batch",
                   "jth256_pallas_roofline": "jth256_roofline"}
# what the parent's registry holds: stages of other commands, no listing count
PARENT = {STAGE % ("sum", "tpu", "hash", "pack"): 3.0,
          STAGE % ("count", "tpu", "hash", "pack"): 17.0,
          "juicefs_tpu_h2d_bytes": 1.0}


def window(case, registry_after):
    return {"registry_before": dict(PARENT), "registry_after": registry_after,
            "work": case["work"], "trace": case.get("trace"), "device": DEVICE}


def test_they_are_appended_after_what_was_accepted_in_this_order(manifest_root):
    names = [e["name"] for e in checks.manifest(manifest_root)["per_layer"]]
    at = names.index("tpu.pack_prepare_ms_per_buffer") + 1
    assert names[at:at + len(THIRTEEN)] == list(THIRTEEN)


@pytest.mark.parametrize("metric", THIRTEEN)
def test_the_entry_lists_its_cells_under_a_layer_the_manifest_had(manifest_root, metric):
    per_layer = checks.manifest(manifest_root)["per_layer"]
    entry, = [e for e in per_layer if e["name"] == metric]
    want = THIRTEEN[metric]["entry"]
    # its cells first and in order; a later PR's cells come after them
    assert entry["workloads"][:len(want["workloads"])] == want["workloads"]
    assert entry == dict(want, name=metric, workloads=entry["workloads"])
    accepted = per_layer[:[e["name"] for e in per_layer].index(list(THIRTEEN)[0])]
    assert entry["layer"] in {e["layer"] for e in accepted}


@pytest.mark.parametrize("metric", THIRTEEN)
def test_its_file_names_its_reader_and_args(metric):
    spec = spec_of(metric)
    assert (spec["reader"], spec["args"]) == (
        THIRTEEN[metric]["reader"], THIRTEEN[metric]["args"])
    if metric in AS_THE_XLA_PAIR:
        xla = spec_of(AS_THE_XLA_PAIR[metric])
        assert (spec["reader"], spec["args"]) == (xla["reader"], xla["args"])


@pytest.mark.parametrize("metric", THIRTEEN)
def test_it_reads_the_window_and_nothing_where_there_is_nothing(metric):
    case = THIRTEEN[metric]
    read = reader(case["reader"]).read
    after = dict(PARENT, **case.get("gained", {}))
    assert read(window(case, after), **case["args"]) == pytest.approx(case["reads"])
    # the parent's registry: the program has no such series
    parent = window(case, dict(PARENT))
    if "trace" in case:
        parent["trace"] = None  # and off the chip, no device trace
    assert read(parent, **case["args"]) is None


@pytest.mark.parametrize("metric", [m for m, c in THIRTEEN.items()
                                    if c["args"].get("per_work") == "ops"])
def test_a_count_per_op_is_nothing_where_no_op_answered(metric):
    case = THIRTEEN[metric]
    ctx = window(dict(case, work={"ops": 0}), dict(PARENT, **case["gained"]))
    assert reader(case["reader"]).read(ctx, **case["args"]) is None


def test_every_series_read_is_one_the_program_registers():
    from juicefs_tpu.cmd import fsck, gc, sync  # noqa: F401 (register them)
    from juicefs_tpu.meta import redis_kv  # noqa: F401
    from juicefs_tpu.object import file  # noqa: F401

    registered = run.registry_snapshot()
    for metric, case in THIRTEEN.items():
        for series in case.get("gained", {}):
            assert series in registered, (metric, series)
