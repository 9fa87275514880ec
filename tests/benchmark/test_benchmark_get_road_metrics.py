"""The per-layer metrics that split `object.get_ms`, a block's GET, into the
parts the program times where they happen — the fetch stage's queue, the
`chunk.load.fetch` span, the resilience pool's two hand-offs, the store's own
read and its CPU time, the share of GETs hedged — and the one that reads
`main`'s argument parsing. Each is a data file on the `registry` reader,
appended after what was accepted and listing only the cells whose code has
the part.

Here, over one table: each entry and its place in the manifest, each file's
reader and arguments, the number each reads from a hand-made window, nothing
where the program has no such series, and every series read is one today's
program registers. Then two traced rehearsals on the CPU: in `scan-cold`
every op's store claims one label (the hedge share's data file names it),
and the parts nest inside the whole, as in the mirror check's."""

import pytest

import manifest_checks as checks
from benchmark import run
from test_benchmark_grows import manifest_root  # noqa: F401 (fixture)
from test_benchmark_program_spans import reader, spec_of
from test_benchmark_run import (  # noqa: F401 (fixtures)
    any_device, argv, last_line, make_root, over_limit, process_as_new)

SIX = checks.ACCEPTED_CELLS + ["fsck-verify-redis", "sync-check-all"]
LOADS = checks.ACCEPTED_CELLS + ["fsck-verify-redis"]  # a chunk store's GETs
CHUNK = "chunk (chunk/parallel.py)"
STAGE = 'juicefs_tpu_stage_seconds_%s{layer="%s",op="%s",stage="%s"}'
STORE = '%s_%s{backend="file",method="GET"}'
DUR = "juicefs_object_request_durations_histogram_seconds"
CPU = "juicefs_object_request_cpu_seconds"
HEDGES = 'juicefs_object_hedged_requests{backend="file"}'


def entry(layer, cells, source="program_counter", moves="scan_gibs", unit="ms"):
    return {"unit": unit, "better": "lower", "source": source, "layer": layer,
            "moves": moves, "workloads": cells}


def stage_mean(layer, op, stage, cells, entry_layer, source="program_counter",
               moves="scan_gibs"):
    """The mean of one stage of the program's stage histogram, in ms."""
    return {
        "entry": entry(entry_layer, cells, source, moves),
        "args": {"kind": "histogram_mean", "series": "juicefs_tpu_stage_seconds",
                 "labels": {"layer": layer, "op": op, "stage": stage},
                 "scale": 1000},
        "gained": {STAGE % ("sum", layer, op, stage): 0.25,
                   STAGE % ("count", layer, op, stage): 2.0},
        "work": {}, "reads": 125.0}


def store_mean(series, source):
    return {
        "entry": entry("object", SIX, source),
        "args": {"kind": "histogram_mean", "series": series,
                 "labels": {"backend": "file", "method": "GET"}, "scale": 1000},
        "gained": {STORE % (series, "sum"): 0.008, STORE % (series, "count"): 4.0},
        "work": {}, "reads": 2.0}


EIGHT = {
    "chunk.fetch_queue_ms": stage_mean("chunk", "fetch", "queue", SIX, CHUNK),
    "chunk.load_fetch_ms": stage_mean("chunk", "load", "fetch", LOADS, CHUNK,
                                      source="program_span"),
    "object.resilient_queue_ms": stage_mean("object", "get", "queue", SIX, "object"),
    "object.resilient_wake_ms": stage_mean("object", "get", "wake", SIX, "object"),
    "object.store_get_ms": store_mean(DUR, "program_span"),
    "object.store_get_cpu_ms": store_mean(CPU, "program_counter"),
    "object.hedged_share": {
        "entry": entry("object", LOADS, unit="%"),
        "args": {"kind": "counter_gain", "series": "juicefs_object_hedged_requests",
                 "labels": {"backend": "file"}, "per_work": "hashed_blocks",
                 "scale": 100},
        "gained": {HEDGES: 3.0}, "work": {"hashed_blocks": 1034}, "reads": 300 / 1034},
    "entry.parse_ms_per_op": stage_mean("cmd", "main", "parse", SIX, "entry (cmd/)",
                                        source="program_span", moves="op_p50_ms"),
}
# what the parent's registry holds: stages of other commands, none of these
PARENT = {STAGE % ("sum", "tpu", "hash", "pack"): 3.0,
          STAGE % ("count", "tpu", "hash", "pack"): 17.0,
          "juicefs_tpu_h2d_bytes": 1.0}


def window(case, registry_after):
    return {"registry_before": dict(PARENT), "registry_after": registry_after,
            "work": case["work"]}


def test_they_are_appended_after_what_was_accepted_in_this_order(manifest_root):
    names = [e["name"] for e in checks.manifest(manifest_root)["per_layer"]]
    at = names.index("object.list_objects_per_op") + 1
    assert names[at:at + len(EIGHT)] == list(EIGHT)


@pytest.mark.parametrize("metric", EIGHT)
def test_the_entry_lists_its_cells_under_a_layer_the_manifest_had(manifest_root, metric):
    per_layer = checks.manifest(manifest_root)["per_layer"]
    entry_, = [e for e in per_layer if e["name"] == metric]
    want = EIGHT[metric]["entry"]
    # of the six cells exactly those whose code has the part, first and in
    # order; a later PR's cells come after them
    assert [c for c in entry_["workloads"] if c in SIX] == want["workloads"]
    assert entry_["workloads"][:len(want["workloads"])] == want["workloads"]
    assert entry_ == dict(want, name=metric, workloads=entry_["workloads"])
    accepted = per_layer[:[e["name"] for e in per_layer].index(list(EIGHT)[0])]
    assert entry_["layer"] in {e["layer"] for e in accepted}
    assert entry_["moves"] in {e["moves"] for e in accepted}


@pytest.mark.parametrize("metric", EIGHT)
def test_its_file_names_the_registry_reader_and_args(metric):
    spec = spec_of(metric)
    assert (spec["reader"], spec["args"]) == ("registry", EIGHT[metric]["args"])


@pytest.mark.parametrize("metric", EIGHT)
def test_it_reads_the_window_and_nothing_where_there_is_nothing(metric):
    case = EIGHT[metric]
    read = reader("registry").read
    after = dict(PARENT, **case["gained"])
    assert read(window(case, after), **case["args"]) == pytest.approx(case["reads"])
    assert read(window(case, dict(PARENT)), **case["args"]) is None


def test_the_hedge_share_reads_zero_where_nothing_hedged():
    case = EIGHT["object.hedged_share"]
    ctx = window(case, dict(PARENT, **{HEDGES: 0.0}))
    assert reader("registry").read(ctx, **case["args"]) == 0.0


def test_every_series_read_is_one_the_program_registers(tmp_path):
    from juicefs_tpu.cmd import fsck, gc, sync  # noqa: F401 (register them)
    from juicefs_tpu.object import create_storage
    from juicefs_tpu.object.metered import metered
    from juicefs_tpu.object.resilient import resilient

    # a `file://` store as the commands stack it: its children exist
    store = resilient(metered(create_storage(f"file://{tmp_path}/")))
    try:
        registered = run.registry_snapshot()
    finally:
        store.close()
    for metric, case in EIGHT.items():
        for series in case["gained"]:
            assert series in registered, (metric, series)


# -- traced rehearsals ---------------------------------------------------------

def _traced(root, cell, device_check, capsys):
    assert run.main(argv(cell, trace=1), root=root, device_check=device_check) == 0
    line = last_line(capsys)
    assert over_limit(line) == {} and line["correct"] is True
    return {k: v["value"] for k, v in line["metrics"].items()}


def test_scan_cold_claims_one_label_and_its_parts_nest(tmp_path, any_device,
                                                       capsys, monkeypatch):
    """Every op's store claims the label `file` (the store of the op before
    is closed by then), so the hedge share's data file, with that label
    fixed, reads every op; the parts of a GET lie inside `chunk.load.fetch`,
    which lies inside `object.get_ms`."""
    import importlib

    res = importlib.import_module("juicefs_tpu.object.resilient")
    claimed = []
    real = res._claim_label

    def claim(base):
        label = real(base)
        claimed.append(label)
        return label

    monkeypatch.setattr(res, "_claim_label", claim)
    m = _traced(make_root(str(tmp_path / "root")), "scan-cold", any_device, capsys)
    assert claimed and set(claimed) == {"file"}
    for name in EIGHT:
        assert m[name] >= 0, name
    assert m["object.hedged_share"] <= 100
    assert (m["object.resilient_queue_ms"] + m["object.store_get_ms"]
            + m["object.resilient_wake_ms"]
            <= m["chunk.load_fetch_ms"] + 1e-3 <= m["object.get_ms"] + 2e-3)
    assert m["object.store_get_cpu_ms"] <= m["object.store_get_ms"] + 1e-3
    assert 0 < m["entry.parse_ms_per_op"] < m["entry.listing_ms_per_op"]


def test_in_the_mirror_check_the_parts_nest_in_the_get(tmp_path, any_device, capsys):
    m = _traced(make_root(str(tmp_path / "root"), big_objects=1),
                "sync-check-all", any_device, capsys)
    assert "chunk.load_fetch_ms" not in m and "object.hedged_share" not in m
    assert (m["object.resilient_queue_ms"] + m["object.store_get_ms"]
            + m["object.resilient_wake_ms"] <= m["object.get_ms"] + 1e-3)
    assert m["object.store_get_cpu_ms"] <= m["object.store_get_ms"] + 1e-3
    assert m["entry.parse_ms_per_op"] > 0


# -- the same metrics from an untraced run (tools/get_road_probe.py) -----------

@pytest.mark.parametrize("cell", ["scan-cold", "sync-check-all"])
def test_the_probe_reads_them_from_an_untraced_window(tmp_path, any_device,
                                                      capsys, cell):
    import json

    from tools import get_road_probe

    root = make_root(str(tmp_path / "root"), big_objects=1)
    assert get_road_probe.main(["--workload", cell, "--seed", str(2**31 + 41),
                                "--seconds", "0.5", "--root", root],
                               device_check=any_device) == 0
    out = capsys.readouterr().out.strip().splitlines()
    result, road = json.loads(out[-2]), out[-1]
    assert result["correct"] is True and "breakdown" not in result
    assert road.startswith("GET_ROAD ")
    m = json.loads(road[len("GET_ROAD "):])
    listed = {name for name in EIGHT if cell in EIGHT[name]["entry"]["workloads"]}
    assert listed - {"entry.parse_ms_per_op"} <= set(m)
    assert m["store_off_cpu_ms"] >= -1e-3
    if cell == "scan-cold":
        assert m["load_block_own_ms"] >= -1e-3 and m["gate_shed_ms"] >= -1e-3
    else:
        assert m["wrappers_ms"] >= -1e-3
