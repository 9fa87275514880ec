"""The standing proof that a later PR, of any kind, can add a deployment to
the benchmark as files and entries alone. In a temporary copy of the
benchmark: a configuration of its own (a new file, its own `source`, a
`reduced` key, its entry at the end of `configs`), a mix, a cell at the end of
`workloads`, the cell's name at the end of every accepted per-layer list, and a
per-layer metric of its own. Then every check of the manifest and every pin
on what is accepted pass on that root, the cell runs traced to a `correct`
line that carries every accepted per-layer metric it can read and its own,
and the accepted cells resolve to what they resolve to today.

No file that the copy started with is edited but BENCHMARK.json, and there
nothing but appended entries and appended names."""

import copy
import json
import os

import pytest

import manifest_checks as checks
from benchmark import run
from benchmark.lib.plan import plan_of
from test_benchmark_pack_fresh import METRICS
from test_benchmark_program_spans import NEW
from test_benchmark_run import (  # noqa: F401 (fixtures)
    any_device, argv, last_line, make_root, over_limit, process_as_new)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PINNED_BY_NAME = NEW + list(METRICS)  # PR 25's ten and PR 27's two
# no device plane off the chip: a rehearsal leaves these out, never 0
DEVICE_METRICS = {"kernel.hash_ms_per_batch", "jth256_roofline",
                  "device.idle_share", "device.peak_bytes"}

FOURTH = {
    "config": {"name": "scan-sqlite-file-4m-halfdup", "like": "scan-sqlite-file-4m",
               "source": "a test's own deployment: the sqlite3 + file:// 4 MiB volume "
                         "with half of its blocks duplicates and other ragged sizes",
               "volume": {"dup_probability": 0.5, "ragged_sizes": [7, 65537]}},
    "mix": ("cold-files", {"driver": "scan", "forget": "all"}),
    "cell": {"name": "scan-cold-halfdup", "chips": 1,
             "why": "a fourth cell, as a later PR appends one"},
    "metric": {"name": "entry.blocks_per_op", "unit": "blocks", "better": "higher",
               "source": "program_counter", "layer": "entry (cmd/)",
               "moves": "scan_gibs",
               "spec": {"reader": "op_stat", "args": {"field": "stats.blocks"}}},
}
FIFTH = {
    "config": {"name": "scan-sqlite-file-4m-x4-halfdup",
               "like": "scan-sqlite-file-4m-x4",
               "source": "a test's own deployment: the half-duplicate volume through "
                         "the ShardPlane mesh of one four-chip host",
               "volume": {"dup_probability": 0.5}},
    "mix": ("cold-files", {"driver": "scan", "forget": "all"}),
    "cell": {"name": "scan-cold-x4-halfdup", "chips": 4,
             "why": "a fifth cell, on four chips"},
}
# the cell that waits (PERF.md section 7 (a)), over a stand-in configuration file
WAITING = {
    "config": {"name": "scan-sqlite-file-smallfiles", "like": "scan-sqlite-file-4m",
               "source": "JuiceFS `juicefs bench`/`objbench` defaults (cmd/bench.go: 4 MiB "
                         "blocks, small files 128 KiB, one object each) on sqlite3 + "
                         "file://; BASELINE.json metric \"dedup scan GiB/s and blocks/s\"",
               "volume": {}},
    "mix": ("cold-files", {"driver": "scan", "forget": "all"}),
    "cell": {"name": "scan-cold-smallfiles", "chips": 1,
             "why": "4,165 blocks an op, 98% of them 128 KiB files of one object each: "
                    "batches by lane class; closed loop, one operator, every row "
                    "forgotten before each op."},
}


def write_json(path, body):
    with open(path, "w") as f:
        json.dump(body, f)


def new_root(tmp_path):
    """make_root's copy, and tests/benchmark beside it: `paths` names both."""
    root = make_root(str(tmp_path / "root"))
    os.makedirs(os.path.join(root, "tests"))
    os.symlink(os.path.join(REPO, "tests", "benchmark"),
               os.path.join(root, "tests", "benchmark"))
    return root


def add_deployment(root, config, mix, cell, metric=None):
    """New files, and entries appended to BENCHMARK.json; nothing else."""
    bench = checks.bench_dir(root)
    m = checks.manifest(root)
    like, = [c for c in m["configs"] if c["name"] == config["like"]]
    body = run.read_json(os.path.join(root, like["file"]))
    body.update(name=config["name"], source=config["source"])
    body["volume"].update(config["volume"])
    body["volume_blocks"] = len(plan_of(0, body["volume"]).blocks)
    file = f"{m['paths'][0]}/configs/{config['name']}.json"
    assert not os.path.exists(os.path.join(root, file))
    write_json(os.path.join(root, file), body)
    m["configs"].append({"name": config["name"], "source": config["source"],
                         "file": file, "reduced": ["volume_blocks"],
                         "why": "a deployment of its own"})
    mix_name, mix_body = mix
    mix_file = os.path.join(bench, "traffic", mix_name + ".json")
    if not os.path.exists(mix_file):  # two cells may share a mix: one file
        write_json(mix_file, mix_body)
    assert run.read_json(mix_file) == mix_body
    m["workloads"].append(dict(cell, config=config["name"], traffic=mix_name))
    for entry in m["per_layer"]:
        entry["workloads"].append(cell["name"])
    if metric is not None:
        entry = {k: v for k, v in metric.items() if k != "spec"}
        m["per_layer"].append(dict(entry, workloads=[cell["name"]]))
        write_json(os.path.join(bench, "layer_metrics", metric["name"] + ".json"),
                   metric["spec"])
    write_json(os.path.join(root, "BENCHMARK.json"), m)
    return body


def resolved_names(root, cell):
    r = run.resolve(root, cell)
    return ([e["name"] for e in r["end_to_end"]],
            [e["name"] for e in r["per_layer"]])


def check_all_and_the_pins(root, appended):
    """Every check of the manifest; and what is accepted is as accepted, but
    for the names appended to its per-layer lists."""
    checks.check_all(root)
    accepted, grown = checks.manifest(REPO), checks.manifest(root)
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert grown[key] == accepted[key]
    for key in ("configs", "workloads"):
        assert grown[key][:len(accepted[key])] == accepted[key]
    for was, now in zip(accepted["per_layer"], grown["per_layer"]):
        assert now == dict(was, workloads=was["workloads"] + appended)
    for name in PINNED_BY_NAME:
        checks.check_accepted_metric_lists_its_cells(root, name)
    for cell in checks.ACCEPTED_CELLS:
        assert resolved_names(root, cell) == resolved_names(REPO, cell)


def test_a_deployment_comes_as_files_and_entries_alone(tmp_path, any_device, capsys):
    root = new_root(tmp_path)
    body = add_deployment(root, **FOURTH)
    check_all_and_the_pins(root, [FOURTH["cell"]["name"]])

    cell, own = FOURTH["cell"]["name"], FOURTH["metric"]["name"]
    assert run.main(argv(cell, trace=1), root=root, device_check=any_device) == 0
    line = last_line(capsys)
    assert over_limit(line) == {} and line["correct"] is True
    accepted = {e["name"] for e in checks.manifest(REPO)["per_layer"]}
    assert set(line["metrics"]) == (accepted - DEVICE_METRICS) | {own}
    assert line["metrics"][own] == {"value": body["volume_blocks"], "unit": "blocks"}
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_fifth_cell_on_four_chips_is_admitted_at_five_cells_and_not_at_three(tmp_path):
    root = new_root(tmp_path)
    add_deployment(root, **FOURTH)
    add_deployment(root, **FIFTH)
    check_all_and_the_pins(root, [FOURTH["cell"]["name"], FIFTH["cell"]["name"]])
    m = checks.manifest(root)
    assert [w["chips"] for w in m["workloads"]] == [1, 1, 4, 1, 4]

    # the same two four-chip cells among three cells: refused
    three = copy.deepcopy(m)
    three["workloads"] = [w for w in m["workloads"]
                          if w["chips"] == 4 or w["name"] == "scan-cold"]
    os.makedirs(tmp_path / "three")
    write_json(tmp_path / "three" / "BENCHMARK.json", three)
    with pytest.raises(AssertionError):
        checks.check_at_most_half_take_four_chips(str(tmp_path / "three"))


def test_the_cell_that_waits_passes_the_manifest_checks(tmp_path):
    root = new_root(tmp_path)
    add_deployment(root, **WAITING)
    check_all_and_the_pins(root, [WAITING["cell"]["name"]])
    m = checks.manifest(root)
    assert m["configs"][-1]["reduced"] == ["volume_blocks"]
    assert m["workloads"][-1] == {
        "name": "scan-cold-smallfiles", "config": "scan-sqlite-file-smallfiles",
        "traffic": "cold-files", "chips": 1, "why": WAITING["cell"]["why"]}


def test_an_edit_to_what_is_accepted_fails_the_pins(tmp_path):
    """The pins bite: a cell put before the accepted three, or a name put
    into the middle of an accepted list, is no longer "appended"."""
    root = new_root(tmp_path)
    add_deployment(root, **FOURTH)
    m = checks.manifest(root)
    moved = copy.deepcopy(m)
    moved["workloads"].insert(0, moved["workloads"].pop())
    write_json(os.path.join(root, "BENCHMARK.json"), moved)
    with pytest.raises(AssertionError):
        checks.check_accepted_cells_come_first(root)
    moved = copy.deepcopy(m)
    listed = moved["per_layer"][-2]["workloads"]  # an accepted metric's
    listed.insert(1, listed.pop())
    write_json(os.path.join(root, "BENCHMARK.json"), moved)
    with pytest.raises(AssertionError):
        checks.check_accepted_metric_lists_its_cells(root, moved["per_layer"][-2]["name"])
