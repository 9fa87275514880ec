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
nothing but appended entries and appended names.

Every pin is relative to the manifest the growth started from, never to a
count: each growth test runs from the repo's manifest and again from a base
that already holds one more cell than the repo (the copy grown once by NEXT),
so the cell after the next one meets no pin either. The repo's own newest
cell (`scan-cold-bench-mix`, PR 31) is held by name: its two entries, its
source down to the defaults that define its volume, and the other accepted
cells resolve alike without it."""

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
# a cell more than the repo holds: the base the growth tests run from again
NEXT = {
    "config": {"name": "scan-sqlite-file-4m-nodup", "like": "scan-sqlite-file-4m",
               "source": "a test's own deployment: the sqlite3 + file:// 4 MiB volume "
                         "with no duplicate planted",
               "volume": {"dup_probability": 0.0}},
    "mix": ("cold-next", {"driver": "scan", "forget": "all"}),
    "cell": {"name": "scan-cold-nodup", "chips": 1,
             "why": "the cell a later PR has already appended when this one comes"},
}
# the repo's newest cell (PR 31): the volume of upstream's own benchmark, its
# source named down to the defaults that fix the mix of files and full blocks
NEWEST = {
    "config": {
        "name": "scan-sqlite-file-bench-mix",
        "source": "JuiceFS docs, `juicefs bench` defaults (cmd/bench.go: --big-file-size "
                  "1024 MiB, --small-file-size 128 KiB, --small-file-count 100, -p 1) then "
                  "`juicefs gc`; sqlite3 + file://: BASELINE.json configs[0]",
        "file": "benchmark/configs/scan-sqlite-file-bench-mix.json",
        "reduced": ["volume_blocks"]},
    "cell": {"name": "scan-cold-bench-mix", "config": "scan-sqlite-file-bench-mix",
             "traffic": "cold", "chips": 1},
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


def standing(root):
    """What a root holds before it grows: its manifest, and what each of
    its cells resolves to."""
    m = checks.manifest(root)
    return {"manifest": m, "resolved": {
        w["name"]: resolved_names(root, w["name"]) for w in m["workloads"]}}


@pytest.fixture(params=["the_repo", "one_cell_more"])
def base(request, tmp_path):
    """A copy to grow, and what it held before: the repo's manifest, or the
    repo's grown once by NEXT (one more cell than the repo holds)."""
    root = new_root(tmp_path)
    if request.param == "one_cell_more":
        add_deployment(root, **NEXT)
        check_all_and_the_pins(root, [NEXT["cell"]["name"]], standing(REPO))
    return root, standing(root)


def check_all_and_the_pins(root, appended, was):
    """Every check of the manifest; and what the root held before (`was`,
    of `standing`) is as it was, but for the names appended to its
    per-layer lists."""
    checks.check_all(root)
    before, grown = was["manifest"], checks.manifest(root)
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert grown[key] == before[key]
    for key in ("configs", "workloads"):
        assert grown[key][:len(before[key])] == before[key]
    assert [w["name"] for w in grown["workloads"][len(before["workloads"]):]
            ] == appended
    for old, now in zip(before["per_layer"], grown["per_layer"]):
        assert now == dict(old, workloads=old["workloads"] + appended)
    for name in PINNED_BY_NAME:
        checks.check_accepted_metric_lists_its_cells(root, name)
    for cell, names in was["resolved"].items():
        assert resolved_names(root, cell) == names


def test_a_deployment_comes_as_files_and_entries_alone(base, any_device, capsys):
    root, was = base
    body = add_deployment(root, **FOURTH)
    check_all_and_the_pins(root, [FOURTH["cell"]["name"]], was)

    cell, own = FOURTH["cell"]["name"], FOURTH["metric"]["name"]
    assert run.main(argv(cell, trace=1), root=root, device_check=any_device) == 0
    line = last_line(capsys)
    assert over_limit(line) == {} and line["correct"] is True
    accepted = {e["name"] for e in checks.manifest(REPO)["per_layer"]}
    assert set(line["metrics"]) == (accepted - DEVICE_METRICS) | {own}
    assert line["metrics"][own] == {"value": body["volume_blocks"], "unit": "blocks"}
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_fifth_cell_on_four_chips_is_admitted_at_five_cells_and_not_at_three(
        base, tmp_path):
    root, was = base
    add_deployment(root, **FOURTH)
    add_deployment(root, **FIFTH)
    check_all_and_the_pins(
        root, [FOURTH["cell"]["name"], FIFTH["cell"]["name"]], was)
    m = checks.manifest(root)
    # what the test added, not what the root held: a one-chip cell, then a
    # four-chip one, and with them half of the cells or fewer take four
    assert [w["chips"] for w in m["workloads"]][-2:] == [1, 4]

    # the same two four-chip cells among three cells: refused
    three = copy.deepcopy(m)
    three["workloads"] = [w for w in m["workloads"]
                          if w["chips"] == 4 or w["name"] == "scan-cold"]
    os.makedirs(tmp_path / "three")
    write_json(tmp_path / "three" / "BENCHMARK.json", three)
    with pytest.raises(AssertionError):
        checks.check_at_most_half_take_four_chips(str(tmp_path / "three"))


def without(m, config, cell):
    """The manifest as it stood before `cell` on `config` was appended."""
    return dict(
        m, configs=[c for c in m["configs"] if c["name"] != config],
        workloads=[w for w in m["workloads"] if w["name"] != cell],
        per_layer=[dict(e, workloads=[w for w in e["workloads"] if w != cell])
                   for e in m["per_layer"]])


def test_the_cell_that_waited_passes_the_manifest_checks_in_the_repo(tmp_path):
    """The repo's own newest accepted cell (PERF.md section 7 (a) kept it
    waiting through two refused PRs; PR 31 brought it at the source's own
    mix of files and full blocks): its two entries, among the accepted cells,
    the accepted names first and in order in every list that holds it; and
    with it taken out by name the other accepted cells resolve to what they
    resolve to with it. Nothing here counts what else the manifest holds: a
    later PR appends."""
    config, cell = NEWEST["config"]["name"], NEWEST["cell"]["name"]
    checks.check_all(REPO)
    m = checks.manifest(REPO)
    entry, = [c for c in m["configs"] if c["name"] == config]
    assert {k: entry[k] for k in NEWEST["config"]} == NEWEST["config"]
    listed, = [w for w in m["workloads"] if w["name"] == cell]
    assert {k: listed[k] for k in NEWEST["cell"]} == NEWEST["cell"]
    assert listed["why"].startswith("361 blocks an op: bench's default mix")
    assert run.read_json(os.path.join(REPO, entry["file"]))["source"] == entry["source"]
    assert cell in checks.ACCEPTED_CELLS
    holding = [e["name"] for e in m["per_layer"] if cell in e["workloads"]]
    assert set(PINNED_BY_NAME) | {"tpu.blocks_per_batch"} <= set(holding)
    for name in holding:
        checks.check_accepted_metric_lists_its_cells(REPO, name)

    root = new_root(tmp_path)
    write_json(os.path.join(root, "BENCHMARK.json"), without(m, config, cell))
    for other in (c for c in checks.ACCEPTED_CELLS if c != cell):
        assert resolved_names(root, other) == resolved_names(REPO, other)
    with pytest.raises(run.Refused):
        run.resolve(root, cell)


def test_an_edit_to_what_is_accepted_fails_the_pins(base):
    """The pins bite: a cell put before the accepted ones, or a name put
    into the middle of an accepted list, is no longer "appended"."""
    root, was = base
    add_deployment(root, **FOURTH)
    m = checks.manifest(root)
    moved = copy.deepcopy(m)
    moved["workloads"].insert(0, moved["workloads"].pop())
    write_json(os.path.join(root, "BENCHMARK.json"), moved)
    with pytest.raises(AssertionError):
        checks.check_accepted_cells_come_first(root)
    with pytest.raises(AssertionError):
        check_all_and_the_pins(root, [FOURTH["cell"]["name"]], was)
    moved = copy.deepcopy(m)
    listed = moved["per_layer"][-2]["workloads"]  # an accepted metric's
    listed.insert(1, listed.pop())
    write_json(os.path.join(root, "BENCHMARK.json"), moved)
    with pytest.raises(AssertionError):
        checks.check_accepted_metric_lists_its_cells(root, moved["per_layer"][-2]["name"])
    # a cell put inside what the root held, after the accepted ones
    moved = copy.deepcopy(m)
    moved["workloads"].insert(len(was["manifest"]["workloads"]) - 1,
                              moved["workloads"].pop())
    write_json(os.path.join(root, "BENCHMARK.json"), moved)
    with pytest.raises(AssertionError):
        check_all_and_the_pins(root, [FOURTH["cell"]["name"]], was)
