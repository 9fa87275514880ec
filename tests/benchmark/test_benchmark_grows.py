"""The standing proof that a later PR, of any kind, can add a deployment to
the benchmark as files and entries alone. In a temporary copy of the
benchmark: a configuration of its own (a new file, its own `source`, a
`reduced` key, its entry at the end of `configs`), a mix, a cell at the end of
`workloads`, the cell's name at the end of each per-layer list that holds the
cell of the configuration it is like, and a per-layer metric of its own. Then
every check of the manifest and every pin on what is accepted pass on that
root, the cell runs traced to a `correct` line that carries exactly the
per-layer metrics that list it (less the device's, off the chip), and the
accepted cells resolve to what they resolve to today.

No file that the copy started with is edited but BENCHMARK.json, and there
nothing but appended entries and appended names.

Every pin is relative to the manifest the growth started from, never to a
count: each growth test runs from the repo's manifest, from a base that
already holds one more cell than the repo (the copy grown once by NEXT), so
the cell after the next one meets no pin either, and from a base that holds
one more per-layer entry, listing one cell that is not the first (ONE_CELL):
what a PR of any kind may append. The manifest's pins elsewhere under
tests/benchmark/ run on that base too (`manifest_root`). The repo's own newest
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
    any_device, argv, blocks_of, last_line, make_root, over_limit, process_as_new)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PINNED_BY_NAME = NEW + list(METRICS)  # PR 25's ten and PR 27's two

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
# a per-layer entry more than the repo holds, listing one cell that is not
# the first: the mirror check's pairs compared a pass
ONE_CELL = {"name": "entry.pairs_checked_per_op", "unit": "pairs",
            "better": "higher", "source": "program_counter",
            "layer": "entry (cmd/)", "moves": "op_p50_ms",
            "workloads": ["sync-check-all"],
            "spec": {"reader": "registry",
                     "args": {"kind": "counter_gain",
                              "series": "juicefs_sync_objects",
                              "labels": {"result": "checked"},
                              "per_work": "ops"}}}
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


def like_cell(m, config):
    """The cell whose per-layer lists a deployment `like` another joins: the
    first cell of the configuration it is like."""
    return next(w["name"] for w in m["workloads"] if w["config"] == config["like"])


def add_metric(root, metric):
    """A per-layer entry appended to BENCHMARK.json, and its file."""
    m = checks.manifest(root)
    m["per_layer"].append({k: v for k, v in metric.items() if k != "spec"})
    write_json(os.path.join(checks.bench_dir(root), "layer_metrics",
                            metric["name"] + ".json"), metric["spec"])
    write_json(os.path.join(root, "BENCHMARK.json"), m)


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
    similar = like_cell(m, config)
    m["workloads"].append(dict(cell, config=config["name"], traffic=mix_name))
    for entry in m["per_layer"]:
        if similar in entry["workloads"]:
            entry["workloads"].append(cell["name"])
    write_json(os.path.join(root, "BENCHMARK.json"), m)
    if metric is not None:
        add_metric(root, dict(metric, workloads=[cell["name"]]))
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


@pytest.fixture(params=["the_repo", "one_cell_more", "one_entry_more"])
def base(request, tmp_path):
    """A copy to grow, and what it held before: the repo's manifest, the
    repo's grown once by NEXT (one more cell than the repo holds), or the
    repo's with ONE_CELL appended (one more per-layer entry)."""
    root = new_root(tmp_path)
    if request.param == "one_cell_more":
        add_deployment(root, **NEXT)
        check_all_and_the_pins(root, [NEXT], standing(REPO))
    elif request.param == "one_entry_more":
        add_metric(root, ONE_CELL)
        check_all_and_the_pins(root, [], standing(REPO))
    return root, standing(root)


@pytest.fixture(params=["the_repo", "one_entry_more"])
def manifest_root(request, tmp_path):
    """A root for the pins on the manifest: the repo itself, or a copy of it
    with ONE_CELL appended, which every such pin has to pass as well."""
    if request.param == "the_repo":
        return REPO
    root = new_root(tmp_path)
    add_metric(root, ONE_CELL)
    return root


def check_all_and_the_pins(root, appended, was):
    """Every check of the manifest; and what the root held before (`was`,
    of `standing`) is as it was, but for the deployments `appended`: each
    cell's name at the end of exactly the per-layer lists that held the cell
    of the configuration it is like."""
    checks.check_all(root)
    before, grown = was["manifest"], checks.manifest(root)
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert grown[key] == before[key]
    for key in ("configs", "workloads"):
        assert grown[key][:len(before[key])] == before[key]
    assert [w["name"] for w in grown["workloads"][len(before["workloads"]):]
            ] == [d["cell"]["name"] for d in appended]
    for old, now in zip(before["per_layer"], grown["per_layer"]):
        joined = [d["cell"]["name"] for d in appended
                  if like_cell(before, d["config"]) in old["workloads"]]
        assert now == dict(old, workloads=old["workloads"] + joined)
    for name in PINNED_BY_NAME:
        checks.check_accepted_metric_lists_its_cells(root, name)
    # each cell resolves to what it did, and to appended entries after that
    appended_entries = {e["name"] for e in grown["per_layer"][len(before["per_layer"]):]}
    for cell, (end_to_end, per_layer) in was["resolved"].items():
        now_end_to_end, now_per_layer = resolved_names(root, cell)
        assert now_end_to_end == end_to_end
        assert now_per_layer[:len(per_layer)] == per_layer
        assert set(now_per_layer[len(per_layer):]) <= appended_entries


def test_a_deployment_comes_as_files_and_entries_alone(base, any_device, capsys):
    root, was = base
    body = add_deployment(root, **FOURTH)
    check_all_and_the_pins(root, [FOURTH], was)

    cell, own = FOURTH["cell"]["name"], FOURTH["metric"]["name"]
    assert run.main(argv(cell, trace=1), root=root, device_check=any_device) == 0
    line = last_line(capsys)
    assert over_limit(line) == {} and line["correct"] is True
    listing = checks.listing(root, cell)
    # what lists the cell it is like, and its own; not what lists another
    like = like_cell(was["manifest"], FOURTH["config"])
    assert listing == checks.listing(root, like) | {own}
    assert ONE_CELL["name"] not in listing
    assert set(line["metrics"]) == listing - checks.DEVICE_METRICS
    assert line["metrics"][own] == {"value": body["volume_blocks"], "unit": "blocks"}
    # every op lists chunks/ once: the volume's block objects, exactly
    listed = line["metrics"]["object.list_objects_per_op"]["value"]
    assert listed == body["volume_blocks"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_an_entry_that_lists_one_cell_reaches_that_cells_line_alone(
        any_device, capsys, tmp_path):
    """The repo's manifest with ONE_CELL appended: every pin on the manifest
    passes (here, and through `manifest_root` in every module that pins it),
    the cell it lists carries it on a traced line, and a scan cell does not."""
    root = new_root(tmp_path)
    add_metric(root, ONE_CELL)
    check_all_and_the_pins(root, [], standing(REPO))
    cell, = ONE_CELL["workloads"]
    assert ONE_CELL["name"] in checks.listing(root, cell)
    assert all(ONE_CELL["name"] not in checks.listing(root, other)
               for other in checks.ACCEPTED_CELLS)
    assert run.main(argv(cell, trace=1), root=root, device_check=any_device) == 0
    line = last_line(capsys)
    assert over_limit(line) == {} and line["correct"] is True
    assert set(line["metrics"]) == checks.listing(root, cell) - checks.DEVICE_METRICS
    pairs = blocks_of(root, "sync-file-file-bench-mix")
    assert line["metrics"][ONE_CELL["name"]] == {"value": pairs, "unit": "pairs"}


def test_a_fifth_cell_on_four_chips_is_admitted_at_five_cells_and_not_at_three(
        base, tmp_path):
    root, was = base
    add_deployment(root, **FOURTH)
    add_deployment(root, **FIFTH)
    check_all_and_the_pins(root, [FOURTH, FIFTH], was)
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


def test_the_cell_that_waited_passes_the_manifest_checks_in_the_repo(
        manifest_root, tmp_path):
    """The repo's own newest accepted cell (PERF.md section 7 (a) kept it
    waiting through two refused PRs; PR 31 brought it at the source's own
    mix of files and full blocks): its two entries, among the accepted cells,
    the accepted names first and in order in every list that holds it; and
    with it taken out by name the other accepted cells resolve to what they
    resolve to with it. Nothing here counts what else the manifest holds: a
    later PR appends."""
    config, cell = NEWEST["config"]["name"], NEWEST["cell"]["name"]
    checks.check_all(manifest_root)
    m = checks.manifest(manifest_root)
    entry, = [c for c in m["configs"] if c["name"] == config]
    assert {k: entry[k] for k in NEWEST["config"]} == NEWEST["config"]
    listed, = [w for w in m["workloads"] if w["name"] == cell]
    assert {k: listed[k] for k in NEWEST["cell"]} == NEWEST["cell"]
    assert listed["why"].startswith("361 blocks an op: bench's default mix")
    assert run.read_json(os.path.join(manifest_root, entry["file"]))["source"] == (
        entry["source"])
    assert cell in checks.ACCEPTED_CELLS
    holding = [e["name"] for e in m["per_layer"] if cell in e["workloads"]]
    assert set(PINNED_BY_NAME) | {"tpu.blocks_per_batch"} <= set(holding)
    for name in holding:
        checks.check_accepted_metric_lists_its_cells(manifest_root, name)

    root = new_root(tmp_path / "without")
    write_json(os.path.join(root, "BENCHMARK.json"), without(m, config, cell))
    for other in (c for c in checks.ACCEPTED_CELLS if c != cell):
        assert resolved_names(root, other) == resolved_names(manifest_root, other)
    with pytest.raises(run.Refused):
        run.resolve(root, cell)


def test_an_edit_to_what_is_accepted_fails_the_pins(base):
    """The pins bite: a cell put before the accepted ones, or a name put
    into the middle of an accepted list, is no longer "appended"; nor is a
    cell's name put on a list that does not hold the cell it is like."""
    root, was = base
    add_deployment(root, **FOURTH)
    m = checks.manifest(root)
    moved = copy.deepcopy(m)
    moved["workloads"].insert(0, moved["workloads"].pop())
    write_json(os.path.join(root, "BENCHMARK.json"), moved)
    with pytest.raises(AssertionError):
        checks.check_accepted_cells_come_first(root)
    with pytest.raises(AssertionError):
        check_all_and_the_pins(root, [FOURTH], was)
    moved = copy.deepcopy(m)
    accepted = "tpu.pack_prepare_ms_per_buffer"
    listed, = [e["workloads"] for e in moved["per_layer"] if e["name"] == accepted]
    listed.insert(1, listed.pop())
    write_json(os.path.join(root, "BENCHMARK.json"), moved)
    with pytest.raises(AssertionError):
        checks.check_accepted_metric_lists_its_cells(root, accepted)
    # the cell on a list that holds the scrub's cell alone
    moved = copy.deepcopy(m)
    scrubs, = [e for e in moved["per_layer"] if e["name"] == "entry.fsck_list_ms_per_op"]
    scrubs["workloads"].append(FOURTH["cell"]["name"])
    write_json(os.path.join(root, "BENCHMARK.json"), moved)
    with pytest.raises(AssertionError):
        check_all_and_the_pins(root, [FOURTH], was)
    # a cell put inside what the root held, after the accepted ones
    moved = copy.deepcopy(m)
    moved["workloads"].insert(len(was["manifest"]["workloads"]) - 1,
                              moved["workloads"].pop())
    write_json(os.path.join(root, "BENCHMARK.json"), moved)
    with pytest.raises(AssertionError):
        check_all_and_the_pins(root, [FOURTH], was)
