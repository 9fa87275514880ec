"""The checks of BENCHMARK.json as functions of a root directory, so that the
same code holds the repo's manifest (test_benchmark_manifest.py) and a
temporary copy that a test has grown by a deployment
(test_benchmark_grows.py). A plain module, not a conftest.py: a second module
of that name would shadow tests/conftest.py.

The rule for every test under tests/benchmark/: they hold the harness and the
meaning of its metrics. What is accepted is pinned as accepted — the
accepted cells first and in order (ACCEPTED_CELLS), each accepted metric's
fields, its list of cells *starting* with those — and never the count of
cells or of metrics: a later PR appends. An assertion on a manifest list is
relative to the manifest it started from (a slice by that manifest's length,
a filter by name), never a literal list or length of the whole; a traced
line is held to the names whose `workloads` hold its cell (`listing`), so an
appended entry may list one cell or a few. How the
program batches, how many programs or buffers it uses in a rehearsal op, is
held by tests/test_pack_buffers.py and its like, which any PR may edit; here
a metric is held to what it means (a share to its range, a part to its
whole), not to the value today's batching gives it.
"""

import os
import re

from benchmark import run
from benchmark.run import read_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
ACCEPTED_CELLS = ["scan-cold", "scan-incr", "scan-cold-x4", "scan-cold-bench-mix"]
# no device plane off the chip: a rehearsal leaves these out, never 0
DEVICE_METRICS = {"kernel.hash_ms_per_batch", "jth256_roofline",
                  "kernel.pallas_ms_per_batch", "jth256_pallas_roofline",
                  "device.idle_share", "device.peak_bytes"}


def manifest(root):
    return read_json(os.path.join(root, "BENCHMARK.json"))


def listing(root, cell):
    """The per-layer names whose `workloads` hold `cell`: what its traced
    line carries on the chip (an entry without the key lists every cell)."""
    return {e["name"] for e in manifest(root)["per_layer"]
            if cell in e.get("workloads", [cell])}


def bench_dir(root):
    return os.path.join(root, manifest(root)["paths"][0])


def all_metrics(root):
    m = manifest(root)
    return m["end_to_end"] + m["per_layer"]


def check_top_level(root):
    m = manifest(root)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 << 10
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    # a full check with the full 24 cells has to fit into 43200 s
    assert (2 + 14 * 24) * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= len(m["paths"]) <= 16 and len(m["command"]) <= 32
    assert 1 <= len(m["configs"]) <= 24 and 1 <= len(m["workloads"]) <= 24
    assert 1 <= len(m["end_to_end"]) <= 16 and 1 <= len(m["per_layer"]) <= 128
    for p in m["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p) and os.path.isdir(
            os.path.join(root, p))
    assert os.path.isfile(os.path.join(root, m["command"][1]))
    assert any(m["command"][1].startswith(p + "/") for p in m["paths"])


def check_metric_entry(root, metric):
    m = manifest(root)
    per_layer = metric in m["per_layer"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in m["workloads"]}
    listed = metric.get("workloads", sorted(cells))
    assert set(listed) <= cells and len(set(listed)) == len(listed)
    if per_layer:
        assert metric["moves"] in {e["name"] for e in m["end_to_end"]}
        assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%" and metric["source"] == "device_trace"
    else:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25


def check_names(root):
    m = manifest(root)
    for group in (all_metrics(root), m["workloads"], m["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    assert "setup_s" in {e["name"] for e in m["end_to_end"]}


def check_config_entry_and_file(root, config):
    m = manifest(root)
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert all(1 <= len(config[k]) <= 200 and "\n" not in config[k]
               and "\t" not in config[k] for k in ("source", "why"))
    assert any(config["file"].startswith(p + "/") for p in m["paths"])
    body = read_json(os.path.join(root, config["file"]))
    assert body["name"] == config["name"] and body["source"] == config["source"]
    assert len(config["reduced"]) <= 16
    for key in config["reduced"]:
        assert NAME.match(key) and key in body["reduced"] and key in body
        assert not key.endswith(("_dim", "_rank"))
    assert body["guarantees"] and body["deployment"]["chips"] in (1, 4)
    assert config["name"] in {w["config"] for w in m["workloads"]}
    files = [c["file"] for c in m["configs"]]
    sources = [c["source"] for c in m["configs"]]
    assert len(set(files)) == len(files) and len(set(sources)) == len(sources)


def check_workload_resolves_to_files(root, cell):
    m = manifest(root)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["traffic"]) and 1 <= len(cell["why"]) <= 200
    r = run.resolve(root, cell["name"])
    assert r["config"]["deployment"]["chips"] == cell["chips"]
    driver = os.path.join(bench_dir(root), "drivers", r["traffic"]["driver"] + ".py")
    assert os.path.isfile(driver)
    reported = {e["name"] for e in r["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2 and r["per_layer"]
    for metric in r["per_layer"]:
        spec = read_json(os.path.join(
            bench_dir(root), "layer_metrics", metric["name"] + ".json"))
        assert os.path.isfile(os.path.join(
            bench_dir(root), "readers", spec["reader"] + ".py"))
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs)


def check_at_most_half_take_four_chips(root):
    cells = manifest(root)["workloads"]
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)
    assert all(w["chips"] in (1, 4) for w in cells)


def check_every_layer_metric_file_has_an_entry(root):
    names = {m["name"] for m in manifest(root)["per_layer"]}
    files = {f[:-5] for f in os.listdir(os.path.join(bench_dir(root), "layer_metrics"))}
    assert files == names


def check_files_are_named_from_name_characters(root):
    for p in manifest(root)["paths"]:
        for base, dirs, files in os.walk(os.path.join(root, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert re.fullmatch(r"[A-Za-z0-9_.\-]+", f), os.path.join(base, f)


def check_accepted_cells_come_first(root):
    """The accepted cells are the first, in order; what follows them is a
    later PR's."""
    names = [w["name"] for w in manifest(root)["workloads"]]
    assert names[:len(ACCEPTED_CELLS)] == ACCEPTED_CELLS


def check_accepted_metric_lists_its_cells(root, metric_name):
    """An accepted per-layer metric's list of cells starts with the accepted
    cells, in order; every further name is a cell of the manifest."""
    m = manifest(root)
    entry, = [e for e in m["per_layer"] if e["name"] == metric_name]
    listed = entry["workloads"]
    assert listed[:len(ACCEPTED_CELLS)] == ACCEPTED_CELLS
    further = listed[len(ACCEPTED_CELLS):]
    assert set(further) <= {w["name"] for w in m["workloads"]} - set(ACCEPTED_CELLS)
    assert len(set(further)) == len(further)
    return entry


def check_all(root):
    """Every check above that is not about one accepted metric by name."""
    m = manifest(root)
    check_top_level(root)
    check_names(root)
    for metric in all_metrics(root):
        check_metric_entry(root, metric)
    for config in m["configs"]:
        check_config_entry_and_file(root, config)
    for cell in m["workloads"]:
        check_workload_resolves_to_files(root, cell)
    check_at_most_half_take_four_chips(root)
    check_every_layer_metric_file_has_an_entry(root)
    check_files_are_named_from_name_characters(root)
    check_accepted_cells_come_first(root)
