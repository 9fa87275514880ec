"""BENCHMARK.json against the contract's limits, and against the files the
harness finds by name."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def bench_dir():
    return os.path.join(REPO, manifest()["paths"][0])


def all_metrics():
    m = manifest()
    return m["end_to_end"] + m["per_layer"]


def test_top_level_keys_and_limits():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    # a full check with the full 24 cells has to fit into 43200 s
    assert (2 + 14 * 24) * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= len(m["paths"]) <= 16 and len(m["command"]) <= 32
    for p in m["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p) and os.path.isdir(
            os.path.join(REPO, p))
    assert os.path.isfile(os.path.join(REPO, m["command"][1]))
    assert any(m["command"][1].startswith(p + "/") for p in m["paths"])


@pytest.mark.parametrize("metric", all_metrics(), ids=lambda m: m["name"])
def test_metric_entry(metric):
    m = manifest()
    per_layer = metric in m["per_layer"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in m["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if per_layer:
        assert metric["moves"] in {e["name"] for e in m["end_to_end"]}
        assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%" and metric["source"] == "device_trace"
    else:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25


def test_names_are_unique_and_setup_s_is_there():
    m = manifest()
    for group in (all_metrics(), m["workloads"], m["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    assert "setup_s" in {e["name"] for e in m["end_to_end"]}


@pytest.mark.parametrize("config", manifest()["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert all(1 <= len(config[k]) <= 200 for k in ("source", "why"))
    assert any(config["file"].startswith(p + "/") for p in manifest()["paths"])
    with open(os.path.join(REPO, config["file"])) as f:
        body = json.load(f)
    assert body["name"] == config["name"] and body["source"] == config["source"]
    assert len(config["reduced"]) <= 16
    for key in config["reduced"]:
        assert NAME.match(key) and key in body["reduced"] and key in body
        assert not key.endswith(("_dim", "_rank"))
    assert body["guarantees"] and body["deployment"]["chips"] in (1, 4)
    assert config["name"] in {w["config"] for w in manifest()["workloads"]}
    files = [c["file"] for c in manifest()["configs"]]
    sources = [c["source"] for c in manifest()["configs"]]
    assert len(set(files)) == len(files) and len(set(sources)) == len(sources)


@pytest.mark.parametrize("cell", manifest()["workloads"], ids=lambda w: w["name"])
def test_workload_resolves_to_files_that_exist(cell):
    from benchmark import run

    m = manifest()
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["traffic"]) and 1 <= len(cell["why"]) <= 200
    r = run.resolve(REPO, cell["name"])
    assert r["config"]["deployment"]["chips"] == cell["chips"]
    driver = os.path.join(bench_dir(), "drivers", r["traffic"]["driver"] + ".py")
    assert os.path.isfile(driver)
    reported = {e["name"] for e in r["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2 and r["per_layer"]
    for metric in r["per_layer"]:
        spec_file = os.path.join(bench_dir(), "layer_metrics", metric["name"] + ".json")
        with open(spec_file) as f:
            spec = json.load(f)
        assert os.path.isfile(os.path.join(
            bench_dir(), "readers", spec["reader"] + ".py"))
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_at_most_half_of_the_cells_take_four_chips():
    cells = manifest()["workloads"]
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)
    assert all(w["chips"] in (1, 4) for w in cells)


def test_every_layer_metric_file_has_a_manifest_entry():
    names = {m["name"] for m in manifest()["per_layer"]}
    files = {f[:-5] for f in os.listdir(os.path.join(bench_dir(), "layer_metrics"))}
    assert files == names


def test_files_under_paths_are_named_from_name_characters():
    for p in manifest()["paths"]:
        for base, dirs, files in os.walk(os.path.join(REPO, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert re.fullmatch(r"[A-Za-z0-9_.\-]+", f), os.path.join(base, f)
