"""benchmark/run.py end to end at a size a test run can hold: it refuses to
print a result without a TPU; with its look for a chip stubbed (here only)
the last line has the contract's keys; a metric, a mix and a cell are added
by files alone; and with the timed path broken underneath, `correct` comes
out false.

The fixtures live here and not in a conftest.py: a second module named
`conftest` would shadow tests/conftest.py for `from conftest import ...`."""

import json
import os
import shutil

import pytest

import manifest_checks as checks
from benchmark import control, run
from benchmark.lib import faults
from benchmark.lib.plan import plan_of

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_root(dst: str, big_objects: int = 2, hash_backend: str = "xla",
              files: int = 6) -> str:
    """BENCHMARK.json + benchmark/ copied, juicefs_tpu/ linked, every
    configuration cut to `big_objects` (37 blocks at 2) and, where its volume
    has small files, to `files` of them (43 blocks at 2 and 6), and pointed
    at the hash backend that runs on whatever JAX found."""
    os.makedirs(dst, exist_ok=True)
    os.symlink(os.path.join(REPO, "juicefs_tpu"), os.path.join(dst, "juicefs_tpu"))
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    cfg_dir = os.path.join(dst, "benchmark", "configs")
    for name in os.listdir(cfg_dir):
        path = os.path.join(cfg_dir, name)
        with open(path) as f:
            cfg = json.load(f)
        cfg["volume"]["big_objects"] = big_objects
        if "files" in cfg["volume"]:
            cfg["volume"]["files"] = files
        cfg["deployment"]["hash_backend"] = hash_backend
        with open(path, "w") as f:
            json.dump(cfg, f)
    return dst


@pytest.fixture(autouse=True)
def process_as_new(monkeypatch):
    """A benchmark run is a new process; a test worker is not. The check
    holds each op's device report to a plane that never degraded, so what
    earlier tests of this worker left in the program's process-wide plane
    and its degrade count is put aside."""
    from juicefs_tpu.tpu import sharding

    sharding._reset_plane_for_tests()
    monkeypatch.setattr(sharding._DEGRADED, "value", 0.0)
    yield
    sharding._reset_plane_for_tests()


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(str(tmp_path / "root"))


@pytest.fixture
def any_device():
    """Stands in for run.require_tpu: whatever devices JAX has, all of them
    (the program's ShardPlane spans them all, so the count has to match)."""
    def check(chips):
        import jax

        return run.device_info(jax.devices())
    return check


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]) if out else None


def over_limit(line):
    return {k: c["value"] for k, c in line["compared"].items()
            if c["value"] > c["limit"]}


def manifest_of(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def blocks_of(root, config):
    """Blocks of a root's configuration: every seed plans the same sizes."""
    body = run.read_json(os.path.join(root, "benchmark", "configs", config + ".json"))
    return len(plan_of(0, body["volume"]).blocks)


def argv(workload, trace=0, seconds=0.5, seed=2**31 + 11):
    return ["--workload", workload, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]


def test_without_a_tpu_it_refuses_and_prints_no_result(tiny_root, capsys):
    assert run.main(argv("scan-cold"), root=tiny_root) == 1
    captured = capsys.readouterr()
    assert captured.out == "" and "refused" in captured.err
    assert os.listdir(os.path.join(tiny_root, ".bench_work")) == []


def test_without_the_program_beside_it_it_refuses(tmp_path, capsys):
    assert run.main(argv("scan-cold"), root=str(tmp_path)) == 1
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("workload", ["scan-cold", "scan-incr", "scan-cold-x4",
                                      "scan-cold-bench-mix"])
def test_untraced_run_prints_the_contracts_line(tiny_root, any_device, capsys, workload):
    assert run.main(argv(workload), root=tiny_root, device_check=any_device) == 0
    line = last_line(capsys)
    assert list(line)[:5] == RESULT_KEYS and list(line)[-1] == "compared"
    assert over_limit(line) == {} and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    cell_metrics = {m["name"] for m in manifest_of(tiny_root)["end_to_end"]}
    assert set(line["metrics"]) == cell_metrics
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())


def test_traced_run_reports_per_layer_metrics_and_a_breakdown(
        tiny_root, any_device, capsys):
    assert run.main(argv("scan-incr", trace=1), root=tiny_root,
                    device_check=any_device) == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert list(line)[:5] == RESULT_KEYS and list(line)[-1] == "compared"
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # what lists the cell; no device plane off the chip: the device's
    # metrics are left out, not 0
    assert set(line["metrics"]) == (checks.listing(tiny_root, "scan-incr")
                                    - checks.DEVICE_METRICS)
    assert line["metrics"]["tpu.compiles_in_window"]["value"] == 0
    assert line["metrics"]["tpu.pack_ms_per_batch"]["value"] > 0
    # every op lists chunks/ once: the volume's block objects, exactly
    assert line["metrics"]["object.list_objects_per_op"]["value"] == blocks_of(
        tiny_root, "scan-sqlite-file-4m")
    assert over_limit(line) == {} and line["correct"] is True
    # the numbers compared are the last lines of stderr, each beside its limit
    tail = captured.err.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") and "(limit 0)" in t for t in tail)


def test_a_cell_a_mix_and_a_metric_come_as_files_alone(tiny_root, any_device, capsys):
    """What a later PR does: add files and BENCHMARK.json entries, edit none."""
    bench = os.path.join(tiny_root, "benchmark")
    with open(os.path.join(bench, "traffic", "incr-2.json"), "w") as f:
        json.dump({"driver": "scan", "forget": 2}, f)
    with open(os.path.join(bench, "readers", "op_count.py"), "w") as f:
        f.write("def read(ctx, scale=1.0):\n    return len(ctx['ops']) * scale\n")
    with open(os.path.join(bench, "layer_metrics", "entry.ops.json"), "w") as f:
        json.dump({"reader": "op_count", "args": {"scale": 1.0}}, f)
    m = manifest_of(tiny_root)
    m["workloads"].append({"name": "scan-incr-2", "config": "scan-sqlite-file-4m",
                           "traffic": "incr-2", "chips": 1, "why": "two new blocks"})
    m["per_layer"].append({"name": "entry.ops", "unit": "count", "better": "higher",
                           "source": "program_counter", "layer": "entry (cmd/)",
                           "moves": "scan_gibs", "workloads": ["scan-incr-2"]})
    for metric in m["per_layer"][:3]:
        metric["workloads"].append("scan-incr-2")
    with open(os.path.join(tiny_root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    assert run.main(argv("scan-incr-2", trace=1), root=tiny_root,
                    device_check=any_device) == 0
    line = last_line(capsys)
    assert over_limit(line) == {} and line["correct"] is True
    assert line["metrics"]["entry.ops"]["value"] == line["attempted"]
    assert set(line["metrics"]) == {"entry.ops"} | {
        x["name"] for x in m["per_layer"][:3]}


@pytest.mark.parametrize("workload", ["scan-cold", "scan-incr", "scan-cold-x4"])
def test_the_control_and_every_fault_come_out_not_correct(
        tiny_root, any_device, workload):
    """One volume, short windows of the cell's mix: sound, the control (the
    host hash in the device's place), then each fault planted under the
    timed path. On the 8 virtual devices of the test run every cell rides
    the mesh, so the exchange can be left out of each."""
    r = run.resolve(tiny_root, workload)
    r["cell"] = dict(r["cell"], chips=4)  # offers exchange_left_out
    failed = control.one_seed(r, 2**31 + 29, 0.3, any_device,
                              lambda msg: None, root=tiny_root)
    assert set(failed) == {"sound", control.CONTROL} | set(faults.FAULTS)
    assert failed.pop("sound") == {}
    assert all(failed.values()), failed
    assert set(failed[control.CONTROL]) == {"device_reports_wrong", "h2d_bytes_short"}
    assert "digests_wrong" in failed["digest_altered"]
    assert "digests_wrong" in failed["exchange_left_out"]
    assert "index_rows_wrong" in failed["rows_not_committed"]
    assert "op_counts_wrong" in failed["half_left_out"]


def test_a_fault_under_a_whole_run_makes_the_line_say_not_correct(
        tiny_root, any_device, capsys):
    with faults.plant("digest_altered"):
        try:
            rc = run.main(argv("scan-incr"), root=tiny_root, device_check=any_device)
        except RuntimeError:
            return  # the warm-up op already failed: no result at all
    line = last_line(capsys)
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["digests_wrong"]["value"] > 0
