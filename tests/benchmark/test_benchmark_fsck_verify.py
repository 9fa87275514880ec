"""The scrub's cell (PR 35): configuration `fsck-redis-file-4m` — BASELINE's
`juicefs fsck` full block-hash verify over a Redis-backed volume — its mix
`verify` on the driver `fsck`, and the cell `fsck-verify-redis`.

The cell resolves from the repo's manifest to new files alone, over the plan
the accepted scan cells run; what was accepted is entry for entry what it
was; cut to one object it runs on the CPU through `run.main` — the meta
server a child, the Pallas kernel interpreted — to a `correct` line, traced
to every per-layer metric that lists it and is not the device's; and with
the host hash in the kernel's place, a digest altered, a store that answers
a scrub from memory, or a program whose `fsck` cannot take the entry, it
comes out not correct or not at all. No server outlives a run."""

import hashlib
import json
import os

import pytest

import manifest_checks as checks
from benchmark import control, run
from benchmark.lib import plan
from test_benchmark_grows import manifest_root  # noqa: F401 (fixture)
from test_benchmark_run import (  # noqa: F401 (fixtures)
    any_device, argv, last_line, make_root, over_limit, process_as_new)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG, MIX, CELL = "fsck-redis-file-4m", "verify", "fsck-verify-redis"
COMPARED = {"ops_failed", "op_counts_wrong", "device_reports_wrong",
            "digests_wrong", "index_rows_wrong", "h2d_bytes_short",
            "bitrot_missed"}
# per-layer metrics of the parent's manifest (its first 31) that do not list
# the cell: `gc`'s own spans; two that time a layer from outside and wait to
# be retired; and four that would put the scrub's reading under another
# program's or another span's name (the XLA hash's, `gc`'s index load, an op
# less `dedup_scan`); the scrub's own spans and its kernel come as entries of
# their own (test_benchmark_stage_metrics.py)
PARENT_METRICS = 31
NOT_THE_SCRUBS = {"meta.backfill_ms_per_op", "entry.open_ms_per_op",
                  "entry.list_ms_per_op", "entry.reconcile_ms_per_op",
                  "tpu.pack_ms_per_batch", "entry.scan_faults_per_block",
                  "kernel.hash_ms_per_batch", "jth256_roofline",
                  "meta.index_load_ms_per_op", "entry.listing_ms_per_op"}
BLOCKS = 16 + 5  # make_root's cut to one object: 21 blocks


def config_body(root=REPO, name=CONFIG):
    return run.read_json(os.path.join(root, "benchmark", "configs", name + ".json"))


def servers_of(root):
    """Meta servers whose append-only file lies under `root`."""
    found = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmdline = f.read().decode(errors="replace")
        except OSError:
            continue
        if "meta-server" in cmdline and root in cmdline:
            found.append(pid)
    return found


@pytest.fixture
def small_root(tmp_path):
    """A copy of the benchmark with every volume cut to one object, the
    scrub on the Pallas kernel (interpreted off the chip) and the index
    filled by the XLA program on whatever JAX found."""
    root = make_root(str(tmp_path / "root"), big_objects=1, hash_backend="pallas")
    path = os.path.join(root, "benchmark", "configs", CONFIG + ".json")
    body = config_body(root)
    body["deployment"]["index_backend"] = "xla"
    with open(path, "w") as f:
        json.dump(body, f)
    yield root
    assert servers_of(root) == []
    assert os.listdir(os.path.join(root, ".bench_work")) == []


# -- the manifest ------------------------------------------------------------

def test_the_cell_resolves_from_the_repos_manifest_to_new_files():
    r = run.resolve(REPO, CELL)
    assert (r["cell"]["config"], r["cell"]["traffic"], r["cell"]["chips"]) == (
        CONFIG, MIX, 1)
    bench = checks.bench_dir(REPO)
    for rel in (f"configs/{CONFIG}.json", f"traffic/{MIX}.json",
                "drivers/fsck.py", "lib/volume_served.py"):
        assert os.path.isfile(os.path.join(bench, rel)), rel
    assert r["config"] == config_body()
    assert (r["traffic"]["driver"], r["traffic"]["index"]) == ("fsck", "full")
    assert [e["name"] for e in r["end_to_end"]] == [
        e["name"] for e in checks.manifest(REPO)["end_to_end"]]
    entry, = [c for c in checks.manifest(REPO)["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["volume_blocks"] == list(r["config"]["reduced"])
    for part in ("BASELINE.json configs[2]", "Redis", "redis://host:6379/1",
                 "appendfsync everysec", "`juicefs fsck`"):
        assert part in entry["source"], part
    dep = r["config"]["deployment"]
    assert (dep["meta"], dep["meta_db"], dep["storage"], dep["block_bytes"],
            dep["compression"], dep["hash_backend"], dep["threads"]) == (
            "redis", 1, "file", 4 << 20, "none", "pallas", 10)
    assert dep["entry"] == ("fsck <meta> --verify-data --hash-index <file> "
                            "--hash-backend pallas --threads 10")
    assert r["config"]["architecture"] is None  # no model: a deployment
    assert {"storage", "meta_server", "index"} <= set(r["config"]["assumed"])


@pytest.mark.parametrize("seed", [0, 35, 2**31 + 35])
def test_the_volume_is_the_accepted_scan_cells_own(seed):
    like = config_body(name="scan-sqlite-file-4m")
    body = config_body()
    assert body["volume"] == like["volume"]  # key for key
    assert body["volume_blocks"] == like["volume_blocks"] == 517
    p = plan.plan_of(seed, body["volume"])
    assert p == plan.plan_of(seed, like["volume"])
    assert len(p.blocks) == 517 and p.nbytes == 2_155_972_264
    # 16 batches of 32 and a tail of 5
    assert divmod(len(p.blocks), 32) == (16, 5)


def test_the_cell_is_appended_to_what_could_read_a_scrub_before_it(manifest_root):
    """Of the entries the parent's manifest had: the cell comes straight
    after the accepted cells where the metric reads a scrub under its own
    name, and is not listed elsewhere. What a later PR appends -- an entry,
    or a cell's name behind this one -- is not this test's to hold."""
    checks.check_all(manifest_root)
    m = checks.manifest(manifest_root)
    n = len(checks.ACCEPTED_CELLS)
    assert m["workloads"][n]["name"] == CELL
    was = m["per_layer"][:PARENT_METRICS]
    assert NOT_THE_SCRUBS <= {e["name"] for e in was}
    for entry in was:
        checks.check_accepted_metric_lists_its_cells(manifest_root, entry["name"])
        if entry["name"] in NOT_THE_SCRUBS:
            assert CELL not in entry["workloads"], entry["name"]
        else:
            assert entry["workloads"][:n + 1] == checks.ACCEPTED_CELLS + [CELL]


def test_what_was_accepted_is_entry_for_entry_what_it_was(manifest_root):
    """Everything of the manifest that PR 34 left, each accepted list cut to
    the accepted cells: sha256 as the parent of PR 35 gives it (4630eb9)."""
    m = checks.manifest(manifest_root)
    n = len(checks.ACCEPTED_CELLS)
    was = {"command": m["command"], "paths": m["paths"],
           "run_seconds": m["run_seconds"], "configs": m["configs"][:3],
           "workloads": m["workloads"][:n], "end_to_end": m["end_to_end"],
           "per_layer": [dict(e, workloads=e["workloads"][:n])
                         for e in m["per_layer"][:PARENT_METRICS]]}
    digest = hashlib.sha256(json.dumps(was, sort_keys=True).encode()).hexdigest()
    assert digest[:16] == "2e2216535d6527ad"


# -- the cell, on the CPU ------------------------------------------------------

def test_the_cell_runs_to_a_correct_line(small_root, any_device, capsys):
    assert run.main(argv(CELL), root=small_root, device_check=any_device) == 0
    line = last_line(capsys)
    assert over_limit(line) == {} and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["compared"]) == COMPARED
    assert all(c["limit"] == 0 for c in line["compared"].values())
    assert set(line["metrics"]) == {"scan_gibs", "op_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_the_cell_runs_traced_with_every_host_metric_that_lists_it(
        small_root, any_device, capsys):
    assert run.main(argv(CELL, trace=1), root=small_root,
                    device_check=any_device) == 0
    line = last_line(capsys)
    assert over_limit(line) == {} and line["correct"] is True
    assert set(line["metrics"]) == (checks.listing(small_root, CELL)
                                    - checks.DEVICE_METRICS)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["tpu.compiles_in_window"] == 0
    assert m["tpu.blocks_per_batch"] == BLOCKS
    # both steps of the single-device path are timed, as the plane's are
    assert m["tpu.h2d_ms_per_batch"] > 0 and m["tpu.enqueue_ms_per_batch"] > 0
    assert 0 <= m["tpu.pack_unready_share"] <= m["tpu.pack_fresh_share"]
    # the scrub's own stages, each once an op, and its round trips
    assert m["entry.fsck_list_ms_per_op"] > 0 and m["entry.fsck_index_load_ms_per_op"] > 0
    assert m["meta.kv_roundtrip_ms"] > 0 and m["meta.kv_roundtrips_per_op"] >= 1
    # every op lists chunks/ once: the volume's block objects, exactly
    assert m["object.list_objects_per_op"] == BLOCKS
    assert NOT_THE_SCRUBS.isdisjoint(m)
    assert set(line["end_to_end_while_traced"]) == {
        "scan_gibs", "op_p50_ms", "setup_s"}


@pytest.mark.parametrize("window,fails", [
    (control.CONTROL, {"device_reports_wrong", "h2d_bytes_short"}),
    ("digest_altered", {"digests_wrong", "ops_failed", "op_counts_wrong"}),
])
def test_with_the_control_or_a_fault_the_cell_comes_out_not_correct(
        small_root, any_device, window, fails):
    r = run.resolve(small_root, CELL)
    failed = control.one_seed(
        r, 2**31 + 35, 0.3, any_device, lambda msg: None, root=small_root,
        only=["sound", window])
    assert set(failed) == {"sound", window}
    assert failed["sound"] == {}
    assert fails <= set(failed[window]) <= COMPARED


def test_a_scrub_answered_from_memory_misses_the_bitrot(
        small_root, any_device, capsys, monkeypatch):
    """The flip is really made: a store that remembers what it read answers
    the last scrub with the bytes as they were, and the line says so."""
    from juicefs_tpu.chunk.cached_store import CachedStore

    seen = {}
    load = CachedStore._load_block

    def remembered(self, key, *a, **kw):
        if key not in seen:
            seen[key] = load(self, key, *a, **kw)
        return seen[key]

    monkeypatch.setattr(CachedStore, "_load_block", remembered)
    assert run.main(argv(CELL), root=small_root, device_check=any_device) == 0
    line = last_line(capsys)
    assert line["correct"] is False and over_limit(line) == {"bitrot_missed": 1}


def test_a_program_whose_fsck_cannot_take_the_entry_ends_the_run_at_once(
        small_root, any_device, capsys, monkeypatch):
    """The parent of PR 35: no `--threads`. Exit 1, no result line, and
    nothing was started for it."""
    from juicefs_tpu.cmd import fsck

    def add_parser_as_it_was(sub):
        p = sub.add_parser("fsck")
        p.add_argument("meta_url")
        p.add_argument("--verify-data", action="store_true")
        p.add_argument("--hash-index", default="")
        p.add_argument("--hash-backend", default=None)

    monkeypatch.setattr(fsck, "add_parser", add_parser_as_it_was)
    with pytest.raises(SystemExit) as ended:
        run.main(argv(CELL), root=small_root, device_check=any_device)
    assert ended.value.code not in (0, None)
    captured = capsys.readouterr()
    assert captured.out == "" and "refused" in str(ended.value.code)
