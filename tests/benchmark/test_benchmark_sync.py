"""The mirror check's cell (PR 38): configuration `sync-file-file-bench-mix`
— BASELINE's `juicefs sync` content-hash compare, over the bucket of one
default `juicefs bench` run and its mirror — its mix `check-all` on the driver
`sync`, and the cell `sync-check-all`.

The cell resolves from the repo's manifest to new files alone, over the plan
`scan-cold-bench-mix` runs; what was accepted is entry for entry what it was;
cut to one big object and six files it runs on the CPU through `run.main` to
a `correct` line, traced to every per-layer metric that lists it and is not
the device's; and with the host hash in the device's place, a digest altered,
a store that answers a pass from memory, a program that skips half the pairs
or one whose `sync` cannot take the entry, it comes out not correct or not at
all. The plain byte compare of two trees is held to trees made to differ."""

import hashlib
import json
import os

import pytest

import manifest_checks as checks
from benchmark import control, run
from benchmark.lib import mirror, pair_compare, plan
from test_benchmark_grows import manifest_root  # noqa: F401 (fixture)
from test_benchmark_run import (  # noqa: F401 (fixtures)
    any_device, argv, last_line, make_root, over_limit, process_as_new)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG, MIX, CELL = "sync-file-file-bench-mix", "check-all", "sync-check-all"
LIKE = "scan-sqlite-file-bench-mix"
COMPARED = {"ops_failed", "op_counts_wrong", "device_reports_wrong",
            "digests_wrong", "pairs_wrong", "h2d_bytes_short",
            "mismatch_missed"}
# the per-layer metrics of the parent's manifest (all 31) that do not list
# the cell: `gc`'s and `fsck`'s own spans and rows, what times `gc` from
# outside, and `gc`'s fault counter
PARENT_METRICS = 31
PARENT_CELLS = checks.ACCEPTED_CELLS + ["fsck-verify-redis"]
NOT_THE_PASSES = {"entry.listing_ms_per_op", "meta.index_load_ms_per_op",
                  "meta.backfill_ms_per_op", "tpu.pack_ms_per_batch",
                  "entry.open_ms_per_op", "entry.list_ms_per_op",
                  "entry.reconcile_ms_per_op", "entry.scan_faults_per_block"}
# the pass's own stages, each an entry that lists the cell first
PASS_STAGES = {"entry.sync_open_ms_per_op": "open", "entry.sync_list_ms_per_op": "list",
               "entry.sync_check_ms_per_op": "check",
               "entry.sync_report_ms_per_op": "report"}
PAIRS = 16 + 6 + 5  # make_root's cut to one big object and six files


def config_body(root=REPO, name=CONFIG):
    return run.read_json(os.path.join(root, "benchmark", "configs", name + ".json"))


def builders_of(root):
    """Children of a run whose command line names `root`."""
    found = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmdline = f.read().decode(errors="replace")
        except OSError:
            continue
        if root in cmdline and int(pid) != os.getpid():
            found.append(cmdline)
    return found


@pytest.fixture
def small_root(tmp_path):
    """A copy of the benchmark with every volume cut to one big object and
    six files, the pass on the XLA program on whatever JAX found."""
    root = make_root(str(tmp_path / "root"), big_objects=1)
    yield root
    assert builders_of(root) == []
    assert os.listdir(os.path.join(root, ".bench_work")) == []


# -- the manifest ------------------------------------------------------------

def test_the_cell_resolves_from_the_repos_manifest_to_new_files():
    r = run.resolve(REPO, CELL)
    assert (r["cell"]["config"], r["cell"]["traffic"], r["cell"]["chips"]) == (
        CONFIG, MIX, 1)
    bench = checks.bench_dir(REPO)
    for rel in (f"configs/{CONFIG}.json", f"traffic/{MIX}.json",
                "drivers/sync.py", "lib/mirror.py", "lib/pair_compare.py"):
        assert os.path.isfile(os.path.join(bench, rel)), rel
    assert r["config"] == config_body()
    assert (r["traffic"]["driver"], r["traffic"]["check"]) == ("sync", "all")
    assert [e["name"] for e in r["end_to_end"]] == [
        e["name"] for e in checks.manifest(REPO)["end_to_end"]]
    entry, = [c for c in checks.manifest(REPO)["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["volume_blocks"] == list(r["config"]["reduced"])
    for part in ("BASELINE.json configs[3]", "`juicefs sync`", "content-hash",
                 "--check-all", "--check-new", "--threads 10", "`juicefs bench`"):
        assert part in entry["source"], part
    dep = r["config"]["deployment"]
    assert (dep["src"], dep["dst"], dep["meta"], dep["block_bytes"],
            dep["hash_backend"], dep["threads"]) == (
            "file", "file", None, 4 << 20, "tpu", 10)
    assert dep["entry"] == ("sync <src> <dst> --check-all --hash-backend tpu "
                            "--threads 10 --hash-index <file>")
    assert r["config"]["architecture"] is None  # no model: a deployment
    assert {"bucket", "stores", "mode", "page_cache"} <= set(r["config"]["assumed"])
    assert len(r["config"]["guarantees"]) == 5


@pytest.mark.parametrize("seed", [0, 38, 2**31 + 38])
def test_the_volume_is_the_bench_mix_cells_own(seed):
    like = config_body(name=LIKE)
    body = config_body()
    assert body["volume"] == like["volume"]  # key for key
    assert body["volume_blocks"] == like["volume_blocks"] == 361
    p = plan.plan_of(seed, body["volume"])
    assert p == plan.plan_of(seed, like["volume"])
    assert len(p.blocks) == 361 and p.nbytes == 1_095_337_640
    # every block an object of at most one range: 722 GETs a pass, 22
    # batches of 32 and a tail of 18
    assert max(b.size for b in p.blocks) == body["deployment"]["block_bytes"]
    assert divmod(2 * len(p.blocks), 32) == (22, 18)


def test_the_cell_is_appended_to_what_can_read_a_pass(manifest_root):
    """Of the entries the parent's manifest had: the cell comes straight
    after the parent's cells where the metric reads a pass under its own
    name, and is not listed elsewhere; no entry came or went with it. The
    pass's own stages have entries of their own that list it first."""
    checks.check_all(manifest_root)
    m = checks.manifest(manifest_root)
    n = len(PARENT_CELLS)
    assert [w["name"] for w in m["workloads"][:n + 1]] == PARENT_CELLS + [CELL]
    was = m["per_layer"][:PARENT_METRICS]
    assert NOT_THE_PASSES <= {e["name"] for e in was}
    listing = []
    for entry in was:
        checks.check_accepted_metric_lists_its_cells(manifest_root, entry["name"])
        if entry["name"] in NOT_THE_PASSES:
            assert CELL not in entry["workloads"], entry["name"]
        else:
            before = [c for c in PARENT_CELLS if c in entry["workloads"]]
            assert entry["workloads"][:len(before) + 1] == before + [CELL]
            listing.append(entry["name"])
    assert len(listing) == 23
    # the cell runs the XLA program: the kernel's two lines read it
    assert {"kernel.hash_ms_per_batch", "jth256_roofline"} <= set(listing)
    for name in PASS_STAGES:
        entry, = [e for e in m["per_layer"] if e["name"] == name]
        assert entry["workloads"][:1] == [CELL] and entry["moves"] == "op_p50_ms"


def test_what_was_accepted_is_entry_for_entry_what_it_was(manifest_root):
    """Everything of the manifest that PR 37 left, each list cut back to the
    parent's cells: sha256 as the parent of PR 38 gives it (ae3265b)."""
    m = checks.manifest(manifest_root)
    was = {"command": m["command"], "paths": m["paths"],
           "run_seconds": m["run_seconds"], "configs": m["configs"][:4],
           "workloads": m["workloads"][:len(PARENT_CELLS)],
           "end_to_end": m["end_to_end"],
           "per_layer": [dict(e, workloads=[c for c in e["workloads"]
                                            if c in PARENT_CELLS])
                         for e in m["per_layer"][:PARENT_METRICS]]}
    digest = hashlib.sha256(json.dumps(was, sort_keys=True).encode()).hexdigest()
    assert digest[:16] == "91a4a5419b8c748a"


# -- the plain reference -------------------------------------------------------

def test_pair_compare_tells_equal_differing_and_missing_files(tmp_path):
    src, dst = tmp_path / "s", tmp_path / "d"
    for root in (src, dst):
        (root / "a" / "b").mkdir(parents=True)
        (root / "a" / "b" / "same").write_bytes(b"x" * 3_000_000)
        (root / "empty").write_bytes(b"")
    (src / "a" / "flipped").write_bytes(b"y" * 2_000_000 + b"0")
    (dst / "a" / "flipped").write_bytes(b"y" * 2_000_000 + b"1")
    (src / "longer").write_bytes(b"zz")
    (dst / "longer").write_bytes(b"z")
    (src / "left").write_bytes(b"l")
    (dst / "right").write_bytes(b"r")
    assert pair_compare.compare(str(src), str(dst)) == {
        "a/b/same": "equal", "empty": "equal", "a/flipped": "differ",
        "longer": "differ", "left": "only_src", "right": "only_dst"}
    with open(pair_compare.__file__) as f:
        assert "juicefs_tpu" not in f.read()  # imports nothing of the program


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
    # both sides of every pair, every pass: 54 blocks, a batch of 32 and a
    # tail of 22
    assert m["tpu.blocks_per_batch"] == 2 * PAIRS / 2
    assert m["tpu.h2d_ms_per_batch"] > 0 and m["tpu.enqueue_ms_per_batch"] > 0
    assert 0 <= m["tpu.pack_unready_share"] <= m["tpu.pack_fresh_share"]
    assert m["tpu.h2d_bytes_per_user_byte"] >= 1.0
    assert m["object.get_ms"] > 0 and m["chunk.get_wall_ms_per_op"] > 0
    assert 0 <= m["chunk.fetch_ready_share"] <= 100
    # each stage once a pass, the pair stage the longest of them
    stages = {stage: m[name] for name, stage in PASS_STAGES.items()}
    assert min(stages.values()) > 0 and max(stages, key=stages.get) == "check"
    # both stores listed once a pass: every pair's two objects, exactly
    assert m["object.list_objects_per_op"] == 2 * PAIRS
    assert NOT_THE_PASSES.isdisjoint(m)
    assert set(line["end_to_end_while_traced"]) == {
        "scan_gibs", "op_p50_ms", "setup_s"}


def test_scan_gibs_counts_each_pair_once(small_root, any_device, capsys):
    """The operator's bucket verified per second: the source's bytes of a
    pass, not the bytes hashed (twice that)."""
    assert run.main(argv(CELL), root=small_root, device_check=any_device) == 0
    line = last_line(capsys)
    nbytes = plan.plan_of(0, config_body(small_root)["volume"]).nbytes
    ops = line["attempted"] - line["failed"]
    assert line["metrics"]["scan_gibs"]["value"] == pytest.approx(
        ops * nbytes / 2**30 / line["window_s"])


@pytest.mark.parametrize("window,fails", [
    (control.CONTROL, {"device_reports_wrong", "h2d_bytes_short"}),
    ("digest_altered", {"digests_wrong", "pairs_wrong", "ops_failed",
                        "op_counts_wrong"}),
])
def test_with_the_control_or_a_fault_the_cell_comes_out_not_correct(
        small_root, any_device, window, fails):
    r = run.resolve(small_root, CELL)
    failed = control.one_seed(
        r, 2**31 + 38, 0.3, any_device, lambda msg: None, root=small_root,
        only=["sound", window])
    assert set(failed) == {"sound", window}
    assert failed["sound"] == {}
    assert fails <= set(failed[window]) <= COMPARED


def test_a_pass_answered_from_memory_misses_the_planted_byte(
        small_root, any_device, capsys, monkeypatch):
    """The flip is really made, in the mirror: a store that remembers what
    it read answers the last pass with the bytes as they were, and the line
    says so. Only a program that reports it passes `mismatch_missed`."""
    from juicefs_tpu.object.file import FileStorage

    seen = {}
    get = FileStorage.get

    def remembered(self, key, off=0, limit=-1):
        at = (self.root, key, off, limit)
        if at not in seen:
            seen[at] = get(self, key, off, limit)
        return seen[at]

    monkeypatch.setattr(FileStorage, "get", remembered)
    assert run.main(argv(CELL), root=small_root, device_check=any_device) == 0
    line = last_line(capsys)
    assert line["correct"] is False and over_limit(line) == {"mismatch_missed": 1}


def test_a_program_that_skips_half_the_pairs_fails_the_counts(
        small_root, any_device, capsys, monkeypatch):
    from juicefs_tpu.cmd import sync

    diff = sync._diff

    def every_other(src_iter, dst_iter, args):
        for i, task in enumerate(diff(src_iter, dst_iter, args)):
            if i % 2 == 0:
                yield task

    monkeypatch.setattr(sync, "_diff", every_other)
    assert run.main(argv(CELL), root=small_root, device_check=any_device) == 0
    line = last_line(capsys)
    assert line["correct"] is False and line["failed"] == 0
    wrong = over_limit(line)
    ops = line["attempted"]
    assert wrong["op_counts_wrong"] == ops
    # what was left out has no digest on either side, and no verdict
    assert wrong["pairs_wrong"] == ops * (PAIRS // 2)
    assert wrong["digests_wrong"] == 2 * wrong["pairs_wrong"]
    assert set(wrong) == {"op_counts_wrong", "pairs_wrong", "digests_wrong",
                          "h2d_bytes_short", "mismatch_missed"}


def test_a_program_whose_sync_cannot_take_the_entry_ends_the_run_at_once(
        small_root, any_device, capsys, monkeypatch):
    """The parent of PR 38: no `--hash-backend`. Exit 1, no result line,
    and nothing was started for it."""
    from juicefs_tpu.cmd import sync

    def add_parser_as_it_was(sub):
        p = sub.add_parser("sync")
        p.add_argument("src")
        p.add_argument("dst")
        p.add_argument("--threads", type=int, default=10)
        p.add_argument("--check-new", action="store_true")
        p.add_argument("--check-all", action="store_true")

    monkeypatch.setattr(sync, "add_parser", add_parser_as_it_was)
    with pytest.raises(SystemExit) as ended:
        run.main(argv(CELL), root=small_root, device_check=any_device)
    assert ended.value.code not in (0, None)
    captured = capsys.readouterr()
    assert captured.out == "" and "refused" in str(ended.value.code)
    assert not os.path.exists(os.path.join(
        mirror.endpoints(os.path.join(small_root, ".bench_work"))[0]))
