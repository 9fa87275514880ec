"""The volume that upstream's own benchmark writes (PR 31): the configuration
`scan-sqlite-file-bench-mix` — one default `juicefs bench` run's data set, a
1 GiB big file and 100 small files of 128 KiB — its cell
`scan-cold-bench-mix` on the accepted mix `cold` and driver `scan`, and the
per-layer metric that came with it, `tpu.blocks_per_batch`.

`lib/plan.py` plans small files where a volume states them, and the plans of
the accepted configurations are what they were, draw for draw; the plan gives
every seed the sizes the configuration states and imports nothing of the
program; the cell resolves from the repo's manifest to one new file; cut to a
few objects it runs traced on the CPU to a `correct` line with every per-layer
metric that is not the device's; and with a fault planted, or the host hash
in the device's place, it comes out not correct. How the program batches the
volume is not held here (a later PR changes it): the metric is held to what
it means."""

import hashlib
import os

import numpy as np
import pytest

import manifest_checks as checks
from benchmark import control, run
from benchmark.lib import jth256_spec, plan
from test_benchmark_grows import new_root
from test_benchmark_program_spans import reader, spec_of
from test_benchmark_run import (  # noqa: F401 (fixtures)
    any_device, argv, last_line, over_limit, process_as_new)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG, MIX, CELL = "scan-sqlite-file-bench-mix", "cold", "scan-cold-bench-mix"
METRIC = "tpu.blocks_per_batch"
FILE, FULL = 131_072, 4 << 20
FILES = 6  # make_root's cut: 2 x 16 + 6 + 5 = 43 blocks
COMPARED = {"ops_failed", "op_counts_wrong", "duplicate_counts_wrong",
            "device_reports_wrong", "digests_wrong", "index_rows_wrong",
            "h2d_bytes_short"}


def config_body(root=REPO, name=CONFIG):
    return run.read_json(os.path.join(root, "benchmark", "configs", name + ".json"))


def shape(p):
    return [(o.name, [b.size for b in o.blocks]) for o in p.objects]


@pytest.fixture
def small_root(tmp_path):
    """A copy of the benchmark with every volume cut to a test's size."""
    root = new_root(tmp_path)
    assert config_body(root)["volume"]["files"] == FILES
    return root


# -- the accepted plans ------------------------------------------------------

def make_plan_as_accepted(seed, big_objects, *, block, object_blocks,
                          pool_blocks, dup_probability, ragged_sizes):
    """`lib/plan.py:make_plan` as PR 24 to PR 29 had it, before it planned
    files: the reference the generalised one is held to."""
    rng = np.random.default_rng([seed, 0])
    objects = []
    for o in range(big_objects):
        blocks = []
        for b in range(object_blocks):
            if rng.random() < dup_probability:
                content = ("pool", int(rng.integers(pool_blocks)))
            else:
                content = ("fresh", o, b)
            blocks.append(plan.PlannedBlock(content, block))
        objects.append(plan.PlannedObject(f"big-{o:04d}", tuple(blocks)))
    for k, size in enumerate(ragged_sizes):
        o = big_objects + k
        sizes = [block] * (size // block) + ([size % block] if size % block else [])
        objects.append(plan.PlannedObject(
            f"ragged-{size}",
            tuple(plan.PlannedBlock(("fresh", o, b), s) for b, s in enumerate(sizes))))
    return plan.Plan(seed, tuple(objects))


# sha256 of the plan's names, content ids and sizes, and of one block's
# bytes, as the parent of PR 31 gave them (fe55c86, my CPU run)
AS_ACCEPTED = {0: ("5812497633874b59", 135, "d063863e3fa44e71"),
               31: ("d8d2910927c473f6", 145, "0db20506074a3757"),
               2**31 + 31: ("30a393ed89f6a508", 159, "b4f538cd5fdcf50b")}


@pytest.mark.parametrize("accepted", ["scan-sqlite-file-4m", "scan-sqlite-file-4m-x4"])
@pytest.mark.parametrize("seed", sorted(AS_ACCEPTED))
def test_the_accepted_configurations_plans_are_what_they_were(accepted, seed):
    volume = config_body(name=accepted)["volume"]
    assert not {"files", "file_bytes", "file_pool_blocks"} & set(volume)
    p = plan.plan_of(seed, volume)
    assert p == make_plan_as_accepted(
        seed, volume["big_objects"], block=volume["block_bytes"],
        object_blocks=volume["object_blocks"], pool_blocks=volume["pool_blocks"],
        dup_probability=volume["dup_probability"],
        ragged_sizes=tuple(volume["ragged_sizes"]))
    digest, duplicates, bytes_digest = AS_ACCEPTED[seed]
    listing = [(o.name, [(b.content, b.size) for b in o.blocks]) for o in p.objects]
    assert hashlib.sha256(repr(listing).encode()).hexdigest()[:16] == digest
    assert p.expected_duplicates == duplicates
    assert hashlib.sha256(plan.block_bytes(seed, p.objects[-3].blocks[0])
                          ).hexdigest()[:16] == bytes_digest
    # the files come after the full blocks' draws: up to them the new
    # configuration's plan of as many objects is the same plan
    with_files = plan.plan_of(seed, dict(volume, files=3, file_bytes=FILE,
                                         file_pool_blocks=4))
    assert with_files.objects[:volume["big_objects"]] == p.objects[:volume["big_objects"]]


# -- the plan ----------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 31, 2**31 + 31])
def test_the_plan_has_the_sizes_the_configuration_states(seed):
    body = config_body()
    p = plan.plan_of(seed, body["volume"])
    sizes = [b.size for b in p.blocks]
    assert len(p.objects) == 16 + 100 + 4 == 120
    assert len(sizes) == body["volume_blocks"] == 361
    assert p.nbytes == 1_095_337_640
    # one default `juicefs bench` run: a big file of 1024 MiB in 64 MiB
    # chunks, then 100 small files of 128 KiB
    big = [o for o in p.objects if o.name.startswith("big-")]
    assert p.objects[:16] == tuple(big) and sum(o.size for o in big) == 1024 << 20
    assert all([b.size for b in o.blocks] == [FULL] * 16 for o in big)
    files = [o for o in p.objects if o.name.startswith("file-")]
    assert p.objects[16:116] == tuple(files) and len(files) == 100
    # each file is one object of one block of its own size
    assert all(len(o.blocks) == 1 and o.size == FILE for o in files)
    assert round(100 * len(files) / len(sizes), 1) == 27.7
    assert round(100 * len(files) * FILE / p.nbytes, 1) == 1.2
    assert len({o.name for o in p.objects}) == len(p.objects)
    assert [o.size for o in p.objects[-4:]] == body["volume"]["ragged_sizes"]
    # 103 of the 361 blocks are shorter than a whole block's 64 lanes
    assert sum(1 for s in sizes if jth256_spec.lanes_of(s) < 64) == 103


def test_two_seeds_differ_only_in_which_blocks_repeat_and_in_bytes():
    volume = config_body()["volume"]
    a, b = (plan.plan_of(seed, volume) for seed in (1, 2**31 + 7))
    assert shape(a) == shape(b)
    assert [x.content for x in a.blocks] != [x.content for x in b.blocks]
    assert a.expected_duplicates > 0 and b.expected_duplicates > 0
    fresh = next(x for x, y in zip(a.blocks, b.blocks)
                 if x.content == y.content and x.content[0] == "fresh")
    assert plan.block_bytes(1, fresh) != plan.block_bytes(2**31 + 7, fresh)
    again = plan.plan_of(1, volume)
    assert [x.content for x in again.blocks] == [x.content for x in a.blocks]
    assert plan.block_bytes(1, fresh) == plan.block_bytes(1, fresh)


def test_a_files_duplicate_comes_from_the_files_pool_and_has_its_size():
    volume = dict(config_body()["volume"], files=200)
    p = plan.plan_of(31, volume)
    pools = {}
    for b in p.blocks:
        if b.content[0] == "pool":
            pools.setdefault(b.content[:-1], set()).add((b.content[-1], b.size))
    # a pool for the whole blocks, another for the files; 4 contents each
    assert set(pools) == {("pool",), ("pool", FILE)}
    assert {size for _, size in pools[("pool",)]} == {FULL}
    assert {size for _, size in pools[("pool", FILE)]} == {FILE}
    assert all({i for i, _ in members} == {0, 1, 2, 3}
               for members in pools.values())
    # a duplicate has the bytes of what it repeats, whole; the pools differ
    by_content = {b.content: b for b in p.blocks}
    big, small = by_content[("pool", 0)], by_content[("pool", FILE, 0)]
    assert len(plan.block_bytes(31, small)) == FILE
    assert plan.block_bytes(31, small) != plan.block_bytes(31, big)[:FILE]
    # no content id stands for two sizes, so the check's reference (one
    # digest a content) is sound
    assert len({b.content for b in p.blocks}) == len(
        {(b.content, b.size) for b in p.blocks})
    # about 0.3 of each kind repeat a pool entry
    files = [b for b in p.blocks if b.size == FILE]
    assert 0.2 < sum(b.content[0] == "pool" for b in files) / len(files) < 0.4
    for wrong in ({"file_bytes": FULL + 1}, {"file_bytes": 0},
                  {"file_pool_blocks": 0}):
        with pytest.raises(ValueError):
            plan.plan_of(31, dict(volume, **wrong))


def test_the_plan_imports_nothing_of_the_program():
    with open(plan.__file__) as f:
        code = f.read().split('"""', 2)[2]
    assert "juicefs_tpu" not in code
    assert [line for line in code.splitlines() if line.startswith(("import ", "from "))
            ] == ["from __future__ import annotations", "import dataclasses",
                  "import numpy as np"]


# -- the manifest ------------------------------------------------------------

def test_the_cell_resolves_from_the_repos_manifest_to_its_files():
    r = run.resolve(REPO, CELL)
    assert (r["cell"]["config"], r["cell"]["traffic"], r["cell"]["chips"]) == (
        CONFIG, MIX, 1)
    bench = checks.bench_dir(REPO)
    for rel in (f"configs/{CONFIG}.json", f"traffic/{MIX}.json",
                "drivers/scan.py", f"layer_metrics/{METRIC}.json"):
        assert os.path.isfile(os.path.join(bench, rel)), rel
    assert r["config"] == config_body()
    # the mix and the driver are the accepted cold scan's own
    assert r["traffic"] == run.resolve(REPO, "scan-cold")["traffic"]
    assert (r["traffic"]["driver"], r["traffic"]["forget"]) == ("scan", "all")
    assert [e["name"] for e in r["end_to_end"]] == [
        e["name"] for e in checks.manifest(REPO)["end_to_end"]]
    # the deployment and the guarantees are scan-sqlite-file-4m's own
    like = config_body(name="scan-sqlite-file-4m")
    assert r["config"]["deployment"] == like["deployment"]  # key for key
    assert r["config"]["guarantees"] == like["guarantees"]  # word for word
    assert list(r["config"]["reduced"]) == ["volume_blocks"]
    assert {k: r["config"]["volume"][k] for k in like["volume"]} == dict(
        like["volume"], big_objects=16)
    # the source's own defaults, in `source` and in the volume
    volume = r["config"]["volume"]
    for part in ("--big-file-size 1024 MiB", "--small-file-size 128 KiB",
                 "--small-file-count 100", "-p 1"):
        assert part in r["config"]["source"], part
    assert volume["big_objects"] * volume["object_blocks"] * volume["block_bytes"] \
        == 1024 << 20
    assert (volume["files"], volume["file_bytes"]) == (100, 128 << 10)


def test_the_metric_lists_the_accepted_cells_and_reads_the_programs_histogram():
    entry = checks.check_accepted_metric_lists_its_cells(REPO, METRIC)
    assert entry == {
        "name": METRIC, "unit": "blocks", "better": "higher",
        "source": "program_counter", "moves": "scan_gibs",
        "layer": "tpu: pack+H2D+enqueue, D2H (tpu/pipeline.py)",
        "workloads": entry["workloads"]}
    # a layer the accepted benchmark already names, under that name
    assert entry["layer"] in {e["layer"] for e in checks.manifest(REPO)["per_layer"]
                              if e["name"] != METRIC}
    spec = spec_of(METRIC)
    assert spec["reader"] == "registry" and spec["args"] == {
        "kind": "histogram_mean", "series": "juicefs_tpu_batch_blocks"}
    # the series is today's program's own
    from juicefs_tpu.tpu import pipeline  # noqa: F401 (registers it)
    assert any(k.startswith("juicefs_tpu_batch_blocks_count")
               for k in run.registry_snapshot())


def test_the_metrics_reader_gives_the_mean_and_nothing_where_nothing_was_hashed():
    args = spec_of(METRIC)["args"]
    ctx = {"registry_before": {}, "work": {},
           "registry_after": {"juicefs_tpu_h2d_bytes": 9.0}}
    assert reader("registry").read(ctx, **args) is None  # no such series
    ctx["registry_after"].update({args["series"] + "_sum": 361.0,
                                  args["series"] + "_count": 12.0})
    assert reader("registry").read(ctx, **args) == pytest.approx(361 / 12)
    ctx["registry_before"] = dict(ctx["registry_after"])  # a window of no batch
    assert reader("registry").read(ctx, **args) is None


# -- the cell, on the CPU ------------------------------------------------------

def test_the_cell_runs_traced_to_a_correct_line_with_every_host_metric(
        small_root, any_device, capsys):
    assert run.main(argv(CELL, trace=1), root=small_root,
                    device_check=any_device) == 0
    line = last_line(capsys)
    assert over_limit(line) == {} and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    listing = checks.listing(small_root, CELL)
    assert METRIC in listing
    assert set(line["metrics"]) == listing - checks.DEVICE_METRICS
    assert set(line["compared"]) == COMPARED
    assert all(c["limit"] == 0 for c in line["compared"].values())
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # a batch holds a block at the least and the op's 43 at the most
    assert line["metrics"][METRIC]["unit"] == "blocks"
    assert 1 <= m[METRIC] <= 2 * 16 + FILES + 5
    # every short block ships a whole block's slot or less: padding, not loss
    assert m["tpu.h2d_bytes_per_user_byte"] >= 1
    assert m["tpu.compiles_in_window"] == 0
    # every op lists chunks/ once: the volume's block objects, exactly
    assert m["object.list_objects_per_op"] == 2 * 16 + FILES + 5
    assert set(line["end_to_end_while_traced"]) == {
        "scan_gibs", "op_p50_ms", "setup_s"}
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("window,fails", [
    (control.CONTROL, {"device_reports_wrong", "h2d_bytes_short"}),
    ("digest_altered", {"digests_wrong"}),
    ("half_left_out", {"op_counts_wrong"}),
])
def test_with_the_control_or_a_fault_the_cell_comes_out_not_correct(
        small_root, any_device, window, fails):
    r = run.resolve(small_root, CELL)
    failed = control.one_seed(
        r, 2**31 + 31, 0.3, any_device, lambda msg: None, root=small_root,
        only=["sound", window])
    assert set(failed) == {"sound", window}
    assert failed["sound"] == {}
    assert fails <= set(failed[window]) <= COMPARED
