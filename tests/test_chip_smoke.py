"""chip_smoke.py on the CPU (ISSUE 21): it must FAIL here, naming the
platform it found and printing no result; its planner — seed to objects,
the duplicate count they must produce, sample digests by the numpy spec —
is what every check on the chip is held to, so it is tested at a few
blocks."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_plan_is_seeded_and_counts_its_duplicates():
    plan = chip_smoke.make_plan(seed=5, big_objects=3)
    assert plan == chip_smoke.make_plan(seed=5, big_objects=3)
    assert plan != chip_smoke.make_plan(seed=6, big_objects=3)
    big = [o for o in plan.objects if o.name.startswith("big-")]
    assert [o.size for o in big] == [64 << 20] * 3
    assert all(len(o.blocks) == 16 for o in big)
    # the ragged handful: 1 B, 100 001 B, 4 MiB - 1, and 4 MiB + 7 (which
    # is two blocks: a full one and a 7-byte tail)
    ragged = {o.name: [b.size for b in o.blocks] for o in plan.objects
              if o.name.startswith("ragged-")}
    assert ragged == {
        "ragged-1": [1], "ragged-100001": [100_001],
        f"ragged-{chip_smoke.BLOCK - 1}": [chip_smoke.BLOCK - 1],
        f"ragged-{chip_smoke.BLOCK + 7}": [chip_smoke.BLOCK, 7],
    }
    assert len(plan.blocks) == 3 * 16 + 5
    assert plan.nbytes == sum(o.size for o in plan.objects)
    # duplicates, recounted the slow way: a block is one iff its content
    # id was seen before — only pool draws can repeat
    seen, dups = set(), 0
    for b in plan.blocks:
        dups += b.content in seen
        seen.add(b.content)
    assert plan.expected_duplicates == dups > 0
    pool_draws = [b for b in plan.blocks if b.content[0] == "pool"]
    assert dups == len(pool_draws) - len({b.content for b in pool_draws})


def test_block_bytes_follow_content_ids():
    plan = chip_smoke.make_plan(seed=5, big_objects=2)
    pool = [b for b in plan.blocks if b.content[0] == "pool"]
    same = [b for b in pool if b.content == pool[0].content]
    assert len(same) >= 2
    small = chip_smoke.PlannedBlock(same[0].content, 4096)
    assert chip_smoke.block_bytes(5, small) == chip_smoke.block_bytes(5, small)
    assert chip_smoke.block_bytes(5, small) != chip_smoke.block_bytes(6, small)
    other = chip_smoke.PlannedBlock(("fresh", 0, 0), 4096)
    assert chip_smoke.block_bytes(5, small) != chip_smoke.block_bytes(5, other)
    tail = plan.objects[-1]  # 4 MiB + 7: the object is its blocks, joined
    assert len(chip_smoke.object_bytes(5, tail)) == chip_smoke.BLOCK + 7


def test_sample_covers_ragged_and_pool_and_matches_the_numpy_spec():
    from juicefs_tpu.tpu.jth256 import jth256

    plan = chip_smoke.make_plan(seed=5, big_objects=2)
    sample = chip_smoke.sample_blocks(plan, 16)
    assert len(sample) >= 16
    assert len({b.content for b in sample}) == len(sample)
    assert {1, 7, 100_001, chip_smoke.BLOCK - 1} <= {b.size for b in sample}
    used_pool = {b.content for b in plan.blocks if b.content[0] == "pool"}
    assert used_pool <= {b.content for b in sample}
    # reference digests: the spec loaded by path (the smoke's parent may
    # not import the tpu package) equals the package's own jth256()
    few = [b for b in sample if b.size < chip_smoke.BLOCK][:3]
    ref = chip_smoke.reference_digests(5, few)
    for b in few:
        assert ref[b.content] == jth256(chip_smoke.block_bytes(5, b)).hex()


def test_compile_counts_collapse_doubled_log_records():
    line = ("Finished XLA compilation of jit(hash_packed_jax) in 0.5 sec")
    hit = ("Persistent compilation cache hit for 'jit_hash_packed_jax' "
           "with key 'k1'")
    comp = ("Compiling jit(hash_packed_jax) with global shapes and types "
            "(ShapedArray(uint32[32,64,128,128]), ShapedArray(int32[32])). "
            "Argument mapping: (UnspecifiedValue,).")
    err = "\n".join([
        "WARNING:jax: " + comp, "2026 W jax: " + comp,      # two handlers
        "WARNING:jax: " + hit, "2026 W jax: " + hit,
        "WARNING:jax: " + line, "2026 W jax: " + line,
        "WARNING:jax: " + line.replace("0.5", "0.7"),
    ])
    assert chip_smoke.compile_counts(err) == {
        "requests": 2, "cache_hits": 1, "compiled": 1,
        "hash_batch_shapes": ["uint32[32,64,128,128]"],
    }


def test_verdict_line_holds_exactly_ok_and_device():
    summary = {"ok": True, "jax": "0.9.0", "seed": 21, "steps": {"cold": {}},
               "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert chip_smoke.verdict(summary) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_chip_smoke_fails_on_cpu_and_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--workdir", str(tmp_path)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""  # no result of any kind
    assert "FAIL at step format" in p.stderr
    assert "platform 'cpu'" in p.stderr  # names what it found
    assert os.listdir(tmp_path) == []  # scratch removed, nothing left


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    with open(os.path.join(REPO, "chip_smoke.py")) as src:
        (alone / "chip_smoke.py").write_text(src.read())
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=str(alone),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "FAIL at step checkout" in p.stderr


def test_step_4_reads_fscks_output_with_the_stats_line_after_it(tmp_path, capsys):
    """`fsck --verify-data` prints its three lines as it did, word for word,
    and one JSON stats line last (ISSUE 35): step 4's regex and its
    `device: ` reader find what they found, and the line they never asked
    for is the last."""
    import json
    import re

    from juicefs_tpu.cmd import build_store, main, open_meta
    from juicefs_tpu.fs import FileSystem
    from juicefs_tpu.vfs import VFS

    meta_url = f"sqlite3://{tmp_path}/meta.db"
    assert main(["format", meta_url, "smokevol", "--storage", "file",
                 "--bucket", str(tmp_path / "blob") + "/",
                 "--block-size", "64"]) == 0
    m, fmt = open_meta(meta_url)
    m.new_session()
    store = build_store(fmt, None)
    vfs = VFS(m, store, fmt=fmt)
    with FileSystem(vfs).create("/a.bin") as f:
        f.write(os.urandom(3 * 65536 + 11))
    vfs.close()
    store.close()
    m.close_session()
    assert main(["gc", meta_url, "--dedup", "--hash-backend", "cpu"]) == 0
    capsys.readouterr()
    assert main(["fsck", meta_url, "--verify-data", "--hash-index",
                 str(tmp_path / "F.json"), "--hash-backend", "pallas"]) == 0
    out = capsys.readouterr().out
    found = re.search(r"verified (\d+) blocks \((\w+)\); (\d+) indexed, "
                      r"(\d+) digest mismatches", out)
    assert found.groups() == ("4", "pallas", "4", "0")
    device = chip_smoke.last_json_line(out, "device: ")
    assert device["backend"] == "pallas" and device["devices"] == 1
    assert "first_batch_seconds" in device
    lines = out.strip().splitlines()
    assert lines[-2] == "checked 1 files / 4 blocks; 0 broken"
    stats = json.loads(lines[-1])
    assert stats == chip_smoke.last_json_line(out)
    assert (stats["verified"], stats["hashed_now"], stats["device"]) == (4, 4, device)
