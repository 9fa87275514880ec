"""The encrypted scrub's cell: configuration `fsck-redis-file-4m-aes256gcm-rsa`
— `fsck-redis-file-4m` with its volume encrypted at rest (JuiceFS docs,
Security > Data Encryption) — its mix `verify-encrypted` on the driver
`fsck_encrypted`, and the cell `fsck-verify-redis-aes`.

The configuration is the scrub's but for its cipher; the cell joins exactly
the lists that hold the scrub's cell, and three entries of its own; the plain
reference of the envelope (`lib/envelope_spec.py`) opens what the program
wrote and nothing under another key; cut to one object the cell runs on the
CPU through `run.main` to a `correct` line; and a store built without
encryption, a program that retries a block whose envelope does not open or
reports it unreadable, or one with no typed error for it, comes out not
correct or not at all."""

import importlib
import json
import os

import pytest

import manifest_checks as checks
from benchmark import run
from benchmark.lib import envelope_spec, volume_encrypted, volume_served
from test_benchmark_fsck_verify import config_body, servers_of
from test_benchmark_grows import manifest_root  # noqa: F401 (fixture)
from test_benchmark_run import (  # noqa: F401 (fixtures)
    any_device, argv, last_line, make_root, over_limit, process_as_new)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG, MIX, CELL = "fsck-redis-file-4m-aes256gcm-rsa", "verify-encrypted", "fsck-verify-redis-aes"
LIKE_CONFIG, LIKE_MIX, LIKE_CELL = "fsck-redis-file-4m", "verify", "fsck-verify-redis"
OWN = {"object.decrypt_ms", "object.key_unwrap_ms", "object.decrypt_cpu_ms"}
CIPHER_KEYS = {"encryption": "aes256gcm-rsa", "rsa_key_bits": 2048}
COMPARED = {"ops_failed", "op_counts_wrong", "device_reports_wrong",
            "digests_wrong", "index_rows_wrong", "h2d_bytes_short",
            "tamper_missed", "envelopes_wrong", "plaintext_stored"}
BLOCKS = 16 + 5  # make_root's cut to one object: 21 blocks


@pytest.fixture
def small_root(tmp_path):
    """A copy of the benchmark with every volume cut to one object, the
    scrub and the index fill on the XLA program on whatever JAX found."""
    root = make_root(str(tmp_path / "root"), big_objects=1, hash_backend="xla")
    body = config_body(root, CONFIG)
    body["deployment"]["index_backend"] = "xla"
    with open(os.path.join(root, "benchmark", "configs", CONFIG + ".json"), "w") as f:
        json.dump(body, f)
    yield root
    assert servers_of(root) == []
    assert os.listdir(os.path.join(root, ".bench_work")) == []


# -- the manifest ------------------------------------------------------------

def test_the_configuration_is_the_scrubs_but_for_its_cipher():
    body, like = config_body(name=CONFIG), config_body(name=LIKE_CONFIG)
    assert set(body) == set(like) and body["name"] == CONFIG
    for key in ("architecture", "volume_blocks", "volume", "reduced"):
        assert body[key] == like[key], key
    dep = dict(body["deployment"])
    assert {k: dep.pop(k) for k in CIPHER_KEYS} == CIPHER_KEYS
    assert dep == like["deployment"]  # the entry unchanged among them
    # the five guarantees, the bitrot one restated for the envelope, and one more
    assert len(body["guarantees"]) == len(like["guarantees"]) + 1
    kept = [i for i, g in enumerate(like["guarantees"]) if g in body["guarantees"]]
    assert len(kept) == len(like["guarantees"]) - 1
    assert "corrupt" in body["guarantees"][2] and "plaintext" in body["guarantees"][-1]
    assert {k: body["assumed"][k] for k in like["assumed"]} == like["assumed"]
    assert set(body["assumed"]) - set(like["assumed"]) == {"key"}
    entry, = [c for c in checks.manifest(REPO)["configs"] if c["name"] == CONFIG]
    assert entry["source"] == body["source"] and entry["reduced"] == ["volume_blocks"]
    for part in ("Data Encryption", "--encrypt-rsa-key", "RSA-2048", "aes256gcm-rsa",
                 "BASELINE.json configs[2]", "`juicefs fsck`", "Redis"):
        assert part in body["source"], part


def test_the_cell_joins_the_scrubs_lists_and_three_of_its_own(manifest_root):
    checks.check_all(manifest_root)
    r = run.resolve(manifest_root, CELL)
    assert (r["cell"]["config"], r["cell"]["traffic"], r["cell"]["chips"]) == (
        CONFIG, MIX, 1)
    assert r["traffic"]["driver"] == "fsck_encrypted"
    like = run.resolve(manifest_root, LIKE_CELL)["traffic"]
    assert {k: v for k, v in r["traffic"].items() if k not in ("driver", "what")} == {
        k: v for k, v in like.items() if k not in ("driver", "what")}
    bench = checks.bench_dir(manifest_root)
    for rel in (f"configs/{CONFIG}.json", f"traffic/{MIX}.json",
                "drivers/fsck_encrypted.py", "lib/volume_encrypted.py",
                "lib/envelope_spec.py"):
        assert os.path.isfile(os.path.join(bench, rel)), rel
    assert checks.listing(manifest_root, CELL) == (
        checks.listing(manifest_root, LIKE_CELL) | OWN)
    m = checks.manifest(manifest_root)
    for entry in m["per_layer"]:
        if entry["name"] in OWN:
            assert entry["workloads"] == [CELL]
            assert (entry["layer"], entry["moves"], entry["unit"]) == (
                "object", "scan_gibs", "ms")
        elif LIKE_CELL in entry["workloads"]:
            at = entry["workloads"].index(LIKE_CELL)
            assert CELL in entry["workloads"][at + 1:]


def test_the_reference_opens_what_the_program_wrote_and_no_other_key(tmp_path):
    from juicefs_tpu.object import MemStorage, new_encrypted

    key_file, other = str(tmp_path / "key.pem"), str(tmp_path / "other.pem")
    envelope_spec.make_key(key_file, 2048)
    envelope_spec.make_key(other, 2048)
    inner = MemStorage()
    with open(key_file, "rb") as f:
        store = new_encrypted(inner, f.read(), algo="aes256gcm-rsa")
    data = os.urandom(100_003)
    store.put("k", data)
    raw = bytes(inner.get("k"))
    assert envelope_spec.open_envelope(envelope_spec.load_key(key_file), raw) == data
    assert envelope_spec.open_envelope(envelope_spec.load_key(other), raw) is None
    flipped = bytearray(raw)
    flipped[-100] ^= 1
    assert envelope_spec.open_envelope(
        envelope_spec.load_key(key_file), bytes(flipped)) is None
    assert envelope_spec.open_envelope(envelope_spec.load_key(key_file), raw[:200]) is None


# -- the cell, on the CPU ------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_to_a_correct_line(small_root, any_device, capsys, trace):
    assert run.main(argv(CELL, trace=trace), root=small_root,
                    device_check=any_device) == 0
    line = last_line(capsys)
    assert over_limit(line) == {} and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["compared"]) == COMPARED
    assert all(c["limit"] == 0 for c in line["compared"].values())
    if not trace:
        assert set(line["metrics"]) == {"scan_gibs", "op_p50_ms", "setup_s"}
        return
    assert set(line["metrics"]) == (checks.listing(small_root, CELL)
                                    - checks.DEVICE_METRICS)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(m[name] > 0 for name in OWN)
    assert m["object.key_unwrap_ms"] < m["object.decrypt_ms"] < m["object.store_get_ms"]
    assert m["object.list_objects_per_op"] == BLOCKS


def test_a_store_built_without_encryption_is_not_correct(
        small_root, any_device, capsys, monkeypatch):
    """The builder swapped for the plain one: every stored object is its
    planned block, no envelope opens, and the flipped byte is a digest
    mismatch, not a corrupt block."""
    def plain(workdir, config, seed, meta_url, key_file):
        return volume_served.start(workdir, config, seed, meta_url)

    monkeypatch.setattr(volume_encrypted, "start", plain)
    assert run.main(argv(CELL), root=small_root, device_check=any_device) == 0
    line = last_line(capsys)
    assert line["correct"] is False
    assert over_limit(line) == {"plaintext_stored": BLOCKS,
                                "envelopes_wrong": BLOCKS, "tamper_missed": 1}


@pytest.mark.parametrize("fault", ["retried", "unreadable"])
def test_a_program_that_mistakes_the_tampered_block_misses_it(
        small_root, any_device, capsys, monkeypatch, fault):
    """A program that retries a block whose envelope does not open (as a
    flaky store), or reports it as unreadable: `tamper_missed`."""
    from juicefs_tpu.cmd import readhash

    # the module, not the function of that name the package exports
    resilient = importlib.import_module("juicefs_tpu.object.resilient")
    if fault == "retried":
        classify = resilient.classify

        def as_transient(exc):
            if isinstance(exc, readhash.CorruptObjectError):
                return resilient.ErrorClass.TRANSIENT
            return classify(exc)

        monkeypatch.setattr(resilient, "classify", as_transient)
        monkeypatch.setattr(resilient.RetryPolicy, "backoff", lambda *a, **kw: 0.0)
    else:
        monkeypatch.setattr(readhash, "CorruptObjectError", type("Other", (Exception,), {}))
    assert run.main(argv(CELL), root=small_root, device_check=any_device) == 0
    line = last_line(capsys)
    assert line["correct"] is False and over_limit(line) == {"tamper_missed": 1}


def test_a_program_with_no_typed_corrupt_error_ends_the_run_at_once(
        small_root, any_device, capsys, monkeypatch):
    """A program from before the typed error: exit 1, no result line,
    nothing started."""
    import juicefs_tpu.object as obj

    monkeypatch.delattr(obj, "CorruptObjectError")
    with pytest.raises(SystemExit) as ended:
        run.main(argv(CELL), root=small_root, device_check=any_device)
    assert ended.value.code not in (0, None)
    captured = capsys.readouterr()
    assert captured.out == "" and "refused" in str(ended.value.code)
