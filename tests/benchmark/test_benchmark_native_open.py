"""`object.native_open_share`: envelopes opened by libjfscore outside the
interpreter lock, per block hashed. Listed by the encrypted scrub's cell
`fsck-verify-redis-aes`, where every block's envelope opens natively (100
where the host has the library and libcrypto), and by the plaintext scrub
`fsck-verify-redis`, which opens no envelope (0): the bypass holds. Cut to
one object, both cells run traced on the CPU through `run.main`."""

import json
import os

import pytest

import manifest_checks as checks
from benchmark import run
from test_benchmark_fsck_verify import config_body, servers_of
from test_benchmark_grows import manifest_root  # noqa: F401 (fixture)
from test_benchmark_run import (  # noqa: F401 (fixtures)
    any_device, argv, last_line, make_root, over_limit, process_as_new)

from juicefs_tpu import native

METRIC = "object.native_open_share"
CELL, LIKE_CELL = "fsck-verify-redis-aes", "fsck-verify-redis"
CONFIGS = ("fsck-redis-file-4m-aes256gcm-rsa", "fsck-redis-file-4m")


@pytest.fixture
def small_root(tmp_path):
    """A copy of the benchmark with every volume cut to one object, both
    scrubs and their index fill on the XLA program on whatever JAX found."""
    root = make_root(str(tmp_path / "root"), big_objects=1, hash_backend="xla")
    for name in CONFIGS:
        body = config_body(root, name)
        body["deployment"]["index_backend"] = "xla"
        with open(os.path.join(root, "benchmark", "configs", name + ".json"), "w") as f:
            json.dump(body, f)
    yield root
    assert servers_of(root) == []
    assert os.listdir(os.path.join(root, ".bench_work")) == []


def test_the_metric_reads_the_open_counter_in_both_scrubs(manifest_root):
    checks.check_all(manifest_root)
    entry, = [e for e in checks.manifest(manifest_root)["per_layer"]
              if e["name"] == METRIC]
    assert entry == {"name": METRIC, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "object",
                     "moves": "scan_gibs", "workloads": [LIKE_CELL, CELL]}
    with open(os.path.join(checks.bench_dir(manifest_root), "layer_metrics",
                           METRIC + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "registry"
    assert spec["args"] == {
        "kind": "counter_gain", "series": "juicefs_object_decrypt_opens",
        "labels": {"algo": "aes256gcm-rsa", "how": "native"},
        "per_work": "hashed_blocks", "scale": 100}


@pytest.mark.parametrize("cell", [LIKE_CELL, CELL])
def test_the_share_is_every_block_where_envelopes_open_natively(
        small_root, any_device, capsys, cell):
    assert run.main(argv(cell, trace=1), root=small_root, device_check=any_device) == 0
    line = last_line(capsys)
    assert over_limit(line) == {} and line["correct"] is True
    share = line["metrics"][METRIC]["value"]
    assert share == (100 if cell == CELL and native.crypto_available() else 0)
