"""Device resolution (ISSUE 21): `--hash-backend tpu` means a TPU wherever
it is given, no failed device init turns into the host hash, every
device-path output carries the device report, and the compile cache can
be placed from outside. All on the CPU backend: what runs on a chip is
chip_smoke.py's to prove."""

import json
import os

import pytest

from juicefs_tpu.cmd import main
from juicefs_tpu.tpu import device
from juicefs_tpu.tpu.pipeline import HashPipeline, PipelineConfig

from test_cmd import _open_vfs, _write_file, vol  # noqa: F401 (fixture)

REPORT_KEYS = {"platform", "device_kind", "devices", "visible_devices",
               "requested", "backend", "pallas_mode", "mesh", "degraded",
               "reason"}


def test_resolve_backend_names():
    assert device.resolve_backend("") == "cpu"
    assert device.resolve_backend("cpu") == "cpu"
    assert device.resolve_backend("xla") == "xla"
    assert device.resolve_backend("pallas") == "pallas"
    with pytest.raises(ValueError, match="unknown hash backend"):
        device.resolve_backend("cuda")


def test_tpu_requested_without_a_tpu_raises_naming_the_platform():
    with pytest.raises(device.DeviceUnavailable, match="platform 'cpu'"):
        device.resolve_backend("tpu")
    # the pipeline, the indexer and inline ingest all resolve through it
    with pytest.raises(device.DeviceUnavailable):
        HashPipeline(PipelineConfig(backend="tpu"))
    from juicefs_tpu.chunk.indexer import BlockIndexer

    with pytest.raises(device.DeviceUnavailable):
        BlockIndexer(backend="tpu")


def test_failed_device_init_raises_instead_of_hashing_on_the_host(monkeypatch):
    import jax

    from juicefs_tpu.tpu import sharding

    def boom():
        raise RuntimeError("libtpu failed to initialise")

    monkeypatch.setattr(jax, "devices", boom)
    sharding._reset_plane_for_tests()
    try:
        for backend in ("xla", "pallas"):
            with pytest.raises(RuntimeError, match="failed to initialise"):
                HashPipeline(PipelineConfig(backend=backend))
        with pytest.raises(device.DeviceUnavailable,
                           match="could not initialise"):
            device.resolve_backend("tpu")
    finally:
        sharding._reset_plane_for_tests()
    # the host hash is reached by asking for it
    monkeypatch.undo()
    pipe = HashPipeline(PipelineConfig(backend="cpu"))
    assert not pipe.device_backend
    assert pipe.device_report()["platform"] == "host"


def test_device_report_fields_per_backend():
    import jax

    xla = HashPipeline(PipelineConfig(backend="xla")).device_report()
    assert REPORT_KEYS <= set(xla)
    assert xla["platform"] == "cpu" and xla["backend"] == "xla"
    assert xla["visible_devices"] == len(jax.devices())
    assert xla["devices"] == (xla["mesh"]["data"] * xla["mesh"]["lane"]
                              if xla["mesh"] else 1)
    assert xla["pallas_mode"] is None and "shard_degraded" in xla
    assert xla["jax"] == jax.__version__

    pal = HashPipeline(PipelineConfig(backend="pallas")).device_report()
    # the Pallas kernel bypasses the plane: one device, and it says so
    assert pal["devices"] == 1 and pal["mesh"] is None
    assert pal["visible_devices"] == len(jax.devices())
    assert pal["pallas_mode"] == "interpret"  # CPU backend; never silent
    assert "first device" in pal["reason"]

    cpu = HashPipeline(PipelineConfig(backend="cpu")).device_report()
    assert cpu["devices"] == 0 and cpu["platform"] == "host"
    assert cpu["device_kind"] in ("libjfscore", "numpy")


def test_first_batch_is_reported_apart():
    pipe = HashPipeline(PipelineConfig(backend="xla", batch_blocks=2,
                                       pad_lanes=1))
    assert pipe.device_report()["first_batch_seconds"] is None
    pipe.hash_blocks([b"a", b"b", b"c"])
    first = pipe.device_report()["first_batch_seconds"]
    assert first is not None and first > 0
    pipe.hash_blocks([b"d"])
    assert pipe.device_report()["first_batch_seconds"] == first


@pytest.mark.parametrize("cmd", ["gc", "fsck", "format"])
def test_cli_tpu_backend_exits_1_without_a_tpu(cmd, vol, tmp_path, capsys,
                                               caplog):
    meta_url, _bucket, _tmp = vol
    capsys.readouterr()
    argv = {
        "gc": ["gc", meta_url, "--dedup", "--hash-backend", "tpu"],
        "fsck": ["fsck", meta_url, "--verify-data", "--hash-backend", "tpu"],
        "format": ["format", f"sqlite3://{tmp_path}/t.db", "tvol",
                   "--storage", "file", "--bucket", str(tmp_path / "tb"),
                   "--hash-backend", "tpu"],
    }[cmd]
    with caplog.at_level("ERROR"):
        assert main(argv) == 1
    assert "platform 'cpu'" in caplog.text
    # no result: not the dedup stats, not a verified line, not a volume
    out = capsys.readouterr().out
    assert "{" not in out and "verified" not in out and "formatted" not in out
    if cmd == "format":  # refused before the meta engine was touched
        assert not os.path.exists(tmp_path / "t.db")


def test_gc_and_fsck_print_the_device_report(vol, capsys, tmp_path):
    meta_url, _bucket, tmp = vol
    v = _open_vfs(meta_url, tmp)
    _write_file(v, b"a.bin", os.urandom(300_000))
    v.close()
    capsys.readouterr()

    assert main(["gc", meta_url, "--dedup", "--hash-backend", "xla"]) == 0
    stats = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert REPORT_KEYS <= set(stats["device"])
    assert stats["device"]["platform"] == "cpu"
    assert stats["device"]["backend"] == stats["backend"] == "xla"
    assert stats["device"]["first_batch_seconds"] > 0
    assert stats["hashed_now"] == 2

    # the volume has no hash backend: the default scan resolves to the
    # host hash and SAYS so, instead of echoing an argument
    assert main(["gc", meta_url, "--dedup"]) == 0
    stats = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert stats["backend"] == "cpu" and stats["device"]["platform"] == "host"

    assert main(["fsck", meta_url, "--verify-data",
                 "--hash-backend", "pallas"]) == 0
    out = capsys.readouterr().out
    assert "verified 2 blocks (pallas); 2 indexed, 0 digest mismatches" in out
    line = [ln for ln in out.splitlines() if ln.startswith("device: ")][-1]
    rep = json.loads(line[len("device: "):])
    assert REPORT_KEYS <= set(rep)
    assert rep["backend"] == "pallas" and rep["pallas_mode"] == "interpret"
    assert rep["platform"] == "cpu" and rep["devices"] == 1


def test_pipeline_exports_device_info_and_index_errors():
    from juicefs_tpu.metric import global_registry

    HashPipeline(PipelineConfig(backend="xla"))
    text = global_registry().render()
    info = [ln for ln in text.splitlines()
            if ln.startswith("juicefs_tpu_device_info{")]
    assert any('platform="cpu"' in ln and 'backend="xla"' in ln
               and 'device_kind="cpu"' in ln for ln in info)
    assert "juicefs_index_errors " in text
    assert "juicefs_index_dropped_blocks " in text


def test_index_errors_gauge_counts_failed_batches():
    from juicefs_tpu.chunk.indexer import BlockIndexer, _sum_live

    class BrokenMeta:
        def set_block_digests(self, rows):
            raise IOError("meta down")

    before = _sum_live("errors")
    ix = BlockIndexer(meta=BrokenMeta(), backend="cpu", block_size=1 << 16)
    try:
        ix.submit_raw(1, 0, 3, b"abc")
        ix.submit_raw(1, 1, 3, b"def")
        ix.flush(10)
        assert ix.errors == 2 and ix.blocks == 0
        assert _sum_live("errors") == before + 2
    finally:
        ix.close()


def test_compile_cache_dir_from_environment_sets_nothing():
    calls = []
    got = device.configure_compile_cache(
        environ={"JAX_COMPILATION_CACHE_DIR": "/some/dir"},
        update=lambda k, v: calls.append((k, v)))
    assert got == "/some/dir"
    # JAX honours the variable itself: no directory is set in code
    assert not [k for k, _ in calls if k == "jax_compilation_cache_dir"]


def test_compile_cache_default_is_fixed_under_the_checkout():
    calls = []
    got = device.configure_compile_cache(
        environ={}, update=lambda k, v: calls.append((k, v)))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(repo, ".jax_cache")
    assert ("jax_compilation_cache_dir", got) in calls
    # fixed: nothing of the process or the clock in it
    assert got == device.default_compile_cache_dir()
    assert str(os.getpid()) not in os.path.basename(got)
    # an operator's own threshold is left alone
    calls.clear()
    device.configure_compile_cache(
        environ={"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "5"},
        update=lambda k, v: calls.append((k, v)))
    assert [k for k, _ in calls] == ["jax_compilation_cache_dir"]
