"""The scan's allocator policy (ISSUE 27, `utils/malloc.py`): stated once, at
the entry of the commands whose process is a bulk scan, and nowhere else."""

import ctypes
import json
import logging
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

from juicefs_tpu.cmd import main
from juicefs_tpu.metric import Registry
from juicefs_tpu.utils import malloc
from test_cmd import _open_vfs, _write_file, vol  # noqa: F401 (fixture)

REPO = pathlib.Path(__file__).resolve().parent.parent

needs_glibc = pytest.mark.skipif(
    malloc._glibc_mallopt()[0] is None, reason="no glibc malloc to tell")


@pytest.fixture
def unasked(monkeypatch):
    """The module as a new process has it, with a gauge of its own."""
    monkeypatch.setattr(malloc, "_in_force", None)
    gauge = Registry().gauge("juicefs_malloc_policy", "",
                             ("mmap_threshold", "trim_threshold"))
    monkeypatch.setattr(malloc, "_POLICY", gauge)
    return gauge


def test_policy_is_set_once_and_says_so(unasked, monkeypatch):
    calls = []
    monkeypatch.setattr(malloc, "_glibc_mallopt", lambda: (
        lambda param, value: calls.append((param, value)) or 1, ""))
    assert [malloc.keep_freed_blocks() for _ in range(3)] == [True] * 3
    assert calls == [(malloc.M_MMAP_THRESHOLD, 32 << 20),
                     (malloc.M_TRIM_THRESHOLD, 64 << 20)]
    assert unasked.render().splitlines()[-1] == (
        'juicefs_malloc_policy{mmap_threshold="33554432",'
        'trim_threshold="67108864"} 1')


@pytest.mark.parametrize("found", [
    lambda: (None, "no glibc mallopt: function 'mallopt' not found"),
    lambda: (lambda param, value: 0, ""),   # there, and refuses
], ids=["absent", "refused"])
def test_policy_is_a_no_op_that_logs_once(unasked, monkeypatch, caplog, found):
    monkeypatch.setattr(malloc, "_glibc_mallopt", found)
    with caplog.at_level(logging.INFO, logger="utils.malloc"):
        assert [malloc.keep_freed_blocks() for _ in range(3)] == [False] * 3
    said = [r.getMessage() for r in caplog.records]
    assert len(said) == 1 and said[0].startswith("allocator policy not set")
    assert "juicefs_malloc_policy{" not in unasked.render()


_CDLL = ctypes.CDLL  # the real one: the tests below stand others in its name


class _Musl:
    """A libc with a malloc and no `mallopt`, nor glibc's version call."""

    malloc = ctypes.CFUNCTYPE(ctypes.c_void_p, ctypes.c_size_t)(lambda n: 0)


class _Preloaded:
    """glibc's names all there, and `malloc` somebody else's."""

    def __init__(self, name):
        self.real = _CDLL(name)
        if name is None:
            self.malloc = _Musl.malloc

    def __getattr__(self, attr):
        return getattr(self.real, attr)


def _no_library(name):
    raise OSError("libc.so.6: cannot open shared object file")


@pytest.mark.parametrize("cdll,why", [
    (lambda name: _Musl, "no glibc mallopt"),
    (_no_library, "no glibc mallopt"),
    pytest.param(_Preloaded, "a preloaded allocator", marks=needs_glibc),
], ids=["musl", "no-libc", "preloaded"])
def test_no_glibc_malloc_is_found_out_and_never_raises(monkeypatch, cdll, why):
    monkeypatch.setattr(ctypes, "CDLL", cdll)
    mallopt, why_not = malloc._glibc_mallopt()
    assert mallopt is None and why in why_not


def _scanned_volume(vol):
    meta_url, _, tmp = vol
    v = _open_vfs(meta_url, tmp)
    _write_file(v, b"a.bin", os.urandom(300_000))
    v.close()
    return meta_url


@pytest.mark.parametrize("argv,asked", [
    (["gc", "--dedup", "--hash-backend", "cpu"], 1),
    (["gc"], 0),
    (["fsck", "--verify-data", "--hash-backend", "cpu"], 1),
    (["fsck", "--hash-index", "{tmp}/index.json", "--hash-backend", "cpu"], 1),
    (["fsck"], 0),
], ids=["gc--dedup", "gc", "fsck--verify-data", "fsck--hash-index", "fsck"])
def test_only_the_scans_ask_for_the_policy(vol, monkeypatch, capsys,
                                           argv, asked):
    meta_url = _scanned_volume(vol)
    calls = []
    monkeypatch.setattr(malloc, "keep_freed_blocks",
                        lambda: calls.append(1) or True)
    argv = [a.format(tmp=vol[2]) for a in argv]
    assert main(argv[:1] + [meta_url] + argv[1:]) == 0
    assert len(calls) == asked


def test_gateway_start_up_does_not_ask_for_the_policy(vol, monkeypatch,
                                                      capsys):
    from juicefs_tpu.cmd import gateway

    meta_url = _scanned_volume(vol)
    calls = []
    monkeypatch.setattr(malloc, "keep_freed_blocks",
                        lambda: calls.append(1) or True)
    served = []

    def stop_at_once(vfs, m, server, what, port, metrics=""):
        served.append(what)
        server.stop()
        vfs.close()
        m.close_session()
        return 0

    monkeypatch.setattr(gateway, "_serve_forever", stop_at_once)
    assert main(["gateway", meta_url, "--port", "0"]) == 0
    assert served == ["S3 gateway"] and calls == []


def test_nothing_else_of_the_program_names_the_policy():
    """`mount`, the gateway and every library function: the module is
    imported by the three commands whose process is a bulk scan — `gc`,
    `fsck` and `sync` with a hash backend — and by nobody else."""
    pkg = REPO / "juicefs_tpu"
    naming = {str(p.relative_to(pkg)) for p in pkg.rglob("*.py")
              if any(word in p.read_text() for word in
                     ("utils.malloc", "keep_freed_blocks", "mallopt"))}
    assert naming == {"utils/malloc.py", "cmd/gc.py", "cmd/fsck.py",
                      "cmd/sync.py"}
    for entry in ("chip_smoke.py", "benchmark/run.py",
                  "benchmark/drivers/scan.py"):
        assert "malloc" not in (REPO / entry).read_text()


GETS = textwrap.dedent("""
    import json, os, resource, sys, tempfile
    from concurrent.futures import ThreadPoolExecutor

    if sys.argv[1] == "with":
        from juicefs_tpu.utils.malloc import keep_freed_blocks
        assert keep_freed_blocks()
    with tempfile.NamedTemporaryFile() as f:
        f.write(os.urandom(4 << 20))
        f.flush()

        def get(_):
            with open(f.name, "rb") as g:
                return g.read()

        rounds = []
        with ThreadPoolExecutor(10) as pool:
            for _ in range(10):
                faults = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
                batch = list(pool.map(get, range(26)))   # held, as a batch is
                assert all(len(b) == 4 << 20 for b in batch)
                del batch
                rounds.append(resource.getrusage(
                    resource.RUSAGE_SELF).ru_minflt - faults)
    print(json.dumps(rounds))
""")


@needs_glibc
@pytest.mark.parametrize("policy", ["with", "without"])
def test_freed_get_buffers_are_recycled_with_the_policy_alone(policy):
    """A scan's GETs off the chip: rounds of 26 reads of 4 MiB on ten
    threads, each round's `bytes` dropped together. The first round
    first-touches every buffer either way (26 x 1,024 faults). With the
    policy the arenas keep them, and once each thread's arena holds its
    share (a few rounds) a round faults hardly at all; without, most of
    every round is first-touched again."""
    out = subprocess.run(
        [sys.executable, "-c", GETS, policy], cwd=REPO, check=True,
        capture_output=True, text=True, timeout=120).stdout
    rounds = json.loads(out.strip().splitlines()[-1])
    assert rounds[0] >= 26 * 1024
    quarter = 3 * rounds[0] / 4
    if policy == "with":
        assert sum(rounds[-3:]) < quarter, rounds
    else:
        assert sum(rounds[-3:]) > quarter, rounds


SCAN = textwrap.dedent("""
    import json, sys
    from juicefs_tpu.cmd import main
    from juicefs_tpu.metric import global_registry

    assert main(["gc", sys.argv[1], "--dedup", "--hash-backend", "xla"]) == 0
    print(json.dumps({"faults": global_registry()._metrics[
        "juicefs_scan_minor_faults"].value}))
""")


def test_a_scan_counts_its_minor_faults(vol):
    """In a process of its own, as an operator's `gc` is: in a test worker
    an earlier scan has set the policy, and the pack's buffer may then come
    from arena memory that was touched before."""
    meta_url = _scanned_volume(vol)
    out = subprocess.run(
        [sys.executable, "-c", SCAN, meta_url], cwd=REPO, check=True,
        capture_output=True, text=True, timeout=120).stdout
    stats, counted = map(json.loads, out.strip().splitlines()[-2:])
    assert stats["hashed_now"] == 2
    # a 2-block batch of 256 KiB blocks, packed fresh: its pages at the least
    assert counted["faults"] >= 2 * (256 << 10) // 4096
