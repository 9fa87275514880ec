"""Registry lint gate (CI satellite): tools/lint_metrics.py must pass on
the real registry, and must actually catch the defect classes it claims."""

import importlib.util
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "lint_metrics", os.path.join(_ROOT, "tools", "lint_metrics.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_global_registry_is_clean():
    lint = _load_lint()
    problems = lint.lint()
    assert problems == [], "\n".join(problems)


def test_cache_group_registry_pinned():
    """The juicefs_cache_group_* series the tests/benchmarks counter-assert
    must all exist, and nothing else may squat under the prefix."""
    lint = _load_lint()
    assert lint.lint_cache_group() == []
    # the check really bites: a missing expected series is reported
    from juicefs_tpu.metric import Registry

    reg = Registry()
    reg.counter("juicefs_cache_group_rogue", "unreviewed")
    problems = lint.lint_cache_group(registry=reg)
    text = "\n".join(problems)
    assert "juicefs_cache_group_peer_hits" in text  # missing expected
    assert "rogue" in text                           # stray under prefix


def test_ingest_registry_pinned():
    """The juicefs_ingest_* series the bench and dedup drills
    counter-assert must all exist; nothing squats under the prefix."""
    lint = _load_lint()
    assert lint.lint_ingest() == []
    from juicefs_tpu.metric import Registry

    reg = Registry()
    reg.counter("juicefs_ingest_rogue", "unreviewed")
    problems = lint.lint_ingest(registry=reg)
    text = "\n".join(problems)
    assert "juicefs_ingest_put_elided" in text  # missing expected
    assert "rogue" in text                       # stray under prefix


def test_ingest_seam_lint():
    """WSlice uploads must route through the ingest stage when present:
    the AST check passes on the real tree and bites on a bare upload."""
    lint = _load_lint()
    assert lint.lint_ingest_seam() == []
    # a synthetic cached_store with an unconditional direct upload trips it
    import tempfile

    bad = (
        "class WSlice:\n"
        "    def _upload_block(self, indx, bsize):\n"
        "        fut = self.store._pool.submit(self.store._put_or_stage, 1)\n"
    )
    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(bad)
        path = f.name
    try:
        problems = lint.lint_ingest_seam(path)
        assert problems and "_put_or_stage" in problems[0]
    finally:
        os.unlink(path)


def test_qos_registry_pinned():
    """The juicefs_qos_* series the chaos drill and tests/test_qos.py
    counter-assert must all exist; nothing squats under the prefix."""
    lint = _load_lint()
    assert lint.lint_qos() == []
    from juicefs_tpu.metric import Registry

    reg = Registry()
    reg.counter("juicefs_qos_rogue", "unreviewed")
    problems = lint.lint_qos(registry=reg)
    text = "\n".join(problems)
    assert "juicefs_qos_submitted" in text  # missing expected
    assert "rogue" in text                   # stray under prefix


def test_qos_seam_lint():
    """No bare ThreadPoolExecutor outside qos/ and the whitelisted
    resilience pool: passes on the real tree, bites on a synthetic
    module that spins up its own pool."""
    import tempfile

    lint = _load_lint()
    assert lint.lint_qos_seam() == []
    with tempfile.TemporaryDirectory() as root:
        bad = os.path.join(root, "rogue.py")
        with open(bad, "w") as f:
            f.write(
                "from concurrent.futures import ThreadPoolExecutor\n"
                "def go():\n"
                "    with ThreadPoolExecutor(max_workers=4) as p:\n"
                "        pass\n"
            )
        # a commented/docstring mention must NOT trip it
        ok = os.path.join(root, "fine.py")
        with open(ok, "w") as f:
            f.write('"""mentions ThreadPoolExecutor only in prose"""\n')
        problems = lint.lint_qos_seam(root)
        assert len(problems) == 1 and "rogue.py:3" in problems[0]
        # the whitelisted resilience pool path is exempt
        objdir = os.path.join(root, "object")
        os.makedirs(objdir)
        os.rename(bad, os.path.join(objdir, "resilient.py"))
        assert lint.lint_qos_seam(root) == []


def test_lint_catches_bad_registrations():
    from juicefs_tpu.metric import Registry

    lint = _load_lint()
    reg = Registry()
    reg.counter("not_prefixed", "has help")
    reg.gauge("juicefs_no_help", "")
    # conflicting duplicate: same name, different kind
    reg.counter("juicefs_dup", "a counter")
    reg.gauge("juicefs_dup", "now a gauge")
    # conflicting duplicate: same name/kind, different label set
    reg.counter("juicefs_dup2", "labeled", ("a",))
    reg.counter("juicefs_dup2", "labeled", ("a", "b"))
    problems = lint.lint(registry=reg)
    text = "\n".join(problems)
    assert "not_prefixed" in text
    assert "juicefs_no_help" in text
    assert "juicefs_dup:" in text
    assert "juicefs_dup2:" in text


def test_benign_re_registration_is_not_flagged():
    from juicefs_tpu.metric import Registry

    reg = Registry()
    a = reg.counter("juicefs_same", "help", ("x",))
    b = reg.counter("juicefs_same", "help", ("x",))
    assert a is b
    assert reg.conflicts == []


def test_cli_entrypoint_exits_zero():
    import subprocess

    p = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "lint_metrics.py")],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert p.returncode == 0, p.stderr
    assert "OK" in p.stdout


def test_compress_registry_pinned():
    """The juicefs_compress_* series (ISSUE 8: batch size histogram,
    bytes in/out, ratio, degrade counter) must all exist; nothing
    squats under the prefix."""
    lint = _load_lint()
    assert lint.lint_compress() == []
    from juicefs_tpu.metric import Registry

    reg = Registry()
    reg.counter("juicefs_compress_rogue", "unreviewed")
    problems = lint.lint_compress(registry=reg)
    text = "\n".join(problems)
    assert "juicefs_compress_ratio" in text  # missing expected
    assert "rogue" in text                    # stray under prefix


def test_index_registry_pinned():
    """The juicefs_index_* series (ISSUE 21: persisted / dropped / failed
    account for every submitted block) must all exist; nothing squats
    under the prefix."""
    lint = _load_lint()
    assert lint.lint_index() == []
    from juicefs_tpu.metric import Registry

    reg = Registry()
    reg.counter("juicefs_index_rogue", "unreviewed")
    text = "\n".join(lint.lint_index(registry=reg))
    assert "juicefs_index_errors" in text  # missing expected
    assert "rogue" in text                  # stray under prefix


def test_compress_seam_lint():
    """Write-path compression in chunk/ must route through the batched
    plane: passes on the real tree, bites on a synthetic chunk module
    calling compressor.compress directly."""
    import tempfile

    lint = _load_lint()
    assert lint.lint_compress_seam() == []
    with tempfile.TemporaryDirectory() as root:
        chunkdir = os.path.join(root, "chunk")
        os.makedirs(chunkdir)
        with open(os.path.join(chunkdir, "cached_store.py"), "w") as f:
            f.write(
                "class CachedStore:\n"
                "    def _put_block(self, key, raw):\n"
                "        data = self.compressor.compress(raw)\n"
            )
        problems = lint.lint_compress_seam(root)
        # both defects: a bare compress call AND no plane seam in sight
        text = "\n".join(problems)
        assert "compressor.compress" in text or "bare" in text
        assert any("compress_one" in p or "plane" in p for p in problems)
        # decompress-side mentions must NOT trip it
        with open(os.path.join(chunkdir, "cached_store.py"), "w") as f:
            f.write(
                "class CachedStore:\n"
                "    def _put_block(self, key, raw):\n"
                "        data = self.compress_plane.compress_one(raw)\n"
                "    def _load(self, key, data, n):\n"
                "        return self.compressor.decompress(data, n)\n"
            )
        assert lint.lint_compress_seam(root) == []
