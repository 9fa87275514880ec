"""The benchmark's own copies — the seeded plan and the numpy JTH-256
spec — against `chip_smoke.py`'s plan and the program's normative
`jth256()`. Later PRs may change those; the copies are the yardstick."""

import importlib.util
import os

import numpy as np
import pytest

from benchmark.lib import jth256_spec, plan

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(name, relpath):
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    import chip_smoke  # the repo root is on sys.path (tests/conftest.py)

    return chip_smoke


@pytest.mark.parametrize("seed", [0, 21, 2**31 + 5])
def test_plan_copy_agrees_with_chip_smoke(smoke, seed):
    theirs, ours = smoke.make_plan(seed, 3), plan.make_plan(seed, 3)
    flat = lambda p: [(o.name, [(b.content, b.size) for b in o.blocks])
                      for o in p.objects]
    assert flat(theirs) == flat(ours)
    assert theirs.expected_duplicates == ours.expected_duplicates
    assert theirs.nbytes == ours.nbytes
    for b in (ours.blocks[0], ours.blocks[-1], ours.blocks[-3]):
        assert smoke.block_bytes(seed, b) == plan.block_bytes(seed, b)


def test_every_seed_plans_the_same_sizes():
    sizes = lambda seed: [b.size for b in plan.make_plan(seed, 4).blocks]
    assert sizes(1) == sizes(2**31 + 7)
    assert len(plan.make_plan(5, 32).blocks) == 517


@pytest.mark.parametrize("n", [0, 1, 7, 65536, 65537, 100_001,
                               (4 << 20) - 1, 4 << 20])
def test_spec_copy_agrees_with_the_normative_reference(n):
    normative = load("_jth256", "juicefs_tpu/tpu/jth256.py")
    data = np.random.default_rng([n, 3]).bytes(n)
    assert jth256_spec.jth256(data) == normative.jth256(data)


def test_spec_copy_imports_nothing_of_the_program():
    for mod in (jth256_spec, plan):
        with open(mod.__file__) as f:
            assert "juicefs_tpu" not in f.read().split('"""', 2)[2]
