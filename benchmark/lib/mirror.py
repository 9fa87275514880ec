"""Builds what a mirror-check cell runs over, from the seed: the source
volume through `lib/volume.py` (the program's own write path, unchanged),
then its mirror, written by the program's own `sync <src> <dst> --threads N`
— the pass an operator ran before they verify it. The two endpoints are the
volume's own prefix of its bucket and the same prefix of a second bucket
beside it, both `file://`.

Like the volume builder it runs as a child of its own (`python3 mirror.py
WORKDIR SEED`, see `start`), never in the process that is measured, never
initialises a JAX backend, and `os.sync()`s last: no write-back of either
side inside the window. `volume.wait` reads its result.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

VOLUME_NAME = "benchvol"  # lib/volume.py's format line


def endpoints(workdir: str) -> tuple[str, str]:
    """(src, dst) directories of the pass: each the volume's prefix of its
    bucket, with the trailing slash an endpoint has."""
    return (os.path.join(workdir, "blob", VOLUME_NAME) + "/",
            os.path.join(workdir, "mirror", VOLUME_NAME) + "/")


def start(workdir: str, config: dict, seed: int) -> subprocess.Popen:
    with open(os.path.join(workdir, "config.json"), "w") as f:
        json.dump(config, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # never the parent's chip
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), workdir, str(seed)],
        env=env, stdout=subprocess.DEVNULL)


def build(workdir: str, plan, deployment: dict) -> None:
    from benchmark.lib import volume
    from juicefs_tpu.cmd import main

    volume.build(workdir, plan, dict(deployment["source_volume"],
                                     block_bytes=deployment["block_bytes"]))
    src, dst = endpoints(workdir)
    argv = ["sync", "file://" + src, "file://" + dst,
            "--threads", str(deployment["threads"])]
    if main(argv) != 0:
        raise RuntimeError(f"writing the mirror failed: {argv}")


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.lib.plan import plan_of

    with open(os.path.join(sys.argv[1], "config.json")) as _f:
        _config = json.load(_f)
    build(sys.argv[1], plan_of(int(sys.argv[2]), _config["volume"]),
          _config["deployment"])
    os.sync()  # as lib/volume.py: no write-back inside the window
