"""The volume builder of `lib/volume.py` for a meta engine that is served:
the meta URL names a server that is already listening (`redis://host:port/db`)
instead of a file under the workdir. Everything else is `lib/volume.py`'s —
the same plan written through the same write path by a child process that
never initialises a JAX backend, the same record of which planned block each
stored key holds (`volume.wait` reads it), the same `os.sync()`. Only the
format line differs; folding the two is a `benchmark` issue's (PERF.md §7 (b)).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor


def start(workdir: str, config: dict, seed: int, meta_url: str) -> subprocess.Popen:
    with open(os.path.join(workdir, "config.json"), "w") as f:
        json.dump(config, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # never the parent's chip
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), workdir, str(seed), meta_url],
        env=env, stdout=subprocess.DEVNULL)


def build(workdir: str, plan, deployment: dict, meta_url: str) -> None:
    from benchmark.lib.plan import block_bytes
    from benchmark.lib.volume import RESULT, _map_blocks
    from juicefs_tpu.chunk.cached_store import block_key
    from juicefs_tpu.cmd import build_store, main, open_meta
    from juicefs_tpu.fs import FileSystem
    from juicefs_tpu.vfs import VFS

    bs = int(deployment["block_bytes"])
    argv = ["format", meta_url, "benchvol", "--storage", deployment["storage"],
            "--bucket", os.path.join(workdir, "blob") + "/", "--trash-days", "0",
            "--block-size", str(bs // 1024),
            "--compress", deployment["compression"]]
    if main(argv) != 0:
        raise RuntimeError(f"format failed: {argv}")
    m, fmt = open_meta(meta_url)
    m.new_session()
    store = build_store(fmt, None)  # no meta attached: no indexer, no ingest
    vfs = VFS(m, store, fmt=fmt)
    fs = FileSystem(vfs)
    block_of: dict = {}

    def object_blocks(obj):
        return [block_bytes(plan.seed, b) for b in obj.blocks]

    try:
        # generation runs two objects ahead of the write, in input order
        with ThreadPoolExecutor(2) as gen:
            for obj, datas in zip(plan.objects, gen.map(object_blocks, plan.objects)):
                with fs.create("/" + obj.name) as f:
                    f.write(b"".join(datas))
                    ino = f.ino
                _map_blocks(m, ino, obj, bs, block_key, block_of)
    finally:
        vfs.close()
        store.close()
        m.close_session()
    if len(block_of) != len(plan.blocks):
        raise RuntimeError(f"volume holds {len(block_of)} blocks, planned "
                           f"{len(plan.blocks)}")
    with open(os.path.join(workdir, RESULT), "w") as f:
        json.dump({"meta_url": meta_url,
                   "blocks": [[k, list(b.content), b.size]
                              for k, b in block_of.items()]}, f)


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.lib.plan import plan_of

    with open(os.path.join(sys.argv[1], "config.json")) as _f:
        _config = json.load(_f)
    build(sys.argv[1], plan_of(int(sys.argv[2]), _config["volume"]),
          _config["deployment"], sys.argv[3])
    os.sync()  # as lib/volume.py: no write-back inside the window
