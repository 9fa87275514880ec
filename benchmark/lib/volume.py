"""Builds the volume a scan cell runs over, from the seed, through the
program's own write path: `format`, then every planned object written with
the SDK (`FileSystem` over VFS -> chunk store -> object store), no indexer,
so the content index starts empty. Records which planned block each stored
block key holds, read back from the chunk records the write committed.

It runs as a child process of its own (`python3 volume.py WORKDIR SEED`,
see `start`), never in the process that is measured. What that process has
allocated before the window decides how fast the program packs
a batch (PERF.md, PR 24: a scan after an in-process build ran 3x faster than
the same scan in a fresh `gc` process, because glibc then serves each 128 MiB
batch buffer from a heap the build left behind instead of fresh pages). The
measured process has to look like the `gc` process an operator starts. The
child never initialises a JAX backend, so the chip stays the parent's.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

CHUNK = 1 << 26  # the volume format's fixed chunk size: one 64 MiB object
RESULT = "volume.json"  # {"meta_url": ..., "blocks": [[key, content, size]]}


def start(workdir: str, config: dict, seed: int) -> subprocess.Popen:
    with open(os.path.join(workdir, "config.json"), "w") as f:
        json.dump(config, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # never the parent's chip
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), workdir, str(seed)],
        env=env, stdout=subprocess.DEVNULL)


def wait(child: subprocess.Popen, workdir: str, plan):
    """-> (meta_url, {stored block key: PlannedBlock})"""
    if child.wait() != 0:
        raise RuntimeError(f"the volume builder exited with {child.returncode}")
    with open(os.path.join(workdir, RESULT)) as f:
        result = json.load(f)
    by_content = {(tuple(b.content), b.size): b for b in plan.blocks}
    return result["meta_url"], {
        key: by_content[(tuple(content), size)]
        for key, content, size in result["blocks"]}


def build(workdir: str, plan, deployment: dict) -> None:
    from benchmark.lib.plan import block_bytes
    from juicefs_tpu.chunk.cached_store import block_key
    from juicefs_tpu.cmd import build_store, main, open_meta
    from juicefs_tpu.fs import FileSystem
    from juicefs_tpu.vfs import VFS

    bs = int(deployment["block_bytes"])
    meta_url = f"{deployment['meta']}://{workdir}/meta.db"
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


def _map_blocks(m, ino, obj, bs, block_key, block_of) -> None:
    """Each slice the write committed must start on a block boundary of the
    object and be whole (no overwrite): then block i of the slice is block
    (start // bs + i) of the plan."""
    for c in range(-(-obj.size // CHUNK)):
        st, slices = m.read_chunk(ino, c)
        if st != 0:
            raise RuntimeError(f"read_chunk({obj.name}, {c}): errno {st}")
        for s in slices:
            start = c * CHUNK + s.pos
            if s.id == 0 or s.off != 0 or s.len != s.size or start % bs:
                raise RuntimeError(
                    f"{obj.name}: slice {s} is not a whole, block-aligned write")
            for i in range(-(-s.size // bs)):
                planned = obj.blocks[start // bs + i]
                size = min(bs, s.size - i * bs)
                if size != planned.size:
                    raise RuntimeError(
                        f"{obj.name}: stored block {i} of slice {s.id} has "
                        f"{size} B, planned {planned.size}")
                block_of[block_key(s.id, i, size)] = planned


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.lib.plan import plan_of

    with open(os.path.join(sys.argv[1], "config.json")) as _f:
        _config = json.load(_f)
    build(sys.argv[1], plan_of(int(sys.argv[2]), _config["volume"]),
          _config["deployment"])
    # 2 GiB of dirty pages written back during the window made runs differ
    # by 4% (PERF.md, PR 24); flushed here, about a second, they repeat to 0.3%
    os.sync()
