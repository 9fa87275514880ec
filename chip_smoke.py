#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served dedup path still
starts, runs and is right on the chip.

It drives the system the way its users do: CLI children
(`python -m juicefs_tpu.cmd ...`), real 4 MiB blocks, real sqlite3 +
file:// volumes under a scratch directory it creates and removes.

  1. write    format --hash-backend tpu, gateway, PUT >= 1 GiB over
              loopback HTTP, GET a sample back, read /metrics, SIGTERM
  2. cold     a second volume with an empty content index, >= 2 GiB
              written through the same gateway code, then
              gc --dedup --hash-backend tpu
  3. warm     the same scan again: every digest read back, none recomputed
  4. fsck     fsck --verify-data --hash-index F --hash-backend pallas: the
              other kernel against the rows step 2 wrote, then a seeded
              sample of F against the numpy spec recomputed from the plan
  5. plane    one device child off the CLI: sharded placement
              (addressable_shards), the device dedup sort (scan_packed),
              XLA and Pallas digests of one batch against each other

It passes only on a TPU: any step that fails, times out or ran on another
platform makes it exit 1 with the step named on stderr and NO result on
stdout. A pass prints two stdout lines: `SUMMARY {...}` with every step's
seconds, counters, device report and compilations, then last the verdict,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}} with
exactly those keys.

One process for each chip: this parent never imports JAX (asserted before
exit) and its children run strictly one after another, each reaped before
the next starts. The environment is passed through, so every child shares
one compile cache: JAX_COMPILATION_CACHE_DIR if set, <checkout>/.jax_cache
otherwise (juicefs_tpu/tpu/device.py).

Rates printed here are host-clock observations of a smoke run, named as
such; they are not benchmark numbers and go nowhere else.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.request
from http.client import HTTPConnection, HTTPException

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

BLOCK = 4 << 20          # the volume's block size (format default)
OBJECT_BLOCKS = 16       # one 64 MiB object = one chunk = 16 blocks
POOL_BLOCKS = 4          # small seeded pool the duplicates are drawn from
DUP_PROBABILITY = 0.3    # BASELINE.json configs 1/2 (dup 0.3)
RAGGED_SIZES = (1, 100_001, BLOCK - 1, BLOCK + 7)  # lane masking, tail batch
TIME_LIMIT = 1150.0      # the contract allows 1200 s, compilation included
_T0 = time.monotonic()   # log lines and the summary count from here


# ---------------------------------------------------------------------------
# The plan: seed -> objects, the duplicate count they must produce, and the
# bytes of any block — the ground truth every check below is held to.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlannedBlock:
    """One block of one object. `content` identifies its bytes: two
    blocks are duplicates exactly when their content ids are equal."""
    content: tuple  # ("pool", i) | ("fresh", object_index, block_index)
    size: int


@dataclasses.dataclass(frozen=True)
class PlannedObject:
    name: str
    blocks: tuple[PlannedBlock, ...]

    @property
    def size(self) -> int:
        return sum(b.size for b in self.blocks)


@dataclasses.dataclass(frozen=True)
class Plan:
    seed: int
    objects: tuple[PlannedObject, ...]

    @property
    def blocks(self) -> list[PlannedBlock]:
        return [b for o in self.objects for b in o.blocks]

    @property
    def nbytes(self) -> int:
        return sum(o.size for o in self.objects)

    def content_counts(self) -> dict[tuple, int]:
        counts: dict[tuple, int] = {}
        for b in self.blocks:
            counts[b.content] = counts.get(b.content, 0) + 1
        return counts

    @property
    def expected_duplicates(self) -> int:
        """Blocks whose content appeared earlier in the volume: every
        occurrence of a content id past its first."""
        return sum(n - 1 for n in self.content_counts().values())


def make_plan(seed: int, big_objects: int) -> Plan:
    """`big_objects` objects of 64 MiB, each block drawn from the pool with
    probability 0.3 and fresh otherwise, plus the ragged handful."""
    rng = np.random.default_rng([seed, 0])
    objects = []
    for o in range(big_objects):
        blocks = []
        for b in range(OBJECT_BLOCKS):
            if rng.random() < DUP_PROBABILITY:
                content = ("pool", int(rng.integers(POOL_BLOCKS)))
            else:
                content = ("fresh", o, b)
            blocks.append(PlannedBlock(content, BLOCK))
        objects.append(PlannedObject(f"big-{o:04d}", tuple(blocks)))
    for k, size in enumerate(RAGGED_SIZES):
        o = big_objects + k
        sizes = [BLOCK] * (size // BLOCK) + ([size % BLOCK] if size % BLOCK else [])
        objects.append(PlannedObject(
            f"ragged-{size}",
            tuple(PlannedBlock(("fresh", o, b), s) for b, s in enumerate(sizes)),
        ))
    return Plan(seed, tuple(objects))


def block_bytes(seed: int, block: PlannedBlock) -> bytes:
    kind, *ids = block.content
    rng = np.random.default_rng([seed, 1 if kind == "pool" else 2, *ids])
    return rng.bytes(block.size)


def object_bytes(seed: int, obj: PlannedObject) -> bytes:
    return b"".join(block_bytes(seed, b) for b in obj.blocks)


def sample_blocks(plan: Plan, n: int = 16) -> list[PlannedBlock]:
    """A seeded sample of at least `n` distinct contents: every ragged
    block, every pool entry in use, and fresh full blocks up to `n`."""
    ragged = {b for o in plan.objects if o.name.startswith("ragged-")
              for b in o.blocks}  # the full block of 4 MiB + 7 included
    picked: dict[tuple, PlannedBlock] = {}
    fresh_full = []
    for b in plan.blocks:
        if b.content[0] == "pool" or b in ragged:
            picked.setdefault(b.content, b)
        else:
            fresh_full.append(b)
    rng = np.random.default_rng([plan.seed, 3])
    rng.shuffle(fresh_full)
    for b in fresh_full:
        if len(picked) >= n:
            break
        picked.setdefault(b.content, b)
    return list(picked.values())


def _load_by_path(name: str, relpath: str):
    """Import one repo module by file path, bypassing its package: the
    numpy spec and the cache helper import no JAX themselves, but
    `import juicefs_tpu.tpu` would (and the parent must stay off JAX)."""
    path = os.path.join(HERE, relpath)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_digests(seed: int, blocks: list[PlannedBlock]) -> dict[tuple, str]:
    """The plain reference: numpy `jth256()` over bytes recomputed from
    the plan, independent of every device path."""
    spec = _load_by_path("_jth256_spec", "juicefs_tpu/tpu/jth256.py")
    return {b.content: spec.jth256(block_bytes(seed, b)).hex() for b in blocks}


# ---------------------------------------------------------------------------
# Running children: one at a time, each in its own process group, each
# reaped (or killed) before the next starts.
# ---------------------------------------------------------------------------

class SmokeFailure(Exception):
    def __init__(self, step: str, message: str):
        super().__init__(f"{step}: {message}")
        self.step = step
        self.message = message


def check(cond: bool, step: str, message: str) -> None:
    if not cond:
        raise SmokeFailure(step, message)


class Children:
    """Starts, watches and stops the smoke's child processes."""

    def __init__(self, workdir: str, deadline: float):
        self.logdir = os.path.join(workdir, "logs")
        os.makedirs(self.logdir, exist_ok=True)
        self.deadline = deadline
        self.live: list[subprocess.Popen] = []
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = HERE + os.pathsep + self.env.get("PYTHONPATH", "")
        self.env["PYTHONUNBUFFERED"] = "1"
        # every compile request and every persistent-cache hit is logged
        # at WARNING; the parent counts the lines, it does not time them
        self.env["JAX_LOG_COMPILES"] = "1"

    def remaining(self, step: str, want: float) -> float:
        left = self.deadline - time.monotonic()
        check(left > 1, step, "out of time (the 1200 s limit is near)")
        return min(want, left)

    def start(self, name: str, argv: list[str]) -> subprocess.Popen:
        check(not self.live, name,
              "a child is still alive; children run one at a time")
        out = open(os.path.join(self.logdir, name + ".out"), "wb")
        err = open(os.path.join(self.logdir, name + ".err"), "wb")
        try:
            p = subprocess.Popen(argv, cwd=HERE, env=self.env, stdout=out,
                                 stderr=err, start_new_session=True)
        finally:
            out.close()
            err.close()
        p.smoke_name = name
        self.live.append(p)
        return p

    def reap(self, p: subprocess.Popen, timeout: float) -> int:
        """Wait for `p`; on timeout kill its whole process group."""
        try:
            rc = p.wait(timeout=self.remaining(p.smoke_name, timeout))
        except subprocess.TimeoutExpired:
            self.kill(p)
            raise SmokeFailure(p.smoke_name, f"timed out after {timeout:.0f} s")
        self.live.remove(p)
        return rc

    def kill(self, p: subprocess.Popen) -> None:
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        p.wait()
        if p in self.live:
            self.live.remove(p)

    def kill_all(self) -> None:
        for p in list(self.live):
            self.kill(p)

    def output(self, name: str) -> tuple[str, str]:
        def read(ext):
            with open(os.path.join(self.logdir, name + ext), "rb") as f:
                return f.read().decode("utf-8", "replace")
        return read(".out"), read(".err")

    def run(self, name: str, argv: list[str], timeout: float) -> dict:
        t0 = time.monotonic()
        rc = self.reap(self.start(name, argv), timeout)
        out, err = self.output(name)
        check(rc == 0, name, f"exit code {rc}; stderr tail:\n" + err[-3000:])
        return {"seconds": round(time.monotonic() - t0, 3), "stdout": out,
                "compiles": compile_counts(err)}


_COMPILE_EVENT = re.compile(
    r"(Finished XLA compilation of .*|Persistent compilation cache hit for .*"
    r"|Compiling \S+ with global shapes and types .*)$")
_HASH_BATCH = re.compile(r"uint32\[\d+,\d+,128,128\]")


def compile_counts(stderr: str) -> dict:
    """What JAX_LOG_COMPILES wrote: compile requests, how many the
    persistent cache answered, and the distinct batch shapes of the hash
    programs (the write-path indexer's B varies with upload timing).
    JAX's handler and the CLI's root handler both print each record, so
    adjacent repeats of one message are one event."""
    events: list[str] = []
    for line in stderr.splitlines():
        m = _COMPILE_EVENT.search(line)
        if m and (not events or events[-1] != m.group(1)):
            events.append(m.group(1))
    requests = sum(e.startswith("Finished XLA") for e in events)
    hits = sum(e.startswith("Persistent") for e in events)
    shapes = sorted({
        m.group(0) for e in events if e.startswith("Compiling")
        for m in [_HASH_BATCH.search(e)] if m})
    return {"requests": requests, "cache_hits": hits,
            "compiled": requests - hits, "hash_batch_shapes": shapes}


def cli(*args: str) -> list[str]:
    return [sys.executable, "-m", "juicefs_tpu.cmd", *args]


def last_json_line(text: str, prefix: str = "") -> dict:
    for line in reversed(text.splitlines()):
        if line.startswith(prefix + "{"):
            return json.loads(line[len(prefix):])
    raise ValueError(f"no {prefix!r} JSON line in output")


# ---------------------------------------------------------------------------
# The gateway leg: a real server answering real HTTP.
# ---------------------------------------------------------------------------

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_port(port: int, p: subprocess.Popen, timeout: float, step: str) -> None:
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if p.poll() is not None:
            raise SmokeFailure(step, f"gateway exited early (rc {p.returncode})")
        try:
            with socket.create_connection(("127.0.0.1", port), timeout=1):
                return
        except OSError:
            time.sleep(0.2)
    raise SmokeFailure(step, f"gateway did not listen within {timeout:.0f} s")


def scrape(port: int) -> tuple[dict[str, float], dict[str, dict]]:
    """Parse /metrics into {name: value} for unlabelled series and
    {name: labels} for labelled ones."""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
        text = r.read().decode()
    plain: dict[str, float] = {}
    labelled: dict[str, dict] = {}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        m = re.match(r"^(\w+)(\{.*\})? (\S+)$", line)
        if not m:
            continue
        if m.group(2):
            labelled[m.group(1)] = dict(
                re.findall(r'(\w+)="([^"]*)"', m.group(2)))
        else:
            plain[m.group(1)] = float(m.group(3))
    return plain, labelled


def request(port: int, method: str, path: str, body: bytes = b"",
            timeout: float = 120.0) -> tuple[int, bytes]:
    conn = HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body=body,
                     headers={"Content-Length": str(len(body))})
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


def serve_and_write(ch: Children, step: str, meta: str, plan: Plan,
                    indexed: bool) -> dict:
    """Start a gateway on `meta`, PUT every planned object, GET a sample
    back, read /metrics, SIGTERM it and wait for it to exit."""
    port, mport = free_port(), free_port()
    t0 = time.monotonic()
    p = ch.start(step, cli("gateway", meta, "--port", str(port),
                           "--metrics", f"127.0.0.1:{mport}"))
    try:
        wait_port(port, p, ch.remaining(step, 240), step)
        ready = time.monotonic() - t0
        status, _ = request(port, "PUT", "/smoke")
        check(status == 200, step, f"create bucket: HTTP {status}")
        t1 = time.monotonic()
        for obj in plan.objects:
            ch.remaining(step, 1)
            status, _ = request(port, "PUT", f"/smoke/{obj.name}",
                                object_bytes(plan.seed, obj))
            check(200 <= status < 300, step, f"PUT {obj.name}: HTTP {status}")
        put_seconds = time.monotonic() - t1
        # read back: the first big object and every ragged one
        sample = [plan.objects[0]] + [
            o for o in plan.objects if o.name.startswith("ragged-")]
        for obj in sample:
            status, got = request(port, "GET", f"/smoke/{obj.name}")
            check(status == 200, step, f"GET {obj.name}: HTTP {status}")
            check(got == object_bytes(plan.seed, obj), step,
                  f"GET {obj.name}: bytes differ from what was PUT")
        n_blocks = len(plan.blocks)
        plain, labelled = scrape(mport)
        if indexed:
            # the indexer hashes behind the uploads: wait until it has
            # accounted for every block before reading the counters
            end = time.monotonic() + ch.remaining(step, 180)
            while time.monotonic() < end:
                plain, labelled = scrape(mport)
                done = (plain.get("juicefs_index_blocks", 0)
                        + plain.get("juicefs_index_dropped_blocks", 0)
                        + plain.get("juicefs_index_errors", 0))
                if done >= n_blocks:  # persisted, dropped or failed
                    break
                time.sleep(0.25)
    except (OSError, HTTPException) as e:
        ch.kill(p)
        _, err = ch.output(step)
        raise SmokeFailure(step, f"HTTP to the gateway failed: {e!r}; "
                           "stderr tail:\n" + err[-3000:]) from e
    except BaseException:
        ch.kill(p)
        raise
    os.killpg(p.pid, signal.SIGTERM)
    rc = ch.reap(p, 180)
    _, err = ch.output(step)
    check(rc == 0, step, f"gateway exit code {rc} after SIGTERM; stderr tail:\n"
          + err[-3000:])
    result = {
        "seconds": round(time.monotonic() - t0, 3),
        "ready_seconds": round(ready, 3),
        "objects": len(plan.objects), "blocks": n_blocks,
        "bytes": plan.nbytes, "get_sample_objects": len(sample),
        "put_seconds": round(put_seconds, 3),
        "put_host_clock_gibs": round(plan.nbytes / (1 << 30) / put_seconds, 3),
        "compiles": compile_counts(err),
    }
    if not indexed:
        return result
    hashed = int(plain.get("juicefs_tpu_blocks_hashed", -1))
    indexed_rows = int(plain.get("juicefs_index_blocks", -1))
    dropped = int(plain.get("juicefs_index_dropped_blocks", -1))
    errors = int(plain.get("juicefs_index_errors", -1))
    device = labelled.get("juicefs_tpu_device_info", {})
    result.update(
        device=device,
        first_batch_seconds=plain.get("juicefs_tpu_first_batch_seconds"),
        counters={
            "juicefs_tpu_blocks_hashed": hashed,
            "juicefs_index_blocks": indexed_rows,
            "juicefs_index_dropped_blocks": dropped,
            "juicefs_index_errors": errors,
            "juicefs_tpu_h2d_bytes": int(plain.get("juicefs_tpu_h2d_bytes", 0)),
            "juicefs_tpu_shard_degraded": int(
                plain.get("juicefs_tpu_shard_degraded", -1)),
        })
    check(errors == 0, step, f"the indexer reported {errors} errors")
    check(hashed + dropped == n_blocks and indexed_rows == hashed, step,
          f"blocks_hashed {hashed} (index rows {indexed_rows}) + dropped "
          f"{dropped} != {n_blocks} written")
    check(result["counters"]["juicefs_tpu_h2d_bytes"] > 0, step,
          "juicefs_tpu_h2d_bytes is 0: nothing was shipped to a device")
    check(result["counters"]["juicefs_tpu_shard_degraded"] == 0, step,
          "juicefs_tpu_shard_degraded != 0")
    check(bool(device), step, "no juicefs_tpu_device_info in /metrics")
    return result


# ---------------------------------------------------------------------------
# Step 5's device child: what the CLI cannot show from outside.
# ---------------------------------------------------------------------------

_PLANE_CHILD = r"""
import json, sys
import numpy as np
import jax
from juicefs_tpu.tpu import sharding
from juicefs_tpu.tpu.dedup import dedup_digests
from juicefs_tpu.tpu.device import device_report
from juicefs_tpu.tpu.hash_jax import hash_packed_pallas, last_pallas_mode
from juicefs_tpu.tpu.jth256 import digests_to_bytes, jth256, pack_blocks

seed, BLOCK = int(sys.argv[1]), 4 << 20
rng = np.random.default_rng([seed, 9])
full = [rng.bytes(BLOCK) for _ in range(32)]
full[9], full[30] = full[2], full[2]              # planted duplicates
ragged = [rng.bytes(n) for n in (1, 100_001, BLOCK - 1, BLOCK, 7)]
ragged += [ragged[1], ragged[3]]                  # 7 rows: odd, 2 duplicates
plane = sharding.get_plane()
out = {"shards_ok": True, "digests_ok": True, "dedup_ok": True}
want_shard = None
for blocks in (full, ragged):
    packed = pack_blocks(blocks, pad_lanes=64)
    sp = plane.put_packed(*packed)
    shards = sp[0].addressable_shards
    b_pad = sp[0].shape[0]
    want_shard = (b_pad // plane.n_data, 64 // plane.n_lane, 128, 128)
    out["shards_ok"] &= (
        b_pad % plane.n_data == 0 and b_pad >= len(blocks)
        and len(shards) == plane.n_data * plane.n_lane
        and len({s.device.id for s in shards}) == len(shards)
        and all(tuple(s.data.shape) == want_shard for s in shards))
    d, dup, first = plane.scan_packed(*sp, n=sp.batch)
    got = digests_to_bytes(d)
    pal = digests_to_bytes(np.asarray(
        hash_packed_pallas(*packed)))[: len(blocks)]
    out["kernels_agree"] = out.get("kernels_agree", True) and got == pal
    for i in {0, 2, len(blocks) - 1, len(blocks) // 2}:
        out["digests_ok"] &= got[i] == jth256(blocks[i])
    hdup, hfirst = dedup_digests(got)
    out["dedup_ok"] &= list(dup) == list(hdup) and list(first) == list(hfirst)
    out.setdefault("duplicates", []).append(int(dup.sum()))
out["full_batch_shard_shape"] = [32 // plane.n_data, 64 // plane.n_lane, 128, 128]
out["pallas_mode"] = last_pallas_mode()
out["device"] = device_report("xla")
print("PLANE " + json.dumps(out))
"""


# ---------------------------------------------------------------------------
# The run.
# ---------------------------------------------------------------------------

def run(args, workdir: str, summary: dict) -> None:
    ch = Children(workdir, time.monotonic() + TIME_LIMIT)
    steps = summary["steps"]
    device_mod = _load_by_path("_device_helper", "juicefs_tpu/tpu/device.py")
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or device_mod.default_compile_cache_dir())

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    summary["compile_cache"] = {
        "dir": cache_dir,
        "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "entries_before": cache_entries(),
    }
    summary["compile_cache"]["state_on_entry"] = (
        "warm" if summary["compile_cache"]["entries_before"] else "cold")

    # the library is keyed on a hash of the committed jfscore.cpp
    # (native/__init__.py), so this builds it unless THIS source built it
    native = _load_native()
    summary["native_available"] = native.available()
    check(summary["native_available"], "native",
          "libjfscore did not build/load: CRC and the host hash would run "
          "on the pure-Python fallbacks")

    write_plan = make_plan(args.seed, max(1, round(args.write_gib * 16)))
    scan_plan = make_plan(args.seed + 1, max(1, round(args.scan_gib * 16)))
    vols = {}
    for name in ("w", "s"):
        os.makedirs(os.path.join(workdir, name))
        vols[name] = (f"sqlite3://{workdir}/{name}/meta.db",
                      f"{workdir}/{name}/blob/")
    try:
        # ---- 1. write leg through a server, indexer on the device ------
        r = ch.run("format-write", cli(
            "format", vols["w"][0], "smokew", "--storage", "file",
            "--bucket", vols["w"][1], "--trash-days", "0",
            "--hash-backend", args.backend), 240)
        fmt_report = last_json_line(r.pop("stdout"), "hash backend: ")
        r["device"] = fmt_report
        steps["format"] = r
        summary["device"] = {"platform": fmt_report["platform"],
                             "kind": fmt_report["device_kind"],
                             "count": fmt_report["visible_devices"]}
        summary["jax"] = fmt_report["jax"]
        log(f"device: {summary['device']} jax {summary['jax']}")
        steps["write"] = serve_and_write(
            ch, "gateway-write", vols["w"][0], write_plan, indexed=True)
        log(f"write: {brief(steps['write'])}")

        # ---- 2. cold gc --dedup on a volume with an empty index ---------
        steps["format-scan"] = ch.run("format-scan", cli(
            "format", vols["s"][0], "smokes", "--storage", "file",
            "--bucket", vols["s"][1], "--trash-days", "0"), 120)
        steps["format-scan"].pop("stdout")
        steps["fill"] = serve_and_write(
            ch, "gateway-fill", vols["s"][0], scan_plan, indexed=False)
        log(f"fill: {brief(steps['fill'])}")
        n_blocks = len(scan_plan.blocks)
        gc_args = cli("gc", vols["s"][0], "--dedup", "--hash-backend",
                      args.backend)
        r = ch.run("gc-cold", gc_args, 600)
        cold = last_json_line(r.pop("stdout"))
        r.update(stats=scan_stats(cold), device=cold["device"],
                 first_batch_seconds=cold["device"]["first_batch_seconds"])
        steps["cold"] = r
        log(f"cold: {brief(r)}")
        check(cold["blocks"] == n_blocks and cold["hashed_now"] == n_blocks,
              "gc-cold", f"blocks {cold['blocks']} hashed_now "
              f"{cold['hashed_now']}, planned {n_blocks}")
        check(cold["from_index"] == 0, "gc-cold",
              f"from_index {cold['from_index']} on an empty index")
        check(cold["duplicate_blocks"] == scan_plan.expected_duplicates,
              "gc-cold", f"duplicate_blocks {cold['duplicate_blocks']}, "
              f"planted {scan_plan.expected_duplicates}")
        check_scan_device(cold["device"], "gc-cold", summary)

        # ---- 3. warm: acknowledged digests are read back ----------------
        r = ch.run("gc-warm", gc_args, 300)
        warm = last_json_line(r.pop("stdout"))
        r.update(stats=scan_stats(warm), device=warm["device"])
        steps["warm"] = r
        log(f"warm: {brief(r)}")
        check(warm["from_index"] == warm["blocks"] == n_blocks
              and warm["hashed_now"] == 0, "gc-warm",
              f"from_index {warm['from_index']} hashed_now "
              f"{warm['hashed_now']}, blocks {warm['blocks']}")
        check(warm["duplicate_blocks"] == scan_plan.expected_duplicates,
              "gc-warm", "duplicate count changed between scans")

        # ---- 4. fsck with the other kernel, then the numpy spec ---------
        index_path = os.path.join(workdir, "F.json")
        r = ch.run("fsck-pallas", cli(
            "fsck", vols["s"][0], "--verify-data", "--hash-index",
            index_path, "--hash-backend", "pallas"), 600)
        out = r.pop("stdout")
        m = re.search(r"verified (\d+) blocks \((\w+)\); (\d+) indexed, "
                      r"(\d+) digest mismatches", out)
        check(m is not None, "fsck-pallas", "no 'verified' line:\n" + out[-500:])
        fsck_dev = last_json_line(out, "device: ")
        r.update(verified=int(m.group(1)), backend=m.group(2),
                 indexed=int(m.group(3)), mismatches=int(m.group(4)),
                 device=fsck_dev,
                 first_batch_seconds=fsck_dev["first_batch_seconds"])
        steps["fsck"] = r
        log(f"fsck: {brief(r)}")
        check(r["verified"] == r["indexed"] == n_blocks and r["mismatches"] == 0
              and r["backend"] == "pallas", "fsck-pallas",
              f"verified {r['verified']} indexed {r['indexed']} mismatches "
              f"{r['mismatches']} backend {r['backend']}, planned {n_blocks}")
        check(fsck_dev["devices"] == 1, "fsck-pallas",
              "the Pallas kernel runs on one device; the report claims "
              f"{fsck_dev['devices']}")
        t0 = time.monotonic()
        with open(index_path) as f:
            index = json.load(f)
        sample = sample_blocks(scan_plan, 16)
        ref = reference_digests(args.seed + 1, sample)
        counts = scan_plan.content_counts()
        seen: dict[tuple[str, int], int] = {}
        for key, hexd in index.items():
            k = (hexd, int(key.rsplit("_", 1)[1]))
            seen[k] = seen.get(k, 0) + 1
        for b in sample:
            got = seen.get((ref[b.content], b.size), 0)
            check(got == counts[b.content], "reference",
                  f"block {b.content} ({b.size} B): numpy jth256 "
                  f"{ref[b.content][:16]}… found {got}x in the fsck index, "
                  f"planned {counts[b.content]}x")
        steps["reference"] = {
            "seconds": round(time.monotonic() - t0, 3),
            "sampled_blocks": len(sample),
            "ragged_sizes": sorted({b.size for b in sample if b.size != BLOCK}),
        }
        log(f"reference: {steps['reference']}")

        # ---- 5. what the CLI cannot show: shards, device sort -----------
        r = ch.run("plane", [sys.executable, "-c", _PLANE_CHILD,
                             str(args.seed)], 300)
        plane = last_json_line(r.pop("stdout"), "PLANE ")
        r.update(plane)
        steps["plane"] = r
        log(f"plane: {brief(r)}")
        for k in ("shards_ok", "digests_ok", "dedup_ok", "kernels_agree"):
            check(plane[k] is True, "plane", f"{k} is {plane[k]}")
        check(plane["duplicates"] == [2, 2], "plane",
              f"device dedup found {plane['duplicates']}, planted [2, 2]")
        check_scan_device(plane["device"], "plane", summary)
    finally:
        ch.kill_all()

    # ---- the verdict: every device-path output must name a TPU ----------
    summary["compile_cache"]["entries_after"] = cache_entries()
    summary["compilations"] = {
        k: v["compiles"] for k, v in steps.items() if "compiles" in v}
    reports = {
        "format": steps["format"]["device"], "write": steps["write"]["device"],
        "cold": steps["cold"]["device"], "warm": steps["warm"]["device"],
        "fsck": steps["fsck"]["device"], "plane": steps["plane"]["device"],
    }
    for step, rep in reports.items():
        check(rep.get("platform") == "tpu", step,
              f"ran on platform {rep.get('platform')!r} "
              f"({rep.get('device_kind')!r}), not on a TPU")
    check(steps["fsck"]["device"]["pallas_mode"] == "compiled"
          and steps["plane"]["pallas_mode"] == "compiled", "fsck-pallas",
          "the Pallas kernel did not run compiled")


def check_scan_device(rep: dict, step: str, summary: dict) -> None:
    n = summary["device"]["count"]
    check(rep["visible_devices"] == n and rep["devices"] == n, step,
          f"plane spans {rep['devices']} of {rep['visible_devices']} "
          f"devices; {n} are visible")
    check(rep["degraded"] == (n == 1), step,
          f"degraded={rep['degraded']} ({rep['reason']!r}) on {n} devices")
    check(rep.get("shard_degraded") == 0, step,
          f"juicefs_tpu_shard_degraded == {rep.get('shard_degraded')}")
    if n == 4:
        check(rep["mesh"] == {"data": 2, "lane": 2}, step,
              f"mesh {rep['mesh']} on four devices")


def scan_stats(stats: dict) -> dict:
    keep = ("blocks", "bytes", "from_index", "hashed_now", "duplicate_blocks",
            "dedup_groups", "backend", "seconds", "stage_seconds")
    out = {k: stats[k] for k in keep}
    out["host_clock_gibs"] = stats["gibs"]
    return out


def brief(step: dict) -> str:
    skip = {"device", "compiles", "stats", "counters"}
    parts = {k: v for k, v in step.items() if k not in skip}
    if "stats" in step:
        parts.update({k: step["stats"][k] for k in
                      ("blocks", "hashed_now", "from_index",
                       "duplicate_blocks", "host_clock_gibs")})
    return json.dumps(parts)


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _load_native():
    sys.path.insert(0, HERE)
    import juicefs_tpu.native as native  # ctypes + g++ only, no JAX

    return native


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--write-gib", type=float, default=1.0,
                    help="GiB PUT through the gateway with the indexer on")
    ap.add_argument("--scan-gib", type=float, default=2.0,
                    help="GiB of 4 MiB blocks in the cold scan")
    ap.add_argument("--backend", default="tpu", choices=["tpu", "xla"],
                    help="xla lets the steps run on whatever JAX finds, to "
                         "debug this script; the verdict still fails off-TPU")
    ap.add_argument("--workdir", default="",
                    help="scratch parent directory (default: the system's)")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "juicefs_tpu")):
        print("chip_smoke: FAIL at step checkout: no juicefs_tpu/ beside "
              "this script — nothing to drive", file=sys.stderr)
        return 1
    workdir = tempfile.mkdtemp(prefix="jfs-smoke-", dir=args.workdir or None)
    summary: dict = {"ok": False, "device": None, "jax": None,
                     "seed": args.seed, "steps": {}}
    failure = None
    try:
        run(args, workdir, summary)
        summary["ok"] = True
    except SmokeFailure as e:
        failure = e
    except Exception:  # the boundary: any other error is a failed smoke
        failure = SmokeFailure("unexpected", traceback.format_exc())
    finally:
        summary["seconds"] = round(time.monotonic() - _T0, 3)
        outdir = os.path.join(HERE, "chiprun_out")
        os.makedirs(outdir, exist_ok=True)
        with open(os.path.join(outdir, "chip_smoke_summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
        if failure is not None and os.path.isdir(os.path.join(workdir, "logs")):
            shutil.copytree(os.path.join(workdir, "logs"),
                            os.path.join(outdir, "chip_smoke_logs"),
                            dirs_exist_ok=True)
        shutil.rmtree(workdir, ignore_errors=True)
    if "jax" in sys.modules:  # one process for each chip: never this one
        print("chip_smoke: FAIL at step parent: the parent imported JAX",
              file=sys.stderr)
        return 1
    if failure is not None:
        print(f"chip_smoke: FAIL at step {failure.step}: {failure.message}",
              file=sys.stderr)
        return 1
    print("SUMMARY " + json.dumps(summary))  # everything measured
    print(json.dumps(verdict(summary)))  # the last line, parsed strictly
    return 0


def verdict(summary: dict) -> dict:
    """The last stdout line of a pass: exactly `ok` and `device`, the
    device exactly as JAX reported it to the format child. Everything else
    the run measured is on the SUMMARY line before it."""
    d = summary["device"]
    return {"ok": summary["ok"],
            "device": {"platform": d["platform"], "kind": d["kind"],
                       "count": int(d["count"])}}


if __name__ == "__main__":
    sys.exit(main())
