"""Traffic driver `fsck`: an operator's scrub, `fsck --verify-data`, over one
volume whose metadata a server of its own holds, again and again, in-process
and closed-loop (one operator: the next scrub starts when the last has
answered). What `drivers/scan.py` does for `gc --dedup` it does here, on that
driver's own window, clock and bookkeeping of what each op opened.

`prepare()` starts the meta server (the program's `meta-server`, a child on
a free loopback port with its append-only file in the workdir) and then the
volume builder against it. Set-up fills the content index with one cold `gc
--dedup` on the XLA program, so that every scrub holds the digests of its
own kernel to rows another program wrote, and warms with one scrub. An op
rereads and rehashes every block: nothing is forgotten between ops and
nothing is answered from the index.

After the window, outside the clock, `check()` flips one seeded byte of one
stored object, runs one more scrub, which has to exit 1 naming that block
and no other (`bitrot_missed`), and puts the byte back. It reads the index
rows from a server started anew on the append-only file where the run has
already let go of its own.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import io
import json
import logging
import os
import select
import subprocess
import sys
import time

import numpy as np

from benchmark.drivers import scan
from benchmark.lib import jth256_spec, volume, volume_served
from benchmark.lib.plan import block_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LISTENING = "meta-server listening on "


class Driver(scan.Driver):

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.server = None
        self.aof = os.path.join(self.workdir, "meta.aof")

    # -- the meta server ---------------------------------------------------

    def _start_server(self) -> None:
        """The program's own `meta-server` as a child on a free loopback
        port; `self.meta_url` names it once it listens."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")  # never the parent's chip
        env["PYTHONPATH"] = os.pathsep.join(
            [ROOT] + [p for p in [env.get("PYTHONPATH")] if p])
        self.server = subprocess.Popen(
            [sys.executable, "-m", "juicefs_tpu.cmd", "meta-server",
             "--host", "127.0.0.1", "--port", "0", "--data", self.aof,
             "--fsync", "everysec"],
            env=env, stdout=subprocess.PIPE, text=True)
        ready, _, _ = select.select([self.server.stdout], [], [], 120)
        line = self.server.stdout.readline() if ready else ""
        if not line.startswith(LISTENING):
            self._stop_server()
            raise RuntimeError(f"the meta server did not come up: {line!r}")
        port = int(line[len(LISTENING):].split()[0].rsplit(":", 1)[1])
        db = self.config["deployment"]["meta_db"]
        self.meta_url = f"redis://127.0.0.1:{port}/{db}"

    def _stop_server(self) -> None:
        server, self.server = self.server, None
        if server is not None:
            server.kill()
            server.wait()
            server.stdout.close()

    # -- set-up ------------------------------------------------------------

    def _argv(self, meta_url: str, index_file: str) -> list[str]:
        dep = self.config["deployment"]
        return ["fsck", meta_url, "--verify-data",
                "--hash-index", index_file,
                "--hash-backend", dep["hash_backend"],
                "--threads", str(dep["threads"])]

    def prepare(self) -> None:
        from juicefs_tpu.cmd import fsck

        # a program whose `fsck` cannot take the entry ends the run here,
        # before anything is started for it: exit 1 and no result line
        parser = argparse.ArgumentParser(prog="juicefs-tpu")
        fsck.add_parser(parser.add_subparsers())
        try:
            parser.parse_args(self._argv("redis://", "index.json"))
        except SystemExit:
            sys.exit("benchmark: refused: this program's `fsck` does not take "
                     f"the entry {self.config['deployment']['entry']!r}")
        self._start_server()
        self.builder = volume_served.start(
            self.workdir, self.config, self.seed, self.meta_url)

    def setup(self, marks: dict) -> None:
        from juicefs_tpu.cmd import open_meta
        from juicefs_tpu.metric import global_registry

        t0 = time.perf_counter()
        builder, self.builder = self.builder, None
        _, self.block_of = volume.wait(builder, self.workdir, self.plan)
        marks["volume_wait_s"] = time.perf_counter() - t0
        self.log(f"volume: {len(self.block_of)} blocks, {self.plan.nbytes} B; "
                 f"waited {marks['volume_wait_s']:.1f} s for its builder")
        self._open_meta = open_meta  # the program's, before ops are captured
        self._capture_opened()
        self.spans.wrap_tracer(scan.TRACER, "jfs.")
        self._fill_index()
        self.warm_up()
        for line in global_registry().render().splitlines():
            if line.startswith("juicefs_tpu_first_batch_seconds "):
                marks["first_batch_s"] = float(line.split()[-1])

    def _fill_index(self) -> None:
        """One cold `gc --dedup` on the XLA program writes every row of the
        content index: what the scrubs then hold their own digests to."""
        from juicefs_tpu.cmd import main

        dep = self.config["deployment"]
        argv = ["gc", self.meta_url, "--dedup",
                "--hash-backend", dep["index_backend"],
                "--threads", str(dep["threads"])]
        with contextlib.redirect_stdout(io.StringIO()):
            rc = main(argv)
        self._close_opened()
        if rc != 0:
            raise RuntimeError(f"filling the index failed: {argv}")

    def warm_up(self) -> None:
        """One scrub: both shapes of the scrub's program (a full batch and
        the tail) are built before the window."""
        op = self.one_op()
        if op["rc"] != 0 or op["stats"] is None:
            raise RuntimeError(f"warm-up op failed: {op}")
        self.n_ops = 0

    # -- one op ------------------------------------------------------------

    def _close_opened(self) -> None:
        clients = [got[0] for got in self.opened if isinstance(got, tuple)]
        super()._close_opened()
        for meta in clients:  # the op's connection to the server goes too
            meta.client.close()

    def one_op(self) -> dict:
        from juicefs_tpu.cmd import main

        index_file = os.path.join(self.workdir, f"index-{self.n_ops:05d}.json")
        out = io.StringIO()
        t0 = time.perf_counter()
        with contextlib.redirect_stdout(out):
            rc = main(self._argv(self.meta_url, index_file))
        wall = time.perf_counter() - t0
        self._close_opened()
        stats = None
        lines = out.getvalue().strip().splitlines()
        if lines and lines[-1].startswith("{"):
            stats = json.loads(lines[-1])
        self.n_ops += 1
        if stats is not None:
            self.log("op %d: %.0f ms, rc %d, verify %.0f ms, stages %s" % (
                self.n_ops, wall * 1e3, rc, stats["seconds"] * 1e3,
                " ".join(f"{k}={v * 1e3:.0f}" for k, v in stats["stage_seconds"].items())))
        # a scrub hashes every block, whatever the index holds
        return {"rc": rc, "wall_s": wall, "stats": stats,
                "forgot": list(self.block_of), "index_file": index_file}

    def release(self) -> None:
        super().release()
        self._stop_server()

    # -- correct -----------------------------------------------------------

    @contextlib.contextmanager
    def _served(self):
        """The meta server for the check: the run's own where it still
        stands (control.py), else one started anew on the append-only
        file, which then also shows that what was acknowledged is there."""
        if self.server is not None:
            yield
            return
        self._start_server()
        try:
            yield
        finally:
            self._stop_server()

    def _stored_file(self, key: str) -> str:
        found = glob.glob(os.path.join(
            self.workdir, "blob", "**", os.path.basename(key)), recursive=True)
        if len(found) != 1:
            raise RuntimeError(f"block {key}: stored as {found}")
        return found[0]

    def _bitrot_missed(self, want: dict) -> int:
        """One seeded byte of one stored object flipped, one more scrub:
        1 unless it exits 1 having reported that block, and no other, as a
        digest mismatch. The byte is put back."""
        rng = np.random.default_rng([self.seed, 7])
        key = sorted(self.block_of)[int(rng.integers(len(self.block_of)))]
        at = int(rng.integers(self.block_of[key].size))
        path = self._stored_file(key)

        def flip():
            with open(path, "r+b") as f:
                f.seek(at)
                byte = f.read(1)
                f.seek(at)
                f.write(bytes([byte[0] ^ 0x10]))

        heard = _Heard()
        log = logging.getLogger("cmd.fsck")
        flip()
        log.addHandler(heard)
        try:
            op = self.one_op()
        finally:
            log.removeHandler(heard)
            flip()
        stats = op["stats"]
        mismatches = [m for m in heard.messages if "digest mismatch" in m]
        with open(op["index_file"]) as f:
            got = json.load(f)
        differing = [k for k in want.keys() | got.keys() if want.get(k) != got.get(k)]
        found = (op["rc"] == 1 and stats is not None
                 and stats["mismatches"] == 1 and stats["broken"] == 1
                 and len(mismatches) == 1 and f"block {key} " in mismatches[0]
                 and differing == [key])
        self.log(f"bitrot: byte {at} of {key} flipped; scrub rc {op['rc']}, "
                 f"reported {mismatches}")
        return 0 if found else 1

    def check(self, window: dict, run: dict) -> dict:
        """Every answer of every op of the window against the plain
        reference: numpy JTH-256 over each distinct content of the plan.
        All comparisons are exact, so every limit is 0."""
        from juicefs_tpu.chunk.cached_store import block_key

        t0 = time.perf_counter()
        ref = {}
        for b in self.block_of.values():
            if b.content not in ref:
                ref[b.content] = jth256_spec.jth256(block_bytes(self.seed, b)).hex()
        want = {key: ref[b.content] for key, b in self.block_of.items()}
        n_blocks = len(want)
        device = run["device"]
        dep = self.config["deployment"]
        kernel_mode = None  # what the report says of the Pallas kernel
        if dep["hash_backend"] == "pallas":
            kernel_mode = "compiled" if device["platform"] == "tpu" else "interpret"

        ops_failed = counts_wrong = reports_wrong = digests_wrong = 0
        for op in window["ops"]:
            stats = op["stats"]
            if op["rc"] != 0 or stats is None:
                ops_failed += 1
            if stats is None:
                continue  # nothing answered: nothing more to hold it to
            if (stats["blocks"], stats["verified"], stats["hashed_now"],
                    stats["indexed"], stats["mismatches"], stats["broken"]) != (
                    n_blocks, n_blocks, n_blocks, n_blocks, 0, 0):
                counts_wrong += 1
            rep = stats["device"]
            if (rep.get("platform") != device["platform"]
                    or rep.get("backend") != dep["hash_backend"]
                    or rep.get("pallas_mode") != kernel_mode
                    or (kernel_mode and rep.get("devices") != 1)
                    or rep.get("visible_devices") != device["count"]):
                reports_wrong += 1
            with open(op["index_file"]) as f:
                got = json.load(f)
            digests_wrong += sum(1 for k in want.keys() | got.keys()
                                 if want.get(k) != got.get(k))

        with self._served():
            # acknowledged rows, read back from the server by a new client
            meta, _ = self._open_meta(self.meta_url)
            try:
                rows = {block_key(sid, indx, bsize): digest.hex()
                        for sid, indx, bsize, digest in meta.scan_block_digests()}
            finally:
                meta.close_session()
                meta.client.close()
            bitrot_missed = self._bitrot_missed(want)
        rows_wrong = sum(1 for k in want.keys() | rows.keys()
                         if want.get(k) != rows.get(k))

        h2d = "juicefs_tpu_h2d_bytes"
        shipped = (run["registry_after"].get(h2d, 0.0)
                   - run["registry_before"].get(h2d, 0.0))
        short = max(0.0, window["work"]["hashed_user_bytes"] - shipped)
        window["reference_s"] = time.perf_counter() - t0
        names = ("ops_failed", "op_counts_wrong", "device_reports_wrong",
                 "digests_wrong", "index_rows_wrong", "h2d_bytes_short",
                 "bitrot_missed")
        values = (ops_failed, counts_wrong, reports_wrong, digests_wrong,
                  rows_wrong, short, bitrot_missed)
        return {n: {"value": v, "limit": 0} for n, v in zip(names, values)}


class _Heard(logging.Handler):
    """What `fsck` reported through its logger during one scrub."""

    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.messages: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.messages.append(record.getMessage())
