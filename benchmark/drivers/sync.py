"""Traffic driver `sync`: an operator's mirror check, `sync SRC DST
--check-all --hash-backend B`, over a volume's bucket and its mirror, again
and again, in-process and closed-loop (one operator: the next pass starts when
the last has answered). What `drivers/scan.py` does for `gc --dedup` it does
here, on that driver's own window, clock and account of the work.

`prepare()` starts one child (`lib/mirror.py`) that builds the source volume
through the program's write path and then its mirror through the program's
own `sync`. No meta engine is on an op's path: a pass lists two stores, diffs
the listings and compares every pair by the digests of both objects. Nothing
is forgotten, copied or written between ops. Warm-up is one pass.

After the window, outside the clock, `check()` holds every digest of every
op's `--hash-index` to the numpy spec and every verdict to a plain byte
compare of the two directories (`lib/pair_compare.py`), then flips one seeded
byte of one destination object, runs one more pass, which has to exit 1
naming that key and no other (`mismatch_missed`), and puts the byte back.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import logging
import os
import sys
import time

import numpy as np

from benchmark.drivers import scan
from benchmark.drivers.fsck import _Heard  # what a command logged at error
from benchmark.lib import jth256_spec, mirror, pair_compare, volume
from benchmark.lib.plan import block_bytes

# what `sync` logs at error for a pair it found to differ
REPORTS = ("content mismatch: ", "verify failed after copy: ")


class Driver(scan.Driver):

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.src_dir, self.dst_dir = mirror.endpoints(self.workdir)
        # every pair is hashed on both sides
        self.hashed_keys: list[str] = []

    # -- set-up ------------------------------------------------------------

    def _argv(self, index_file: str) -> list[str]:
        dep = self.config["deployment"]
        return ["sync", "file://" + self.src_dir, "file://" + self.dst_dir,
                "--check-" + self.traffic["check"],
                "--hash-backend", dep["hash_backend"],
                "--threads", str(dep["threads"]),
                "--hash-index", index_file]

    def prepare(self) -> None:
        from juicefs_tpu.cmd import sync

        # a program whose `sync` cannot take the entry ends the run here,
        # before anything is started for it: exit 1 and no result line
        parser = argparse.ArgumentParser(prog="juicefs-tpu")
        sync.add_parser(parser.add_subparsers())
        try:
            with contextlib.redirect_stderr(io.StringIO()):
                parser.parse_args(self._argv("index.json"))
        except SystemExit:
            sys.exit("benchmark: refused: this program's `sync` does not take "
                     f"the entry {self.config['deployment']['entry']!r}")
        self.builder = mirror.start(self.workdir, self.config, self.seed)

    def setup(self, marks: dict) -> None:
        from juicefs_tpu.metric import global_registry

        t0 = time.perf_counter()
        builder, self.builder = self.builder, None
        _, self.block_of = volume.wait(builder, self.workdir, self.plan)
        marks["volume_wait_s"] = time.perf_counter() - t0
        self.log(f"volume and mirror: {len(self.block_of)} pairs, "
                 f"{self.plan.nbytes} B a side; waited "
                 f"{marks['volume_wait_s']:.1f} s for their builder")
        self.hashed_keys = list(self.block_of) * 2
        self.spans.wrap_tracer(scan.TRACER, "jfs.")
        self.warm_up()
        for line in global_registry().render().splitlines():
            if line.startswith("juicefs_tpu_first_batch_seconds "):
                marks["first_batch_s"] = float(line.split()[-1])

    def warm_up(self) -> None:
        """One pass: both shapes of the pass's program (a full batch and
        the tail) are built before the window."""
        op = self.one_op()
        if op["rc"] != 0 or op["stats"] is None:
            raise RuntimeError(f"warm-up op failed: {op}")
        self.n_ops = 0

    # -- one op ------------------------------------------------------------

    def one_op(self) -> dict:
        from juicefs_tpu.cmd import main

        index_file = os.path.join(self.workdir, f"index-{self.n_ops:05d}.json")
        heard = _Heard()
        log = logging.getLogger("cmd.sync")
        out = io.StringIO()
        log.addHandler(heard)
        t0 = time.perf_counter()
        try:
            with contextlib.redirect_stdout(out):
                rc = main(self._argv(index_file))
        finally:
            wall = time.perf_counter() - t0
            log.removeHandler(heard)
        stats = None
        lines = out.getvalue().strip().splitlines()
        if lines and lines[-1].startswith("{"):
            stats = json.loads(lines[-1])
        self.n_ops += 1
        if stats is not None and "stage_seconds" in stats:
            self.log("op %d: %.0f ms, rc %d, pass %.0f ms, stages %s" % (
                self.n_ops, wall * 1e3, rc, stats["seconds"] * 1e3,
                " ".join(f"{k}={v * 1e3:.0f}" for k, v in stats["stage_seconds"].items())))
        reported = sorted(m[len(p):] for m in heard.messages
                          for p in REPORTS if m.startswith(p))
        # `forgot`: the window's account of what an op hashed (drivers/scan.py)
        return {"rc": rc, "wall_s": wall, "stats": stats,
                "forgot": self.hashed_keys, "index_file": index_file,
                "reported": reported}

    # -- correct -----------------------------------------------------------

    def _mismatch_missed(self, want: dict) -> int:
        """One seeded byte of one destination object flipped (its size as
        it was), one more pass: 1 unless it exits 1 having reported that
        key, and no other, as a mismatch. The byte is put back."""
        rng = np.random.default_rng([self.seed, 7])
        key = sorted(self.block_of)[int(rng.integers(len(self.block_of)))]
        at = int(rng.integers(self.block_of[key].size))
        path = os.path.join(self.dst_dir, key)

        def flip():
            with open(path, "r+b") as f:
                f.seek(at)
                byte = f.read(1)
                f.seek(at)
                f.write(bytes([byte[0] ^ 0x10]))

        flip()
        try:
            op = self.one_op()
        finally:
            flip()
        stats = op["stats"]
        differing = []
        if os.path.exists(op["index_file"]):
            with open(op["index_file"]) as f:
                got = json.load(f)
            differing = [k for k, sides in got.items()
                         if sides["src"] != sides["dst"]]
            # the source's side is still the spec's, the other one not
            if got.get(key, {}).get("src") != [want[key]]:
                differing.append("source of " + key)
        found = (op["rc"] == 1 and stats is not None
                 and stats["mismatch"] == 1 and stats["skipped"] == 0
                 and stats["checked"] == len(want)
                 and op["reported"] == [key] and differing == [key])
        self.log(f"mismatch: byte {at} of {key} flipped in the mirror; pass rc "
                 f"{op['rc']}, reported {op['reported']}")
        return 0 if found else 1

    def check(self, window: dict, run: dict) -> dict:
        """Every answer of every op of the window against the plain
        references: numpy JTH-256 over each distinct content of the plan,
        and the byte compare of the two trees. All comparisons are exact,
        so every limit is 0."""
        t0 = time.perf_counter()
        ref = {}
        for b in self.block_of.values():
            if b.content not in ref:
                ref[b.content] = jth256_spec.jth256(block_bytes(self.seed, b)).hex()
        # the plan's block objects, one store object each, one range each
        want = {key: ref[b.content] for key, b in self.block_of.items()}
        both = {key: {"src": [d], "dst": [d]} for key, d in want.items()}
        truth = pair_compare.compare(self.src_dir, self.dst_dir)
        n_pairs = len(want)
        device = run["device"]
        chips = device["count"]
        dep = self.config["deployment"]
        on_device = dep["hash_backend"] in ("tpu", "xla")

        ops_failed = counts_wrong = reports_wrong = 0
        digests_wrong = pairs_wrong = 0
        for op in window["ops"]:
            stats = op["stats"]
            if op["rc"] != 0 or stats is None:
                ops_failed += 1
            if stats is None:
                continue  # nothing answered: nothing more to hold it to
            if (stats["checked"], stats["mismatch"], stats["copied"],
                    stats["skipped"], stats.get("hashed_now"),
                    stats.get("checked_bytes")) != (
                    n_pairs, 0, 0, 0, 2 * n_pairs, self.plan.nbytes):
                counts_wrong += 1
            rep = stats.get("device", {})
            if (rep.get("platform") != device["platform"]
                    or rep.get("backend") != ("xla" if on_device else dep["hash_backend"])
                    or rep.get("devices") != chips
                    or rep.get("visible_devices") != chips
                    or rep.get("degraded") != (chips == 1)
                    or rep.get("shard_degraded") != 0):
                reports_wrong += 1
            got = {}
            if os.path.exists(op["index_file"]):
                with open(op["index_file"]) as f:
                    got = json.load(f)
            for k in both.keys() | got.keys():
                sides = got.get(k, {})
                digests_wrong += sum(
                    1 for side in ("src", "dst")
                    if sides.get(side) != both.get(k, {}).get(side))
            # the pass's verdict for a key: compared, and reported or not
            said = {k: "differ" if k in op["reported"] else "equal" for k in got}
            said.update({k: "differ" for k in op["reported"]})
            pairs_wrong += sum(1 for k in truth.keys() | said.keys()
                               if truth.get(k) != said.get(k))

        h2d = "juicefs_tpu_h2d_bytes"
        shipped = (run["registry_after"].get(h2d, 0.0)
                   - run["registry_before"].get(h2d, 0.0))
        short = max(0.0, window["work"]["hashed_user_bytes"] - shipped)
        mismatch_missed = self._mismatch_missed(want)
        window["reference_s"] = time.perf_counter() - t0
        names = ("ops_failed", "op_counts_wrong", "device_reports_wrong",
                 "digests_wrong", "pairs_wrong", "h2d_bytes_short",
                 "mismatch_missed")
        values = (ops_failed, counts_wrong, reports_wrong, digests_wrong,
                  pairs_wrong, short, mismatch_missed)
        return {n: {"value": v, "limit": 0} for n, v in zip(names, values)}

