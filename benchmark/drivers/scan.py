"""Traffic driver `scan`: an operator's `gc --dedup` over one volume, again
and again, in-process and closed-loop (one operator, the next scan starts
when the last has answered; a child for each op would pay the whole process
start and the chip belongs to one process).

Set-up builds the volume from the seed and runs the mix once. Before each op
the mix `forget`s content-index rows — "all" (a cold scan: every block is
fetched, packed, shipped, hashed, backfilled) or a fixed count of seeded
full-size blocks (an incremental scan: the rest is read back from the
index) — so every op starts from the same meta state. The forgetting is
inside the window's time and outside the op's.

The window ends with the first op that completes at or after `--seconds`:
whole ops only, and the rate is taken over the time they really took.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import statistics
import time

import numpy as np

from benchmark.lib import jth256_spec, volume
from benchmark.lib.plan import block_bytes, plan_of

GIB = float(1 << 30)

# where a traced run puts the benchmark's spans (benchmark/lib/spans.py)
CALL_SPANS = {
    "juicefs_tpu.tpu.pipeline:pack_blocks": "jfs.tpu.pack_blocks",
    "juicefs_tpu.tpu.pipeline:digests_to_bytes": "jfs.tpu.digests_to_bytes",
    "juicefs_tpu.tpu.sharding:ShardPlane.put_packed": "jfs.tpu.put_packed",
    "juicefs_tpu.tpu.sharding:ShardPlane.hash_async": "jfs.tpu.hash_async",
    "juicefs_tpu.cmd.gc:reconcile_content_refs": "jfs.cmd.reconcile_content_refs",
}
ITERATOR_SPANS = {
    "juicefs_tpu.chunk.parallel:fetch_ordered": "jfs.chunk.fetch_wait",
}
TRACER = "juicefs_tpu.metric.trace:Tracer.span"


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, workdir: str,
                 spans, log=lambda msg: None):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.workdir, self.spans, self.log = workdir, spans, log
        self.plan = plan_of(seed, config["volume"])
        self.builder = None
        self.meta_url = None
        self.block_of: dict = {}                     # block key -> PlannedBlock
        self.meta = None
        self.rows: dict[str, tuple[int, int]] = {}   # block key -> (slice, index)
        self.forgettable: list[str] = []
        self.n_ops = 0
        self.opened: list = []
        self._uncapture: list = []

    # -- set-up ------------------------------------------------------------

    def prepare(self) -> None:
        """Before the process reaches for the chip: the volume builder
        starts, as a child, and builds while JAX comes up."""
        self.builder = volume.start(self.workdir, self.config, self.seed)

    def setup(self, marks: dict) -> None:
        from juicefs_tpu.chunk.cached_store import parse_block_key
        from juicefs_tpu.cmd import open_meta
        from juicefs_tpu.metric import global_registry

        t0 = time.perf_counter()
        builder, self.builder = self.builder, None
        self.meta_url, self.block_of = volume.wait(builder, self.workdir, self.plan)
        marks["volume_wait_s"] = time.perf_counter() - t0
        self.log(f"volume: {len(self.block_of)} blocks, {self.plan.nbytes} B; "
                 f"waited {marks['volume_wait_s']:.1f} s for its builder")
        self._open_meta = open_meta  # the program's, before ops are captured
        self.meta, _ = open_meta(self.meta_url)
        self._capture_opened()
        for key in self.block_of:
            sid, indx, _ = parse_block_key(key)
            self.rows[key] = (sid, indx)
        full = self.config["volume"]["block_bytes"]
        self.forgettable = sorted(
            k for k, b in self.block_of.items() if b.size == full)
        for target, name in CALL_SPANS.items():
            self.spans.wrap_call(target, name)
        meta_cls = type(self.meta)
        self.spans.wrap_call(
            f"{meta_cls.__module__}:{meta_cls.__name__}.set_block_digests",
            "jfs.meta.set_block_digests")
        for target, name in ITERATOR_SPANS.items():
            self.spans.wrap_iterator(target, name)
        self.spans.wrap_tracer(TRACER, "jfs.")

        self.warm_up()
        gauge = global_registry().render()
        for line in gauge.splitlines():
            if line.startswith("juicefs_tpu_first_batch_seconds "):
                marks["first_batch_s"] = float(line.split()[-1])

    def warm_up(self) -> None:
        """The mix once. An incremental mix first needs its index full,
        which only a cold scan can make it."""
        forgets = ["all"] if self.traffic["forget"] != "all" else []
        for forget in forgets + [None]:
            op = self.one_op(forget=forget)
            if op["rc"] != 0 or op["stats"] is None:
                raise RuntimeError(f"warm-up op failed: {op}")
        self.n_ops = 0

    # -- one op ------------------------------------------------------------

    def _forget(self, what) -> list[str]:
        if what == "all":
            keys = list(self.rows)
        else:
            rng = np.random.default_rng([self.seed, 5, self.n_ops])
            picked = rng.choice(len(self.forgettable), size=int(what), replace=False)
            keys = [self.forgettable[i] for i in sorted(picked)]
        self.meta.delete_block_digests([self.rows[k] for k in keys])
        return keys

    def _capture_opened(self) -> None:
        """`gc` leaves its store and meta client open for the exit of its
        process to clean up. Here the process lives on, so what each op
        opened is noted and closed once the op has answered: without that
        every op leaves ten pool threads behind, and ops speed up over the
        first 25 as glibc runs out of fresh arenas to give them."""
        import juicefs_tpu.cmd as cmd

        for name in ("build_store", "open_meta"):
            old = getattr(cmd, name)
            self._uncapture.append((cmd, name, old))

            def capture(*a, _old=old, **kw):
                got = _old(*a, **kw)
                self.opened.append(got)
                return got
            setattr(cmd, name, capture)

    def _close_opened(self) -> None:
        while self.opened:
            got = self.opened.pop()
            if isinstance(got, tuple):   # open_meta -> (client, format)
                got[0].close_session()
            else:
                got.close()

    def one_op(self, forget=None) -> dict:
        from juicefs_tpu.cmd import main

        forgot = self._forget(self.traffic["forget"] if forget is None else forget)
        index_file = os.path.join(self.workdir, f"index-{self.n_ops:05d}.json")
        argv = ["gc", self.meta_url, "--dedup",
                "--hash-backend", self.config["deployment"]["hash_backend"],
                "--threads", str(self.config["deployment"]["threads"]),
                "--dedup-index", index_file]
        out = io.StringIO()
        t0 = time.perf_counter()
        with contextlib.redirect_stdout(out):
            rc = main(argv)
        wall = time.perf_counter() - t0
        self._close_opened()
        stats = None
        lines = out.getvalue().strip().splitlines()
        if rc == 0 and lines and lines[-1].startswith("{"):
            stats = json.loads(lines[-1])
        self.n_ops += 1
        if stats is not None:
            self.log("op %d: %.0f ms, scan %.0f ms, stages %s" % (
                self.n_ops, wall * 1e3, stats["seconds"] * 1e3,
                " ".join(f"{k}={v * 1e3:.0f}" for k, v in stats["stage_seconds"].items())))
        return {"rc": rc, "wall_s": wall, "stats": stats, "forgot": forgot,
                "index_file": index_file}

    # -- the window --------------------------------------------------------

    def window(self, seconds: float) -> dict:
        ops = []
        t0 = time.perf_counter()
        while True:
            with self.spans.span("jfs.bench.op"):
                ops.append(self.one_op())
            if time.perf_counter() - t0 >= seconds:
                break
        failed = sum(1 for op in ops if op["rc"] != 0 or op["stats"] is None)
        sizes = [self.block_of[k].size for op in ops for k in op["forgot"]]
        work = {
            "ops": len(ops) - failed,  # ops that answered
            "hashed_blocks": len(sizes),
            "hashed_user_bytes": sum(sizes),
            # what the hash has to read at the least: whole 64 KiB lanes
            "hashed_lane_bytes": sum(
                jth256_spec.lanes_of(n) * jth256_spec.LANE_BYTES for n in sizes),
        }
        return {"ops": ops, "attempted": len(ops), "failed": failed, "work": work}

    def end_to_end(self, window: dict, window_s: float) -> dict:
        ok = [op for op in window["ops"] if op["rc"] == 0 and op["stats"]]
        return {
            "scan_gibs": len(ok) * self.plan.nbytes / GIB / window_s,
            "op_p50_ms": statistics.median(
                op["wall_s"] for op in window["ops"]) * 1e3,
        }

    def release(self) -> None:
        if self.builder is not None:
            self.builder.kill()
            self.builder.wait()
            self.builder = None
        while self._uncapture:
            owner, name, old = self._uncapture.pop()
            setattr(owner, name, old)
        if self.meta is not None:
            self.meta.close_session()
            self.meta = None

    # -- correct -----------------------------------------------------------

    def check(self, window: dict, run: dict) -> dict:
        """Every answer of every op of the window against the plain
        reference: numpy JTH-256 over each distinct content of the plan.
        All comparisons are exact, so every limit is 0."""
        from juicefs_tpu.chunk.cached_store import block_key

        t0 = time.perf_counter()
        # acknowledged rows, read back from the meta engine by a new client
        meta, _ = self._open_meta(self.meta_url)
        try:
            rows = {block_key(sid, indx, bsize): digest.hex()
                    for sid, indx, bsize, digest in meta.scan_block_digests()}
        finally:
            meta.close_session()
        ref = {}
        for b in self.block_of.values():
            if b.content not in ref:
                ref[b.content] = jth256_spec.jth256(block_bytes(self.seed, b)).hex()
        want = {key: ref[b.content] for key, b in self.block_of.items()}
        n_blocks = len(want)
        device = run["device"]
        chips = device["count"]

        ops_failed = counts_wrong = dups_wrong = reports_wrong = digests_wrong = 0
        for op in window["ops"]:
            stats = op["stats"]
            if op["rc"] != 0 or stats is None:
                ops_failed += 1
                continue
            hashed = len(op["forgot"])
            if (stats["blocks"], stats["hashed_now"], stats["from_index"]) != (
                    n_blocks, hashed, n_blocks - hashed):
                counts_wrong += 1
            if stats["duplicate_blocks"] != self.plan.expected_duplicates:
                dups_wrong += 1
            rep = stats["device"]
            if (rep.get("platform") != device["platform"]
                    or rep.get("devices") != chips
                    or rep.get("visible_devices") != chips
                    or rep.get("degraded") != (chips == 1)
                    or rep.get("shard_degraded") != 0):
                reports_wrong += 1
            with open(op["index_file"]) as f:
                got = json.load(f)
            digests_wrong += sum(1 for k in want.keys() | got.keys()
                                 if want.get(k) != got.get(k))

        rows_wrong = sum(1 for k in want.keys() | rows.keys()
                         if want.get(k) != rows.get(k))

        h2d = "juicefs_tpu_h2d_bytes"
        shipped = (run["registry_after"].get(h2d, 0.0)
                   - run["registry_before"].get(h2d, 0.0))
        short = max(0.0, window["work"]["hashed_user_bytes"] - shipped)
        window["reference_s"] = time.perf_counter() - t0
        names = ("ops_failed", "op_counts_wrong", "duplicate_counts_wrong",
                 "device_reports_wrong", "digests_wrong", "index_rows_wrong",
                 "h2d_bytes_short")
        values = (ops_failed, counts_wrong, dups_wrong, reports_wrong,
                  digests_wrong, rows_wrong, short)
        return {n: {"value": v, "limit": 0} for n, v in zip(names, values)}
