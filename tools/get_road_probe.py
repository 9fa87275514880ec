"""What a block's GET is made of in an untraced benchmark run.

`benchmark/run.py` reads per-layer metrics only with `--trace 1`, but it
snapshots the program's metrics registry before and after the window either
way. This runs one cell through `run.main` in this process, untraced (the
program unpatched, no profiler), keeps those two snapshots and the window's
ops, and then reads the metrics that split `object.get_ms` from them with
the benchmark's own metric files and readers — the same numbers a traced
line carries, without the tracing's cost in them. It prints the run's own
result line, then one line `GET_ROAD {...}`: each metric (ms, or % for the
hedge and inline shares; a metric whose series the program lacks is left
out) and the parts no series times, by subtraction:

  `load_block_own_ms`  `object.get_ms` - `chunk.load_fetch_ms`: the
                       singleflight, cache and staged lookups of
                       `_load_block` (cells with a chunk store)
  `gate_shed_ms`       `chunk.load_fetch_ms` - resilient queue - store -
                       wake: breaker gate, shed, hedge-delay quantile,
                       the submit, decompress and the length check
  `wrappers_ms`        `object.get_ms` - resilient queue - store - wake
                       (the mirror check: no chunk store)
  `store_off_cpu_ms`   `object.store_get_ms` - `object.store_get_cpu_ms`

    python tools/get_road_probe.py --workload scan-cold --seed N \\
        [--seconds 30] [--root CHECKOUT]

`--root` runs another checkout's program and harness (e.g. a parent commit
unpacked beside this one); the metric files and readers are this
checkout's. Needs what the benchmark needs: a TPU with the cell's chips.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRICS = ("object.get_ms", "chunk.fetch_queue_ms", "chunk.load_fetch_ms",
           "object.resilient_queue_ms", "object.resilient_wake_ms",
           "object.store_get_ms", "object.store_get_cpu_ms",
           "object.hedged_share", "entry.parse_ms_per_op",
           "object.inline_get_share")


def parts(m: dict) -> dict:
    """The differences no series times, where their terms were read."""
    out = {}
    hops = ("object.resilient_queue_ms", "object.store_get_ms",
            "object.resilient_wake_ms")
    inner = sum(m[k] for k in hops) if all(k in m for k in hops) else None
    if "chunk.load_fetch_ms" in m:
        if "object.get_ms" in m:
            out["load_block_own_ms"] = m["object.get_ms"] - m["chunk.load_fetch_ms"]
        if inner is not None:
            out["gate_shed_ms"] = m["chunk.load_fetch_ms"] - inner
    elif inner is not None and "object.get_ms" in m:
        out["wrappers_ms"] = m["object.get_ms"] - inner
    if "object.store_get_ms" in m and "object.store_get_cpu_ms" in m:
        out["store_off_cpu_ms"] = m["object.store_get_ms"] - m["object.store_get_cpu_ms"]
    return out


def main(argv=None, device_check=None) -> int:
    """`device_check` stands in for the benchmark's look for the chip
    (tests only)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--root", default=HERE)
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    from benchmark import run

    snaps, windows = [], []
    snapshot, load = run.registry_snapshot, run.load_by_path

    def recorded_snapshot():
        snaps.append(snapshot())
        return snaps[-1]

    def recorded_load(path):
        mod = load(path)
        if os.path.basename(os.path.dirname(path)) == "drivers":
            window = mod.Driver.window

            def kept(self, seconds):
                windows.append(window(self, seconds))
                return windows[-1]
            mod.Driver.window = kept
        return mod

    run.registry_snapshot, run.load_by_path = recorded_snapshot, recorded_load
    try:
        rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds), "--trace", "0"],
                      root=root, device_check=device_check or run.require_tpu)
    finally:
        run.registry_snapshot, run.load_by_path = snapshot, load
        sys.path.remove(root)
    if rc != 0 or len(snaps) != 2 or not windows:
        return rc or 1
    w = windows[-1]
    ctx = {"ops": w["ops"], "work": w["work"], "registry_before": snaps[0],
           "registry_after": snaps[1]}
    manifest = run.read_json(os.path.join(HERE, "BENCHMARK.json"))
    entries = [e for e in manifest["per_layer"] if e["name"] in METRICS
               and args.workload in e.get("workloads", [args.workload])]
    got = run.run_readers(os.path.join(HERE, "benchmark"), entries, ctx)
    m = {k: v["value"] for k, v in got.items()}
    print("GET_ROAD " + json.dumps(dict(m, **parts(m), ops=len(w["ops"]))),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
