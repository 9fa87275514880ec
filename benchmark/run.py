#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload N --seed S --seconds T --trace 0|1

Runs one cell of BENCHMARK.json on the machine it is started on and prints,
as the last line of stdout, one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device` (and `breakdown` with --trace 1), then
`compared` — every number the correctness check compared, beside its limit.

It is driven by data. A workload names a configuration file and a traffic
file; the traffic file names its driver (`drivers/<name>.py`); each per-layer
metric has a file `layer_metrics/<metric>.json` naming a reader
(`readers/<reader>.py`) and its arguments. Nothing here knows a cell, a mix
or a metric by name (README.md says how a later PR adds each).

Without a TPU, or with another number of chips than the cell states, it
exits 1 and prints no result.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:  # started as a script: sys.path[0] is benchmark/
    sys.path.insert(0, ROOT)


class Refused(Exception):
    """The run cannot be a measurement: exit 1, no result line."""


def since_process_start() -> float:
    """Seconds since this process was created (its start time in
    /proc/self/stat, in clock ticks since boot, against CLOCK_BOOTTIME)."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    return (time.clock_gettime(time.CLOCK_BOOTTIME)
            - ticks / os.sysconf("SC_CLK_TCK"))


def load_by_path(path: str):
    name = "_bench_" + os.path.relpath(path, ROOT).replace(os.sep, "_")[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(root: str, workload: str) -> dict:
    """A workload name -> its cell, configuration, traffic mix and the
    metric entries it reports, all found by name from BENCHMARK.json."""
    manifest = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    config_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    bench_dir = os.path.join(root, manifest["paths"][0])
    config = read_json(os.path.join(root, config_entry["file"]))
    traffic = read_json(os.path.join(bench_dir, "traffic", cell["traffic"] + ".json"))

    def reported(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    end_to_end = [m for m in manifest["end_to_end"] if reported(m)]
    moved = {m["name"] for m in end_to_end}
    per_layer = [m for m in manifest["per_layer"]
                 if reported(m) and m["moves"] in moved]
    return {"manifest": manifest, "cell": cell, "config": config,
            "traffic": traffic, "bench_dir": bench_dir,
            "end_to_end": end_to_end, "per_layer": per_layer}


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; anything but `chips` TPU chips refuses."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX found no backend: {e}") from e
    if devs[0].platform != "tpu" or len(devs) != chips:
        raise Refused(f"the cell needs {chips} TPU chip(s); JAX found "
                      f"{len(devs)} x {devs[0].platform} ({devs[0].device_kind})")
    return device_info(devs)


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts every program JAX builds or loads from the persistent cache
    (one backend_compile event each), so that the window can show none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0

    def install(self) -> None:
        import jax.monitoring

        def on_event(event: str, duration: float, **kw) -> None:
            if event == self.EVENT:
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)


def registry_snapshot() -> dict[str, float]:
    """The program's metrics registry through its exposition text:
    `name{labels}` -> value (histograms give `_sum`, `_count`, `_bucket`)."""
    from juicefs_tpu.metric import global_registry

    snap = {}
    for line in global_registry().render().splitlines():
        if line and not line.startswith("#"):
            series, _, value = line.rpartition(" ")
            try:
                snap[series] = float(value)
            except ValueError:
                pass
    return snap


def run_readers(bench_dir: str, per_layer: list[dict], ctx: dict,
                log=lambda msg: None) -> dict:
    """Each per-layer metric through its own reader; one that finds
    nothing to read is left out of the line."""
    out = {}
    for metric in per_layer:
        spec = read_json(os.path.join(
            bench_dir, "layer_metrics", metric["name"] + ".json"))
        reader = load_by_path(os.path.join(
            bench_dir, "readers", spec["reader"] + ".py"))
        value = reader.read(ctx, **spec.get("args", {}))
        if value is None:
            log(f"metric {metric['name']}: nothing to read")
        else:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def main(argv=None, root: str = ROOT, device_check=require_tpu) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(f"[bench +{since_process_start():6.1f}s] {msg}",
              file=sys.stderr, flush=True)

    workdir = None
    try:
        if not os.path.isdir(os.path.join(root, "juicefs_tpu")):
            raise Refused("no juicefs_tpu/ beside the benchmark: nothing to drive")
        r = resolve(root, args.workload)
        os.makedirs(os.path.join(root, ".bench_work"), exist_ok=True)
        workdir = tempfile.mkdtemp(prefix="run-", dir=os.path.join(root, ".bench_work"))
        from benchmark.lib.spans import Spans

        driver = load_by_path(os.path.join(
            r["bench_dir"], "drivers", r["traffic"]["driver"] + ".py"))
        spans = Spans(enabled=bool(args.trace))
        drv = driver.Driver(r["config"], r["traffic"], args.seed, workdir, spans, log)
        try:
            drv.prepare()  # what can start before the process has the chip
            counter = CompileCounter()
            counter.install()
            device = device_check(r["cell"]["chips"])
            marks = {"device_ready_s": since_process_start()}
            log(f"device {device}")
            result = measure(args, r, drv, spans, device, counter, marks, workdir, log)
        finally:
            spans.restore()
            drv.release()
    except Refused as e:
        print(f"benchmark: refused: {e}", file=sys.stderr)
        return 1
    finally:
        if workdir is not None:
            shutil.rmtree(workdir, ignore_errors=True)
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def measure(args, r, drv, spans, device, counter, marks, workdir, log) -> dict:
    from benchmark.lib import xtrace

    trace = None
    drv.setup(marks)
    compiles_before = counter.n
    reg_before = registry_snapshot()
    setup_s = since_process_start()
    log(f"set-up done ({counter.n} programs built or loaded); window "
        f"{args.seconds:g} s")
    if args.trace:
        trace_dir = os.path.join(workdir, "trace")
        xtrace.start(trace_dir)
    t0 = time.perf_counter()
    with spans.span(xtrace.WINDOW_SPAN):
        window = drv.window(args.seconds)
    window_s = time.perf_counter() - t0
    if args.trace:
        trace = xtrace.stop_and_reduce(
            trace_dir, device["count"] if device["platform"] == "tpu" else 0)
    reg_after = registry_snapshot()
    peak = memory_peak_bytes()
    spans.restore()
    drv.release()  # the program's state goes before the reference runs
    compared = drv.check(window, {"registry_before": reg_before,
                                  "registry_after": reg_after,
                                  "device": device})
    correct = all(c["value"] <= c["limit"] for c in compared.values())

    end_to_end = dict(drv.end_to_end(window, window_s))
    end_to_end["setup_s"] = setup_s
    device_out = dict(device, memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": None, "device": device_out}
    if args.trace:
        ctx = {"ops": window["ops"], "work": window["work"], "marks": marks,
               "registry_before": reg_before, "registry_after": reg_after,
               "spans": spans.durations, "trace": trace, "device": device,
               "values": {"compiles_in_window": counter.n - compiles_before,
                          "memory_peak_bytes": peak}}
        result["metrics"] = run_readers(r["bench_dir"], r["per_layer"], ctx, log)
        device_out.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
        result["end_to_end_while_traced"] = end_to_end
    else:
        result["metrics"] = {m["name"]: {"value": float(end_to_end[m["name"]]),
                                         "unit": m["unit"]} for m in r["end_to_end"]}
    result["window_s"] = window_s
    result["op_wall_ms"] = [round(op["wall_s"] * 1e3, 1) for op in window["ops"][:100]]
    result["reference_s"] = window.get("reference_s")
    result["compared"] = compared
    return result


if __name__ == "__main__":
    sys.exit(main())
