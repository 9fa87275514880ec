#!/usr/bin/env python3
"""The control of the comparison that decides `correct`, on the chip at the
cell's own size:

    python3 benchmark/control.py --workload N --seeds 1,2,3 [--seconds 6]

For each seed one process builds the cell's volume once and drives short
windows of the cell's own mix: sound, then the control (the configuration's
`hash_backend` set to `cpu`: the program's own host hash in the device's
place), then each planted fault the cell can have (benchmark/lib/faults.py).
The sound window has to come out correct and every other one not; the last
stdout line says whether they did, and which compared numbers each failed.
A benchmark run never comes here.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.lib import faults  # noqa: E402
from benchmark.lib.spans import Spans  # noqa: E402

CONTROL = "host_hash"


def one_seed(r: dict, seed: int, seconds: float, device_check, log,
             root: str = ROOT, only=None) -> dict:
    """{window name: the compared numbers that exceeded their limit}"""
    driver = run.load_by_path(os.path.join(
        r["bench_dir"], "drivers", r["traffic"]["driver"] + ".py"))
    os.makedirs(os.path.join(root, ".bench_work"), exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="control-", dir=os.path.join(root, ".bench_work"))
    config = copy.deepcopy(r["config"])
    drv = driver.Driver(config, r["traffic"], seed, workdir, Spans(False), log)
    failed = {}
    try:
        drv.prepare()
        device = device_check(r["cell"]["chips"])
        drv.setup({})
        names = ["sound", CONTROL] + [
            f for f in faults.FAULTS
            if f != "exchange_left_out" or r["cell"]["chips"] > 1]
        names = [n for n in names if only is None or n in only]
        sound_backend = config["deployment"]["hash_backend"]
        for name in names:
            drv.warm_up()  # a clean index, whatever the last window left
            before = run.registry_snapshot()
            if name == CONTROL:
                config["deployment"]["hash_backend"] = "cpu"
                window = drv.window(seconds)
                config["deployment"]["hash_backend"] = sound_backend
            elif name == "sound":
                window = drv.window(seconds)
            else:
                with faults.plant(name):
                    window = drv.window(seconds)
            compared = drv.check(window, {
                "registry_before": before,
                "registry_after": run.registry_snapshot(), "device": device})
            failed[name] = {k: c["value"] for k, c in compared.items()
                            if c["value"] > c["limit"]}
            log(f"seed {seed} {name}: "
                + ("correct" if not failed[name] else f"NOT correct {failed[name]}"))
    finally:
        drv.release()
        shutil.rmtree(workdir, ignore_errors=True)
    return failed


def main(argv=None, device_check=run.require_tpu) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--only", default=None,
                    help="comma-separated windows (default: all the cell can have)")
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(f"[control +{run.since_process_start():6.1f}s] {msg}",
              file=sys.stderr, flush=True)

    try:
        r = run.resolve(ROOT, args.workload)
        only = args.only.split(",") if args.only else None
        results = {str(seed): one_seed(r, seed, args.seconds, device_check, log,
                                       only=only)
                   for seed in map(int, args.seeds.split(","))}
    except run.Refused as e:
        print(f"control: refused: {e}", file=sys.stderr)
        return 1
    ok = all(bool(failed) == (name != "sound")
             for by_name in results.values() for name, failed in by_name.items())
    print(json.dumps({"workload": args.workload, "ok": ok, "failed": results}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
