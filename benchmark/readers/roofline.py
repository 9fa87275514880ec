"""A kernel's share of its roofline, in percent: the least time the chips
could take for the bytes the harness saw hashed (`work` names the driver's
count, a function of the blocks' shapes; the peak comes from
benchmark/lib/peaks.json by `device_kind`, an unknown kind is an error),
over the programs' device time in the trace. The bound is bytes: JTH-256 is
uint32 multiply/xor/rotate on the VPU, for which no peak is published."""

import json
import os


def read(ctx, work):
    trace = ctx.get("trace")
    if not trace or not trace.get("program_s"):
        return None
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "lib", "peaks.json")) as f:
        peaks = json.load(f)["device_kinds"]
    kind = ctx["device"]["kind"]
    if kind not in peaks:
        raise KeyError(f"no peak for device_kind {kind!r} in lib/peaks.json")
    bytes_per_s = peaks[kind]["hbm_bytes_per_s"] * ctx["device"]["count"]
    least_s = ctx["work"][work] / bytes_per_s
    return 100.0 * least_s / trace["program_s"]
