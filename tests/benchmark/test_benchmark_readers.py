"""Each reader on a hand-made context: the number it gives, and nothing
where there is nothing to read (never a 0)."""

import os

import pytest

from benchmark import run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def reader(name):
    return run.load_by_path(os.path.join(REPO, "benchmark", "readers", name + ".py"))


OPS = [{"wall_s": 1.0, "stats": {"seconds": 0.8, "hashed_now": 10,
                                 "stage_seconds": {"get": 0.2, "get_threads": 1.0}}},
       {"wall_s": 3.0, "stats": {"seconds": 2.0, "hashed_now": 30,
                                 "stage_seconds": {"get": 0.4, "get_threads": 5.0}}}]
S = 'juicefs_tpu_stage_seconds_%s{layer="tpu",op="hash",stage="drain"}'
CTX = {
    "ops": OPS, "work": {"hashed_user_bytes": 1000, "hashed_lane_bytes": 819e9 * 0.002},
    "marks": {"device_ready_s": 10.0, "first_batch_s": 0.5},
    "values": {"compiles_in_window": 0, "memory_peak_bytes": None},
    "spans": {"jfs.tpu.pack_blocks": [0.1, 0.3]},
    "registry_before": {S % "sum": 1.0, S % "count": 10.0, "juicefs_tpu_h2d_bytes": 500.0},
    "registry_after": {S % "sum": 1.6, S % "count": 12.0, "juicefs_tpu_h2d_bytes": 1600.0},
    "trace": {"busy_by_device": {"/device:TPU:0": 0.5}, "window_s": 10.0,
              "busiest_busy_s": 0.5, "program_s": 0.01, "programs": 4},
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
}


@pytest.mark.parametrize("name,args,want", [
    ("op_stat", {"field": "wall_s", "minus": "stats.seconds", "scale": 1000}, 600.0),
    ("op_stat", {"field": "wall_s", "reduce": "max", "scale": 1000}, 3000.0),
    ("op_stat", {"field": "stats.stage_seconds.get", "scale": 1000}, 300.0),
    ("op_stat", {"field": "stats.stage_seconds.get_threads", "per": "stats.hashed_now",
                 "reduce": "ratio_of_sums", "scale": 1000}, 150.0),
    ("op_stat", {"field": "stats.stage_seconds.absent"}, None),
    ("registry", {"kind": "histogram_mean", "series": "juicefs_tpu_stage_seconds",
                  "labels": {"layer": "tpu", "op": "hash", "stage": "drain"},
                  "scale": 1000}, 300.0),
    ("registry", {"kind": "histogram_mean", "series": "absent_seconds"}, None),
    ("registry", {"kind": "counter_gain", "series": "juicefs_tpu_h2d_bytes",
                  "per_work": "hashed_user_bytes"}, 1.1),
    ("value", {"sum_marks": ["device_ready_s", "first_batch_s"]}, 10.5),
    ("value", {"sum_marks": ["device_ready_s", "absent"]}, None),
    ("value", {"name": "compiles_in_window"}, 0.0),
    ("value", {"name": "memory_peak_bytes"}, None),
    ("span", {"name": "jfs.tpu.pack_blocks", "scale": 1000}, 200.0),
    ("span", {"name": "jfs.absent"}, None),
    ("trace", {"field": "program_s", "per": "programs", "scale": 1000}, 2.5),
    ("trace", {"idle_of": "busiest_busy_s"}, 95.0),
    ("roofline", {"work": "hashed_lane_bytes"}, 20.0),
])
def test_reader(name, args, want):
    got = reader(name).read(CTX, **args)
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("name,args", [
    ("trace", {"field": "program_s", "per": "programs"}),
    ("trace", {"idle_of": "busiest_busy_s"}),
    ("roofline", {"work": "hashed_lane_bytes"})])
def test_device_readers_give_nothing_without_a_device_trace(name, args):
    for trace in (None, {"busy_by_device": {}, "window_s": 1.0, "busiest_busy_s": 0.0,
                         "program_s": 0.0, "programs": 0}):
        assert reader(name).read(dict(CTX, trace=trace), **args) is None


def test_an_unknown_device_kind_has_no_peak():
    ctx = dict(CTX, device={"platform": "tpu", "kind": "TPU v9", "count": 1})
    with pytest.raises(KeyError):
        reader("roofline").read(ctx, work="hashed_lane_bytes")
