"""The reduction from a profiler trace to device numbers, on a synthetic
trace with answers worked out by hand and on a small trace recorded on the
chip (PR 24: six 32-block batches through HashPipeline on one TPU v5 lite,
three outer spans of two batches each)."""

import os

import pytest

from benchmark.lib import xtrace

HERE = os.path.dirname(os.path.abspath(__file__))
W = xtrace.WINDOW_SPAN


def synthetic(second_device=False):
    """Window [10, 20]. Device 0 runs two programs: [11, 12] (ops [11, 11.6],
    [11.6, 12]) and [15, 15.5] (one op), and one before the window opens."""
    planes = {
        "/host:CPU": {
            "feeder": [(W, 10.0, 10.0), ("jfs.outer", 10.0, 6.0),
                       ("jfs.pack", 10.5, 0.5), ("jfs.pack", 13.0, 2.0),
                       ("jfs.drain", 15.0, 0.5), ("other.span", 16.0, 1.0)],
            "pool-1": [("jfs.object.get", 10.0, 9.0)],
        },
        "/device:TPU:0": {
            "XLA Modules": [("jit_hash(1)", 9.0, 0.5), ("jit_hash(1)", 11.0, 1.0),
                            ("jit_hash(1)", 15.0, 0.5)],
            "XLA Ops": [("%a.1 = u32[8] fusion(x)", 9.0, 0.5),
                        ("%a.1 = u32[8] fusion(x)", 11.0, 0.6),
                        ("%b = u32[8] while(y)", 11.6, 0.4),
                        ("%a.1 = u32[8] fusion(x)", 15.0, 0.5)],
        },
        "/device:TPU:9-empty": {"XLA Modules": []},
    }
    if second_device:
        planes["/device:TPU:1"] = {
            "XLA Modules": [("jit_hash(1)", 11.0, 0.5)],
            "XLA Ops": [("%a.1 = u32[8] fusion(x)", 11.0, 0.5)]}
    return planes


def test_busy_union_idle_and_program_time():
    s = xtrace.reduce(synthetic())
    assert s["window_s"] == pytest.approx(10.0)
    assert s["busy_by_device"] == {"/device:TPU:0": pytest.approx(1.5)}
    assert s["busy_s"] == pytest.approx(1.5)
    assert s["program_s"] == pytest.approx(1.5) and s["programs"] == 2
    assert dict(map(tuple, s["device_ops"])) == {
        "a.1": pytest.approx(1.1), "b": pytest.approx(0.4)}


def test_idle_gaps_go_to_the_innermost_span_of_the_feeding_thread():
    gaps = dict(map(tuple, xtrace.reduce(synthetic())["idle_gaps"]))
    # idle: [10, 11], [12, 15], [15.5, 20]; pool threads' spans do not count
    assert gaps == {
        "jfs.pack": pytest.approx(0.5 + 2.0),
        "jfs.outer": pytest.approx(0.5 + 1.0 + 0.5),  # [10,10.5] [12,13] [15.5,16]
        xtrace.UNATTRIBUTED: pytest.approx(4.0),      # [16, 20]
    }
    assert sum(gaps.values()) == pytest.approx(10.0 - 1.5)


def test_several_chips_average_busy_and_keep_the_busiest():
    s = xtrace.reduce(synthetic(second_device=True))
    assert s["busy_s"] == pytest.approx((1.5 + 0.5) / 2)
    assert s["busiest_busy_s"] == pytest.approx(1.5)
    assert s["program_s"] == pytest.approx((1.5 + 0.5) / 2) and s["programs"] == 2


def test_a_trace_without_exactly_one_window_is_refused():
    planes = synthetic()
    planes["/host:CPU"]["feeder"] = planes["/host:CPU"]["feeder"][1:]
    with pytest.raises(RuntimeError):
        xtrace.reduce(planes)


@pytest.mark.parametrize("intervals,total", [
    ([(0, 1), (0.5, 2), (3, 4)], 3.0), ([(1, 1), (2, 1)], 0.0), ([], 0.0)])
def test_union_seconds(intervals, total):
    assert xtrace.union_seconds(intervals) == pytest.approx(total)


def test_recorded_tpu_trace():
    s = xtrace.reduce(xtrace.load(os.path.join(HERE, "probe.xplane.pb")))
    assert list(s["busy_by_device"]) == ["/device:TPU:0"]
    assert s["programs"] == 6
    assert s["window_s"] == pytest.approx(1.105008263)
    assert s["program_s"] == pytest.approx(5.013984e-3, rel=1e-6)
    assert s["busy_s"] <= s["program_s"] and s["busy_s"] > 0.9 * s["program_s"]
    assert s["device_ops"][0][0] == "copy_bitcast_fusion"
    # 128 MiB a batch at 819 GB/s over 0.8355 ms: a fifth of the roofline
    share = 6 * (128 << 20) / 819e9 / s["program_s"]
    assert 0.19 < share < 0.20
    gaps = dict(map(tuple, s["idle_gaps"]))
    assert set(gaps) == {"jfs.outer", xtrace.UNATTRIBUTED}
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"])
