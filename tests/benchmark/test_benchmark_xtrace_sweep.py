"""`xtrace.reduce` attributes idle gaps in one sweep, and `load` keeps the
host's threads apart. The double loop that the sweep replaced, and the stack
that closed a span by reversing a list twice, are kept here as the plain
reference: the same output to 1e-9 s on seeded random planes (thousands of
gaps and nested segments, spans that straddle gaps, zero-length spans, the
window's edges, spans that are not nested at all) and on the trace recorded
on the chip; and a window of 30 small-file ops reduces in seconds."""

import os
import random
import time

import pytest

from benchmark.lib import xtrace

HERE = os.path.dirname(os.path.abspath(__file__))
W = xtrace.WINDOW_SPAN


def innermost_segments_by_list(events):
    """benchmark/lib/xtrace.py:innermost_segments as PR 24 wrote it."""
    marks = []
    for name, s, d in events:
        marks.append((s, 1, -d, name))
        marks.append((s + d, 0, 0.0, name))
    marks.sort()
    out, stack, at = [], [], None
    for t, opens, _, name in marks:
        if stack and t > at:
            out.append((at, t, stack[-1]))
        if opens:
            stack.append(name)
        elif name in stack:
            stack.reverse()
            stack.remove(name)
            stack.reverse()
        at = t
    return out


def gap_seconds_by_double_loop(idle, segments):
    """Every segment for every gap, as `reduce` did it up to PR 27."""
    out = {}
    for gs, ge in idle:
        covered = 0.0
        for ss, se, name in segments:
            overlap = min(ge, se) - max(gs, ss)
            if overlap > 0:
                out[name] = out.get(name, 0.0) + overlap
                covered += overlap
        if ge - gs - covered > 0:
            out[xtrace.UNATTRIBUTED] = (
                out.get(xtrace.UNATTRIBUTED, 0.0) + ge - gs - covered)
    return out


def idle_and_spans(planes):
    """What `reduce` hands to the attribution: the busiest device's idle
    gaps in the window, and the feeding thread's spans."""
    host = planes[xtrace.HOST_PLANE]
    (line, (_, lo, length)), = [(line, e) for line, events in host.items()
                                for e in events if e[0] == W]
    busy = {name: xtrace.merge(xtrace.clip(lines["XLA Ops"], lo, lo + length))
            for name, lines in planes.items()
            if name.startswith("/device:TPU:") and lines.get("XLA Modules")}
    busiest = max(busy, key=lambda name: xtrace.union_seconds(busy[name]))
    spans = [e for e in host[line]
             if e[0].startswith(xtrace.SPAN_PREFIX) and e[0] != W]
    return xtrace.gaps(busy[busiest], lo, lo + length), spans


def reference_gap_seconds(planes):
    idle, spans = idle_and_spans(planes)
    return gap_seconds_by_double_loop(idle, innermost_segments_by_list(spans))


def same_seconds(got, want):
    assert set(got) == set(want)
    for name in want:
        assert got[name] == pytest.approx(want[name], abs=1e-9), name


def random_planes(seed, nested, zero_length):
    """Window [100, 130] on a 1 ms grid (so that starts and ends coincide).
    The feeding thread holds a few thousand spans from a pool of six names:
    a tree (`nested`) or any intervals at all; some across the window's
    edges, same names inside each other, and with `zero_length` some of no
    length (such a span is never closed, then as now: it stays the outermost
    span to the end of the line, so nothing after it is unattributed). The
    device runs a few thousand ops, some overlapping, some across the edges."""
    rng = random.Random(seed)
    lo, hi = 100.0, 130.0
    names = ["jfs.a", "jfs.b", "jfs.c.d", "jfs.a.b", "jfs.e", "other.f"]
    lengths = [0, 0, 1, 3, 20, 200] if zero_length else [1, 2, 1, 3, 20, 200]
    spans = []

    def tree(s, e, depth):
        spans.append((rng.choice(names), s, e - s))
        at = s
        while depth < 6 and at < e:
            cs = at + rng.randrange(0, 8) / 1000
            if cs >= e:
                break
            ce = min(e, cs + rng.choice(lengths) / 1000)
            if rng.random() < 0.7:
                tree(cs, ce, depth + 1)
            at = ce + rng.choice([0, 0, 2]) / 1000

    if nested:
        at = lo - 0.5
        while at < hi + 0.5:
            end = at + rng.randrange(1, 1500) / 1000
            tree(at, end, 0)
            at = end + rng.choice([0, 0, 5, 50]) / 1000
    else:
        for _ in range(2000):
            s = lo - 1 + rng.randrange(0, 32000) / 1000
            spans.append((rng.choice(names), s, rng.choice(lengths + [100]) / 1000))
    ops, modules, at = [], [], lo - 0.2
    while at < hi + 0.2:
        n = rng.randrange(1, 12)
        start = at
        for _ in range(n):
            d = rng.choice([0, 1, 1, 2, 7]) / 1000
            ops.append((f"%op.{rng.randrange(5)} = u32[8] fusion(x)", at, d))
            at += d + rng.choice([0, 0, 0, 1]) / 1000 - rng.choice([0, 0, 1]) / 2000
        modules.append(("jit_hash(1)", start, at - start))
        at += rng.choice([0, 1, 3, 10, 30]) / 1000
    rng.shuffle(spans)
    return {
        xtrace.HOST_PLANE: {"python3#7": [(W, lo, hi - lo)] + spans,
                            "python3#8": [("jfs.object.get", lo, 29.0)]},
        "/device:TPU:0": {"XLA Modules": modules, "XLA Ops": ops},
    }


@pytest.mark.parametrize("nested", [True, False], ids=["nested", "any-intervals"])
@pytest.mark.parametrize("seed,zero_length", [
    (1, False), (2, False), (3, True), (2**31 + 5, True)])
def test_the_sweep_equals_the_double_loop_on_random_planes(seed, zero_length, nested):
    planes = random_planes(seed, nested, zero_length)
    idle, spans = idle_and_spans(planes)
    assert len(idle) > 1000 and len(spans) > 1000
    segments = xtrace.innermost_segments(spans)
    assert segments == innermost_segments_by_list(spans)
    assert all(a[1] <= b[0] for a, b in zip(segments, segments[1:]))
    want = gap_seconds_by_double_loop(idle, segments)
    same_seconds(xtrace.gap_seconds_by_segment(idle, segments), want)
    assert len(want) >= 5  # every `jfs.` name of the pool
    assert zero_length or want[xtrace.UNATTRIBUTED] > 0
    summary = xtrace.reduce(planes)
    assert summary["idle_gaps"] == xtrace.top(want)
    assert sum(want.values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"], abs=1e-6)
    assert "jfs.object.get" not in want  # another thread's span


def test_the_sweep_equals_the_double_loop_on_the_recorded_trace():
    planes = xtrace.load(os.path.join(HERE, "probe.xplane.pb"))
    want = reference_gap_seconds(planes)
    assert len(want) == 2
    same_seconds(dict(map(tuple, xtrace.reduce(planes)["idle_gaps"])), want)


def test_two_host_lines_of_one_name_stay_apart():
    """Every thread's line is named `python3`; `load` keys them by name and
    position (as here), and `reduce` takes the one that holds the window. A
    pool thread's GET, open over the whole window, names no gap."""
    planes = {
        xtrace.HOST_PLANE: {
            "python3#0": [("jfs.object.get", 10.0, 9.0),
                          ("jfs.chunk.load.fetch", 10.0, 10.0)],
            "python3#1": [(W, 10.0, 10.0), ("jfs.bench.op", 10.0, 6.0),
                          ("jfs.tpu.hash.pack", 13.0, 2.0)],
        },
        "/device:TPU:0": {
            "XLA Modules": [("jit_jth256_hash(1)", 11.0, 1.0)],
            "XLA Ops": [("%a.1 = u32[8] fusion(x)", 11.0, 1.0)]},
    }
    gaps = dict(map(tuple, xtrace.reduce(planes)["idle_gaps"]))
    assert gaps == {"jfs.bench.op": pytest.approx(1.0 + 1.0 + 1.0),
                    "jfs.tpu.hash.pack": pytest.approx(2.0),
                    xtrace.UNATTRIBUTED: pytest.approx(4.0)}


TWO_THREADS_OF_ONE_NAME = """
planes {
  name: "/host:CPU"
  lines { name: "python3" timestamp_ns: 10000000000
          events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000000000 } }
  lines { name: "python3" timestamp_ns: 10000000000
          events { metadata_id: 2 offset_ps: 0 duration_ps: 10000000000000 }
          events { metadata_id: 3 offset_ps: 1000000000000 duration_ps: 2000000000000 } }
  event_metadata { key: 1 value { id: 1 name: "jfs.object.get" } }
  event_metadata { key: 2 value { id: 2 name: "jfs.window" } }
  event_metadata { key: 3 value { id: 3 name: "jfs.bench.op" } }
}
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 10000000000
          events { metadata_id: 1 offset_ps: 500000000000 duration_ps: 1000000000000 } }
  lines { name: "XLA Ops" timestamp_ns: 10000000000
          events { metadata_id: 2 offset_ps: 500000000000 duration_ps: 1000000000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_jth256_hash(1)" } }
  event_metadata { key: 2 value { id: 2 name: "%a.1 = u32[8] fusion(x)" } }
}
"""


def test_load_keeps_two_host_lines_of_one_name_apart(tmp_path):
    """A trace file whose two host lines are both `python3`, as every
    thread's is on the chip machine: window [10, 20], an op [11, 13] on the
    window's thread, a GET [10, 19] on the other, the device busy [10.5, 11.5]."""
    from jax.profiler import ProfileData

    path = tmp_path / "two.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        TWO_THREADS_OF_ONE_NAME))
    planes = xtrace.load(str(path))
    assert {k: [e[0] for e in v] for k, v in planes[xtrace.HOST_PLANE].items()} == {
        "python3#0": ["jfs.object.get"], "python3#1": [W, "jfs.bench.op"]}
    assert set(planes["/device:TPU:0"]) == {"XLA Modules", "XLA Ops"}
    s = xtrace.reduce(planes)
    assert s["window_s"] == pytest.approx(10.0) and s["busy_s"] == pytest.approx(1.0)
    assert dict(map(tuple, s["idle_gaps"])) == {
        "jfs.bench.op": pytest.approx(1.5), xtrace.UNATTRIBUTED: pytest.approx(7.5)}


def test_load_keys_the_recorded_traces_host_lines_by_position():
    planes = xtrace.load(os.path.join(HERE, "probe.xplane.pb"))
    host = planes[xtrace.HOST_PLANE]
    assert len(host) == 20
    assert all(key.rsplit("#", 1)[1] == str(i) for i, key in enumerate(host))
    assert [k for k, v in host.items() if any(e[0] == W for e in v)] == ["python3#19"]
    assert set(planes["/device:TPU:0"]) >= {"XLA Modules", "XLA Ops"}


def small_file_window(ops=30, blocks=4165, programs=131):
    """A traced window of `ops` small-file scans, each 1 s: one span a block
    on the feeding thread under the op's, and `programs` programs of ten
    device ops each."""
    spans, device_ops, modules = [(W, 0.0, float(ops))], [], []
    for op in range(ops):
        spans.append(("jfs.bench.op", float(op), 1.0))
        step = 0.9 / blocks
        spans.extend(("jfs.chunk.fetch.wait", op + i * step, step * 0.8)
                     for i in range(blocks))
        step = 0.9 / programs
        for p in range(programs):
            start = op + p * step
            modules.append(("jit_jth256_hash(1)", start, step * 0.5))
            device_ops.extend((f"%f.{k} = u32[8] fusion(x)",
                               start + k * step * 0.05, step * 0.04)
                              for k in range(10))
    return {xtrace.HOST_PLANE: {"python3#3": spans},
            "/device:TPU:0": {"XLA Modules": modules, "XLA Ops": device_ops}}


def test_a_window_of_thirty_small_file_ops_reduces_in_seconds():
    """The double loop would compare 39,000 gaps with 250,000 segments
    (minutes); the sweep read 0.6 s here (my CPU run, PR 29). The limit is
    generous: the test run shares its cores."""
    planes = small_file_window()
    t0 = time.perf_counter()
    summary = xtrace.reduce(planes)
    took = time.perf_counter() - t0
    assert summary["programs"] == 30 * 131
    gaps = dict(map(tuple, summary["idle_gaps"]))
    assert set(gaps) == {"jfs.bench.op", "jfs.chunk.fetch.wait"}
    assert sum(gaps.values()) == pytest.approx(30.0 - summary["busy_s"])
    assert took < 20.0, took
