"""The reduction from an xplane trace to device metrics, against a
hand-made two-chip trace whose answers are worked out by hand, against a
small trace recorded on the chip (PR 23), against what the event-by-event
reduction of PR 23-30 read of the three kept traces (written down from
that code before PR 32 replaced it with arrays), and against that
reduction's plain loops, kept here, on seeded random intervals."""

import bisect
import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

from harness import tracing, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1e-6


@pytest.fixture(scope="module")
def handmade():
    return xplane.load(os.path.join(DATA, "two_chip_handmade.xplane.txt"))


def test_interval_arithmetic():
    assert xplane.union([(0, 2), (1, 3), (5, 6)]).tolist() == \
        [[0, 3], [5, 6]]
    assert xplane.union(iter([(5, 6), (0, 2), (2, 3)])).tolist() == \
        [[0, 3], [5, 6]]
    assert xplane.union([]).shape == (0, 2)
    assert xplane.total([(0, 3), (5, 6)]) == 4 and xplane.total([]) == 0
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7)]).tolist() == \
        [[0, 2], [3, 5], [7, 10]]
    assert xplane.subtract([(0, 4), (6, 9)], [(3, 7)]).tolist() == \
        [[0, 3], [7, 9]]
    assert xplane.subtract([(0, 4)], []).tolist() == [[0, 4]]
    assert xplane.subtract([(1, 2)], [(0, 5)]).tolist() == []
    assert xplane.subtract([], [(0, 5)]).tolist() == []


def test_busy_union_idle_share_and_window(handmade):
    red = xplane.reduce_trace(handmade)
    assert red["chips"] == 2
    assert red["window_s"] == pytest.approx(700 * US)
    # both chips: busy [0,520) and [600,700): the while wrapper covers
    # [100,400) though its bodies leave holes, nested events count once
    assert red["busy_s"] == pytest.approx(620 * US)
    idle = 1 - red["busy_s"] / red["window_s"]
    assert idle == pytest.approx(80 / 700)
    assert red["gaps"].tolist() == [
        pytest.approx([520 * US + 1e-6, 600 * US + 1e-6])]


def test_per_name_durations_skip_wrappers_and_average_over_chips(handmade):
    ops = xplane.reduce_trace(handmade)["ops"]
    assert "while" not in ops
    # fusion.1: chip 0 has 100+50+100, chip 1 has 100+100 -> mean 225
    assert ops["fusion.1"] == pytest.approx(225 * US)
    assert red_custom(handmade) == {}
    assert ops["fusion.2"] == pytest.approx((80 + 80 + 140) / 2 * US)
    assert ops["copy.4"] == pytest.approx(130 * US)
    assert ops["all-reduce.7"] == pytest.approx(140 * US)
    top = xplane.top_ops(ops, 2)
    assert [n for n, _ in top] == ["fusion.1", "fusion.2"]
    assert xplane.kernel_time(ops, r"^fusion") == pytest.approx(375 * US)
    one = xplane.reduce_trace(handmade, chips=1)
    assert one["chips"] == 1
    assert one["ops"]["fusion.1"] == pytest.approx(250 * US)


def red_custom(profile):
    return xplane.reduce_trace(profile)["custom_calls"]


def test_exposed_collective_time(handmade):
    red = xplane.reduce_trace(handmade)
    assert red["collective_s"] == pytest.approx(140 * US)
    # chip 0: all-reduce [380,520) with compute only in [450,500): 90
    # exposed; chip 1: hidden behind fusion.2 for its whole length: 0
    assert red["collective_exposed_s"] == pytest.approx(45 * US)
    assert xplane.reduce_trace(handmade, chips=1)[
        "collective_exposed_s"] == pytest.approx(90 * US)


def test_idle_gaps_are_split_among_the_spans_open_while_they_last(handmade):
    """The one gap [520,600) us: ``step_a`` ended at 510, ``step_b``
    opens at 590, so 70 us lie under no span and 10 under ``step_b``.
    The hand-made spans carry the prefix a recorded trace of PR 23 has;
    the program's own are ``hc:`` (the default)."""
    red = xplane.reduce_trace(handmade)
    assert xplane.host_spans(handmade) == []
    spans = xplane.host_spans(handmade, prefix="bench:")
    assert [s[0] for s in spans] == ["step_a", "step_b"]
    gaps = xplane.name_gaps(red["gaps"], spans)
    assert gaps == [[xplane.NO_SPAN, pytest.approx(70 * US)],
                    ["step_b", pytest.approx(10 * US)]]
    assert xplane.name_gaps([(0.0, 1e-6)], []) == [
        [xplane.NO_SPAN, pytest.approx(1e-6)]]
    assert xplane.name_gaps([], spans) == []
    assert xplane.name_gaps(red["gaps"], spans, top=1) == gaps[:1]


def test_names_carry_the_result_shape_and_kernels_are_custom_calls():
    copy = ("%copy.348 = f32[24,16,641,16,128]{4,1,3,2,0:T(8,128)} "
            "copy(f32[24,16,641,16,128]{4,3,2,1,0:T(8,128)} %p)")
    assert xplane.clean_name(copy) == "copy.348:f32[24,16,641,16,128]"
    tup = "%fusion.9 = (f32[16,1024]{1,0}, f32[8]{0}) fusion(%a)"
    assert xplane.clean_name(tup) == "fusion.9:f32[16,1024]"
    kern = ("%MultiHeadAttention_0.128 = bf16[256,1024,64]{2,1,0} "
            "custom-call(bf16[256,1024,64]{2,1,0} %q)")
    assert xplane.is_custom_call(kern) and not xplane.is_custom_call(copy)
    assert xplane.clean_name("fusion.1") == "fusion.1"


def test_recorded_v5e_trace_reduces_to_what_was_seen_on_the_chip():
    """Recorded on one TPU v5e in PR 23 with ``jax.profiler.start_trace``:
    three calls of a jitted 3 x (matmul, tanh), each under a
    ``jax.profiler.TraceAnnotation("bench:work_<i>")`` span."""
    prof = xplane.load(os.path.join(DATA, "recorded_v5e_small.xplane.pb"))
    red = xplane.reduce_trace(prof)
    assert red["chips"] == 1
    assert red["busy_s"] == pytest.approx(107.388e-6, rel=1e-3)
    assert red["window_s"] == pytest.approx(43.1178e-3, rel=1e-3)
    assert 1 - red["busy_s"] / red["window_s"] > 0.99
    names = sorted(red["ops"])
    assert sorted(n.split(":")[0] for n in names) == [
        "copy-done", "copy-start", "fusion", "fusion.1", "fusion.2"]
    assert red["ops"]["fusion:bf16[1024,1024]"] == pytest.approx(
        37.9e-6, rel=0.02)
    assert red["custom_calls"] == {} and red["collective_s"] == 0
    spans = xplane.host_spans(prof, prefix="bench:")
    assert [s[0] for s in spans] == ["work_0", "work_1", "work_2"]
    assert len(red["gaps"]) >= 2
    named = dict(map(tuple, xplane.name_gaps(red["gaps"], spans)))
    assert any(k.endswith("work_0") for k in named)


def test_a_trace_with_no_device_operation_is_refused():
    with pytest.raises(ValueError):
        xplane.reduce_trace(_profile({}))


def test_the_window_tracer_reduces_the_sessions_trace_in_memory(
        tmp_path, monkeypatch):
    """What a profiler session's ``stop()`` hands over is reduced without
    a file; ``BENCH_KEEP_TRACE`` writes it where ``find_xplane`` and
    ``tools/gap_phases.py`` look for a kept trace."""
    with open(os.path.join(DATA, "recorded_v5e_small.xplane.pb"), "rb") as f:
        recorded = f.read()
    tracer = tracing.WindowTracer(str(tmp_path / "trace"), 51.0)
    assert (tracer.start_after, tracer.length) == (12.75, 6.0)
    assert tracer.reduce(1) is None             # no trace was taken
    tracer._xspace = recorded
    red = tracer.reduce(1)
    assert red["busy_s"] == pytest.approx(0.00010738799999998605, rel=1e-9)
    assert red["idle_gaps"] == [[xplane.NO_SPAN, pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)]]
    assert 0 < red["reduce_s"] < 5 and red["stop_s"] is None
    assert not os.path.exists(tmp_path / "trace")
    monkeypatch.setenv("BENCH_KEEP_TRACE", "1")
    tracer._xspace = recorded
    tracer.reduce(1)
    with open(xplane.find_xplane(str(tmp_path / "trace")), "rb") as f:
        assert f.read() == recorded


def test_this_jax_still_has_the_session_the_tracer_takes_its_trace_from(
        monkeypatch):
    """``harness/tracing`` builds JAX's private ``ProfilerSession``: a
    JAX that moves it must fail here and, in a traced run, by name when
    the tracer is armed, not in a timer thread whose error nobody sees."""
    from jax._src.lib import _profiler

    assert tracing.session_class() is _profiler.ProfilerSession
    assert callable(getattr(_profiler.ProfilerSession, "stop"))
    monkeypatch.delattr(_profiler, "ProfilerSession")
    with pytest.raises(RuntimeError, match="ProfilerSession"):
        tracing.WindowTracer(os.path.join(DATA, "no_such_dir"), 0.8).arm()


def test_the_window_tracer_records_the_programs_spans_on_any_backend():
    """A live session on this machine's backend (no chip: no device
    plane, so nothing to reduce): the timer opens and closes it inside
    the window and the ``hc:`` spans the program writes meanwhile are in
    what ``stop()`` returns."""
    import jax

    tracer = tracing.WindowTracer(os.path.join(DATA, "no_such_dir"), 0.8)
    tracer.arm()
    until = time.monotonic() + 0.6
    while time.monotonic() < until:
        with jax.profiler.TraceAnnotation("hc:turn"):
            time.sleep(0.001)
    tracer.stop()
    assert 0 < tracer.stop_s < 30
    spans = xplane.host_spans(xplane.parse(tracer._xspace))
    assert spans and {name for name, _, _ in spans} == {"turn"}
    # opened 0.2 s into the window for 0.2 s of it
    assert 0.05 < spans[-1][2] - spans[0][1] < 0.45
    with pytest.raises(ValueError):
        tracer.reduce(1)


# ---- what the event-by-event reduction read of the kept traces: the
# numbers its code printed (benchmarks/harness/xplane.py as of PR 30)
# before PR 32 replaced the loops; (trace, chips, prefix of its spans)

RECORDED = {
    ("recorded_v5e_small.xplane.pb", None, "bench:"): {
        "busy_s": 0.00010738799999998605, "window_s": 0.04311784600000001,
        "t0": 0.044720216, "collective_s": 0.0,
        "collective_exposed_s": 0.0, "gaps": 12,
        "top": [["fusion:bf16[1024,1024]", 3.7874999999999714e-05],
                ["fusion.1:bf16[1024,1024]", 3.47339999999946e-05],
                ["fusion.2:bf16[1024,1024]", 3.473299999999513e-05],
                ["copy-start:bf16[1024,1024]", 3.9000000000288804e-08],
                ["copy-done:bf16[1024,1024]", 6.999999996315509e-09]],
        "charge": {
            xplane.NO_SPAN: (0.04301045800000002, 12, 0.04117186800000003),
            "work_0": (0.0, 0, 0.0009281100000000028),
            "work_1": (0.0, 0, 0.0009104799999999913)}},
    ("two_chip_handmade.xplane.txt", None, "bench:"): {
        "busy_s": 0.0006199999999999999, "window_s": 0.0007,
        "t0": 1.0000000000000002e-06, "collective_s": 0.00013999999999999993,
        "collective_exposed_s": 4.4999999999999955e-05, "gaps": 1,
        "top": [["fusion.1", 0.00022499999999999994],
                ["fusion.2", 0.00014999999999999996],
                ["all-reduce.7", 0.00013999999999999993],
                ["copy.4", 0.00013000000000000002]],
        "charge": {
            xplane.NO_SPAN: (8.00000000000001e-05, 1, 7.000000000000008e-05),
            "step_b": (0.0, 0, 1.0000000000000026e-05)}},
    ("two_chip_handmade.xplane.txt", 1, "bench:"): {
        "busy_s": 0.0006199999999999999, "window_s": 0.0007,
        "t0": 1.0000000000000002e-06, "collective_s": 0.00013999999999999993,
        "collective_exposed_s": 8.999999999999991e-05, "gaps": 1,
        "top": [["fusion.1", 0.00024999999999999995],
                ["all-reduce.7", 0.00013999999999999993],
                ["copy.4", 0.00013000000000000002],
                ["fusion.2", 7.999999999999999e-05]],
        "charge": {
            xplane.NO_SPAN: (8.00000000000001e-05, 1, 7.000000000000008e-05),
            "step_b": (0.0, 0, 1.0000000000000026e-05)}},
    ("nested_spans_handmade.xplane.txt", None, "hc:"): {
        "busy_s": 0.00028000000000000003, "window_s": 0.0004,
        "t0": 1.0000000000000002e-06, "collective_s": 0.0,
        "collective_exposed_s": 0.0, "gaps": 2,
        "top": [["fusion.1", 0.00016000000000000004],
                ["copy.2", 0.00011999999999999999]],
        "charge": {
            "decode_wait": (0.00012, 2, 2.0000000000000025e-05),
            "retire": (0.0, 0, 5.000000000000001e-05),
            "telemetry": (0.0, 0, 9.999999999999999e-06),
            xplane.NO_SPAN: (0.0, 0, 9.999999999999999e-06),
            "pack": (0.0, 0, 1.999999999999997e-05),
            "decode_dispatch": (0.0, 0, 9.999999999999999e-06)}},
}
SAME = dict(rel=1e-9, abs=1e-18)


@pytest.mark.parametrize("trace,chips,prefix", list(RECORDED))
def test_the_array_reduction_reads_what_the_loops_read(trace, chips, prefix):
    want = RECORDED[trace, chips, prefix]
    profile = xplane.load(os.path.join(DATA, trace))
    red = xplane.reduce_trace(profile, chips)
    for key in ("busy_s", "window_s", "t0", "collective_s",
                "collective_exposed_s"):
        assert red[key] == pytest.approx(want[key], **SAME), key
    assert len(red["gaps"]) == want["gaps"]
    top = xplane.top_ops(red["ops"])
    assert [n for n, _ in top] == [n for n, _ in want["top"]]
    assert [v for _, v in top] == pytest.approx(
        [v for _, v in want["top"]], **SAME)
    spans = xplane.host_spans(profile, prefix)
    charge = xplane.charge_gaps(red["gaps"], spans)
    # the same rows, first touched in the same order
    assert list(charge) == list(want["charge"])
    for name, (began, gaps, split) in want["charge"].items():
        row = charge[name]
        assert row["gaps"] == gaps
        assert row["began_in_s"] == pytest.approx(began, **SAME)
        assert row["split_s"] == pytest.approx(split, **SAME)
    assert sum(r["split_s"] for r in charge.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)


# ---- the loops of PR 23-30, kept as the oracle

def _union_loop(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _subtract_loop(a, b):
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _charge_loop(gaps, spans):
    points = xplane.innermost_points(spans)
    times = [t for t, _ in points]
    out = {}

    def row(k):
        return out.setdefault(
            points[k][1] if k >= 0 else xplane.NO_SPAN,
            {"began_in_s": 0.0, "gaps": 0, "split_s": 0.0})

    for g0, g1 in gaps:
        k = bisect.bisect_right(times, g0) - 1
        first = row(k)
        first["began_in_s"] += g1 - g0
        first["gaps"] += 1
        t = g0
        while k + 1 < len(times) and times[k + 1] < g1:
            row(k)["split_s"] += times[k + 1] - t
            k, t = k + 1, times[k + 1]
        row(k)["split_s"] += g1 - t
    return out


def _random_intervals(rng, n, grid=None):
    """``n`` intervals in [0, 1); on a ``grid`` many share a boundary
    (touching, nested and equal intervals), off it none does."""
    s = rng.uniform(0, 1, n)
    e = s + rng.exponential(0.02, n)
    if grid:
        s, e = np.round(s * grid) / grid, np.round(e * grid) / grid
        e = np.maximum(e, s + 1 / grid)
    return list(zip(s.tolist(), e.tolist()))


@pytest.mark.parametrize("seed", range(8))
def test_union_and_subtract_equal_the_plain_loops_on_random_sets(seed):
    rng = np.random.default_rng(3200 + seed)
    for case in range(40):
        grid = (None, 50, 400)[case % 3]
        a = _random_intervals(rng, int(rng.integers(0, 60)), grid)
        b = _random_intervals(rng, int(rng.integers(0, 60)), grid)
        ua, ub = _union_loop(a), _union_loop(b)
        assert xplane.union(a).tolist() == ua
        assert xplane.union(b).tolist() == ub
        assert xplane.total(ua) == pytest.approx(
            sum(e - s for s, e in ua), rel=1e-12)
        assert xplane.subtract(ua, ub).tolist() == _subtract_loop(ua, ub)


@pytest.mark.parametrize("seed", range(8))
def test_charge_gaps_equals_the_plain_loop_on_random_sets(seed):
    """Gaps are the complement of a random busy union; spans nest, touch
    and share boundaries with each other and with the gaps."""
    rng = np.random.default_rng(3300 + seed)
    for case in range(40):
        grid = (None, 50, 400)[case % 3]
        busy = _union_loop(_random_intervals(
            rng, int(rng.integers(1, 80)), grid))
        gaps = [[a[1], b[0]] for a, b in zip(busy, busy[1:])]
        named = _random_intervals(rng, int(rng.integers(0, 40)), grid)
        spans = sorted(((f"s{i % 7}", s, e)
                        for i, (s, e) in enumerate(named)),
                       key=lambda x: x[1])
        want = _charge_loop(gaps, spans)
        got = xplane.charge_gaps(gaps, spans)
        assert list(got) == list(want)
        for name, row in want.items():
            assert got[name]["gaps"] == row["gaps"]
            for key in ("began_in_s", "split_s"):
                assert got[name][key] == pytest.approx(row[key], **SAME)


# ---- a trace as large as a fast lane's

class _Event:
    __slots__ = ("name", "start_ns", "duration_ns")

    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = (
            name, start_ns, duration_ns)


def _profile(planes: dict):
    """A profile as ``xplane`` walks it: ``{plane: {line: events}}``."""
    return SimpleNamespace(planes=[
        SimpleNamespace(name=plane, lines=[
            SimpleNamespace(name=line, events=events)
            for line, events in lines.items()])
        for plane, lines in planes.items()])


def test_a_trace_of_a_million_gaps_reduces_in_seconds():
    """1 M device operations of 1,500 distinct names with an idle gap
    after each, under 20,000 spans of ten phases: what a 6 s trace of a
    decode lane holds.  The loops took a minute and more on the chip's
    host; the arrays have to stay inside 5 s."""
    n = 1_000_000
    rng = np.random.default_rng(32)
    took = rng.integers(1_000, 6_000, n).astype(float)
    idle = rng.integers(1, 3_000, n).astype(float)
    start = np.concatenate([[0.0], np.cumsum(took + idle)[:-1]])
    names = [f"%fusion.{i} = f32[16,{i},128]{{2,1,0:T(8,128)}} fusion("
             f"f32[24,16,641,16,128]{{4,3,2,1,0}} %p.{i}), kind=kLoop"
             for i in range(1_500)]
    names[7] = "%while.7 = (s32[], f32[16,128]{1,0}) while(%tuple.7)"
    device = [_Event(names[i % 1_500], s, d)
              for i, (s, d) in enumerate(zip(start.tolist(), took.tolist()))]
    end = start[-1] + took[-1]
    host = [_Event(f"hc:phase_{i % 10}", i * end / 20_000, end / 20_000 - 5)
            for i in range(20_000)]
    host += [_Event(f"$engine.py:{i % 50} fn", i * 97.0, 50.0)
             for i in range(200_000)]
    profile = _profile({"/device:TPU:0": {"XLA Ops": device},
                        "/host:CPU": {"python3": host}})
    t0 = time.monotonic()
    red = xplane.reduce_trace(profile, 1)
    spans = xplane.host_spans(profile)
    named = xplane.name_gaps(red["gaps"], spans, top=20)
    seconds = time.monotonic() - t0
    assert len(red["gaps"]) == n - 1 and len(spans) == 20_000
    # ten phases and the holes between their spans
    assert len(red["ops"]) == 1_499 and len(named) == 11
    assert red["busy_s"] == pytest.approx(took.sum() * 1e-9, rel=1e-9)
    assert sum(v for _, v in named) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)
    assert seconds < 5.0, seconds
