"""The reduction from an xplane trace to device metrics, against a
hand-made two-chip trace whose answers are worked out by hand, and
against a small trace recorded on the chip (PR 23)."""

import os

import pytest

from harness import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1e-6


@pytest.fixture(scope="module")
def handmade():
    return xplane.load(os.path.join(DATA, "two_chip_handmade.xplane.txt"))


def test_interval_arithmetic():
    assert xplane.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert xplane.total([(0, 3), (5, 6)]) == 4
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert xplane.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert xplane.subtract([(0, 4)], []) == [(0, 4)]
    assert xplane.subtract([(1, 2)], [(0, 5)]) == []


def test_busy_union_idle_share_and_window(handmade):
    red = xplane.reduce_trace(handmade)
    assert red["chips"] == 2
    assert red["window_s"] == pytest.approx(700 * US)
    # both chips: busy [0,520) and [600,700): the while wrapper covers
    # [100,400) though its bodies leave holes, nested events count once
    assert red["busy_s"] == pytest.approx(620 * US)
    idle = 1 - red["busy_s"] / red["window_s"]
    assert idle == pytest.approx(80 / 700)
    assert red["gaps"] == [pytest.approx((520 * US + 1e-6, 600 * US + 1e-6))]


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
    empty = xplane.load(os.path.join(DATA, "two_chip_handmade.xplane.txt"))
    with pytest.raises(ValueError):
        xplane.reduce_trace(empty, chips=None) if False else \
            xplane.reduce_trace(_NoDevice())


class _NoDevice:
    planes = ()
