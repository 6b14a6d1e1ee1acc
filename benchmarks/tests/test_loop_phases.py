"""The readers of the serve loop's own account (``loop_phases``,
``queue_unseen_ms``) on hand-made inputs, and ``tools/gap_phases.py`` on
a hand-made trace whose spans nest."""

import importlib.util
import os

import pytest

from harness import spec, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1e-6


def _phases(**wall_s):
    return {k: {"count": 4, "wall_s": v} for k, v in wall_s.items()}


SUMMARY = {"loop_phases": _phases(
    arrivals=0.004, admit_host=0.006, prefill_dispatch=0.002,
    prefill_wait=0.060, pack=0.008, decode_dispatch=0.004,
    decode_wait=0.200, retire=0.010, telemetry=0.006, arrival_wait=0.100)}


@pytest.mark.parametrize("metric", ["serve.host_turn_ms",
                                    "serve.host_turn_ms.sat"])
def test_host_turn_is_the_loop_wall_less_its_waits_per_decode_step(metric):
    read = spec.reader_of(metric)
    # 0.400 s of loop, 0.360 s of it waiting, 4 decode steps
    assert read({"summary": SUMMARY}) == pytest.approx(10.0)
    # a program without the fold (the parent), or a run with no step
    assert read({"summary": {"bucket_util": {}}}) is None
    assert read({"summary": None}) is None
    assert read({}) is None
    assert read({"summary": {"loop_phases": _phases(arrivals=0.1)}}) is None


def test_queue_unseen_p90_follows_the_queue_wait_rule():
    read = spec.reader_of("serve.queue_unseen_p90_ms")
    records = [{"queue_ms": 2.0 * i, "queue_unseen_ms": float(i)}
               for i in range(1, 11)]
    ctx = {"records": records, "failed": 0, "drain_limit_ms": 60e3}
    assert read(ctx) == 9.0                   # nearest rank, exact
    queue = spec.reader_of("serve.queue_wait_p90_ms")
    assert queue(ctx) == 18.0
    # a failed request counts as the worst, as for every per-request tail
    assert read(dict(ctx, failed=2)) == 60e3
    # records of a program without the stamp (the parent), or none
    assert read({"records": [{"queue_ms": 1.0}], "failed": 0,
                 "drain_limit_ms": 60e3}) is None
    assert read({"records": []}) is None


@pytest.fixture(scope="module")
def gap_phases():
    sp = importlib.util.spec_from_file_location(
        "gap_phases", os.path.join(os.path.dirname(HERE), "tools",
                                   "gap_phases.py"))
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def test_gap_phases_charges_gaps_to_the_innermost_open_span(gap_phases):
    """Device busy [0,100) [180,300) [340,400) us; the host's spans nest
    (``decode`` holds ``decode_dispatch`` and ``decode_wait``), leave a
    hole at [150,160), and sit beside a ``bench:`` span and the Python
    tracer's own events, which are not the program's."""
    profile = xplane.load(os.path.join(
        HERE, "data", "nested_spans_handmade.xplane.txt"))
    assert [n for n, _, _ in xplane.host_spans(profile)][:3] == [
        "decode", "decode_dispatch", "decode_wait"]
    rep = gap_phases.report(profile)
    assert rep["window_s"] == pytest.approx(400 * US)
    assert rep["idle_s"] == pytest.approx(120 * US)
    assert rep["gaps"] == 2
    ph = rep["phases"]
    # both gaps begin while the host still waits inside decode: the
    # innermost open span is decode_wait, not decode (nor the last one
    # started before it)
    assert ph["decode_wait"]["gaps"] == 2
    assert ph["decode_wait"]["began_in_s"] == pytest.approx(120 * US)
    assert ph["decode"]["gaps"] == 0 and ph["decode"]["split_s"] == 0
    split = {k: round(v["split_s"] / US) for k, v in ph.items()
             if v["split_s"]}
    assert split == {"decode_wait": 20, "retire": 50, "telemetry": 10,
                     xplane.NO_SPAN: 10, "pack": 20,
                     "decode_dispatch": 10}
    assert rep["idle_named_share"] == pytest.approx(110 / 120)
    assert ph["decode"]["spans"] == 2
    assert ph["decode"]["span_s"] == pytest.approx(250 * US)
    text = "\n".join(gap_phases.table(rep))
    assert "retire" in text and "91.7%" in text


def test_the_result_lines_idle_gaps_carry_the_loops_phases():
    """``xplane.name_gaps`` on the same trace: what a traced run prints
    as ``breakdown.idle_gaps`` is the split charge by the program's
    ``hc:`` spans, largest first — ``retire``, not ``after_decode_16``
    (the ``bench:`` span beside them is nobody's)."""
    profile = xplane.load(os.path.join(
        HERE, "data", "nested_spans_handmade.xplane.txt"))
    red = xplane.reduce_trace(profile)
    gaps = xplane.name_gaps(red["gaps"], xplane.host_spans(profile))
    assert gaps[0] == ["retire", pytest.approx(50 * US)]
    assert {k: round(v / US) for k, v in gaps} == {
        "retire": 50, "decode_wait": 20, "pack": 20, "telemetry": 10,
        xplane.NO_SPAN: 10, "decode_dispatch": 10}
    assert sum(v for _, v in gaps) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert not any("decode_16" in k or k == "decode" for k, _ in gaps)
    assert [k for k, _ in xplane.name_gaps(red["gaps"], [], top=3)] == [
        xplane.NO_SPAN]

