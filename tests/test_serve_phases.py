"""The serve loop's own account of its wall (``obs.timeline.Phases`` in
``ServeEngine.run``): the exclusive phases, their two sinks (ring and
profiler trace, one clock), the conserved ``loop_phases`` fold, and the
arrival-to-first-look stamp ``queue_unseen_ms``.

Everything rides the session's ONE warmed ``moe_engine`` (conftest.py);
the profiler runs on the CPU backend, which writes the same host plane
the TPU's trace carries.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import statistics
import time

import pytest

from tpu_hc_bench.obs import metrics as obs_metrics
from tpu_hc_bench.obs import timeline as tl
from tpu_hc_bench.serve import engine as engine_mod
from tpu_hc_bench.serve import faults as faults_mod

from conftest import SERVE_VCOSTS as VCOSTS  # noqa: E402

#: the table of ISSUE 24: every iteration of the loop is tiled by these
LOOP_PHASES = {
    "arrivals", "admit_host", "prefill_dispatch", "prefill_wait", "pack",
    "decode_dispatch", "decode_wait", "retire", "telemetry",
    "arrival_wait"}


def _trace_events(trace_dir):
    """``hc:`` events of the traced run's host planes, by line (one per
    thread), each ``(name, start_ns, duration_ns)`` in start order."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = sorted((e.start_ns, e.name, e.duration_ns)
                         for e in line.events
                         if e.name.startswith(tl.TRACE_PREFIX))
            if evs:
                lines.append([(n[len(tl.TRACE_PREFIX):], s, d)
                              for s, n, d in evs])
    return lines


def _ring_since(m0):
    return [s for s in tl.get_recorder().tail(tl.DEFAULT_CAPACITY)
            if s["t0"] >= m0]


def _traced_run(engine, requests, trace_dir, **kw):
    import jax

    # the profiler's own Python tracer (on by default) hooks every call
    # and stretches the few calls between a boundary's clock read and
    # its annotation; off, the two sinks differ by microseconds
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    m0 = time.monotonic()
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        summary = engine.run(requests, **kw)
    finally:
        jax.profiler.stop_trace()
    return summary, m0


def _records(writer_dir):
    with open(os.path.join(writer_dir, obs_metrics.METRICS_NAME)) as f:
        recs = [json.loads(line) for line in f]
    return [r for r in recs if r.get("kind") == "request"]


def _run_with_records(engine, requests, mdir, **kw):
    writer = obs_metrics.MetricsWriter(
        str(mdir), obs_metrics.run_manifest(
            cfg=engine.cfg, extra={"workload": "serve"}))
    try:
        summary = engine.run(requests, writer=writer, **kw)
    finally:
        writer.close()
    return summary, _records(str(mdir))


# --- (a) the shared clock, shown ---------------------------------------


def test_spans_sit_in_the_profiler_trace_on_the_ring_clock(
        moe_engine, moe_requests, tmp_path):
    summary, m0 = _traced_run(moe_engine, moe_requests, tmp_path / "tr")
    lines = _trace_events(str(tmp_path / "tr"))
    assert len(lines) == 1, "the loop's spans are one thread's"
    events = lines[0]
    ring = _ring_since(m0)
    steps = summary["decode_steps"]
    for parent, child in (("decode", "decode_wait"),
                          ("decode", "decode_dispatch"),
                          ("prefill", "prefill_wait")):
        outer = [e for e in events if e[0] == parent]
        inner = [e for e in events if e[0] == child]
        assert len(outer) == len(inner) > 0
        for (_, s0, d0), (_, s1, d1) in zip(outer, inner):
            # nested, in order: the i-th child inside the i-th parent
            assert s0 <= s1 and s1 + d1 <= s0 + d0
    assert sum(1 for e in events if e[0] == "decode") == steps
    # each trace event lasts what its ring span lasts: both sinks are
    # written at the same boundaries (the trace's epoch is the
    # session's start, so durations compare, not instants).  100 us
    # apart at most; a thread descheduled between a boundary's clock
    # read and its annotation is the odd one out, not a second clock
    apart = []
    for name in LOOP_PHASES | {"decode", "prefill"}:
        in_trace = [d for n, _, d in events if n == name]
        in_ring = [s["t1"] - s["t0"] for s in ring if s["name"] == name]
        assert len(in_trace) == len(in_ring) > 0, name
        off = [abs(d_ns * 1e-9 - dt) for d_ns, dt in zip(in_trace, in_ring)]
        assert statistics.median(off) < 100e-6, (name, off)
        apart += off
    assert sum(o >= 100e-6 for o in apart) <= 0.05 * len(apart), apart


# --- (b) the fold is conserved ------------------------------------------


@pytest.mark.parametrize("clock", ["monotonic", "virtual"])
def test_loop_phases_conserved_and_complete(moe_engine, moe_requests,
                                            clock):
    """A run that admits, decodes, waits for arrivals and drains: every
    phase of the table appears, on the REAL clock under either engine
    clock, and they sum to the loop's separately clocked wall."""
    clk = (engine_mod.MonotonicClock() if clock == "monotonic"
           else engine_mod.VirtualClock(VCOSTS))
    summary = moe_engine.run(moe_requests, clock=clk)
    phases = summary["loop_phases"]
    assert set(phases) == LOOP_PHASES
    total = sum(p["wall_s"] for p in phases.values())
    assert total == pytest.approx(summary["loop_wall_s"], rel=5e-3)
    assert phases["decode_wait"]["count"] == summary["decode_steps"]
    assert phases["prefill_wait"]["count"] == summary["prefill_steps"]
    assert all(p["count"] > 0 and p["wall_s"] >= 0
               for p in phases.values())
    assert summary["queue_depth_max"] >= 1
    assert 0 < summary["queue_depth_mean"] <= summary["queue_depth_max"]


# --- (c) the arrival-to-first-look stamp --------------------------------


def test_queue_unseen_is_the_named_part_of_queue_wait(
        moe_engine, moe_requests, tmp_path):
    """A request due in the middle of a decode step is first looked at
    when that program returns: with a known decode cost the stamp is
    exact."""
    # r0 is admitted at 0, its prefill ends at 4 ms, its first decode
    # step covers 4-7 ms; the others fall due at 5 ms and are seen at 7
    mid = VCOSTS["prefill"] + VCOSTS["decode"] / 3
    reqs = [dataclasses.replace(r, arrival_s=0.0 if i == 0 else mid)
            for i, r in enumerate(moe_requests)]
    _, recs = _run_with_records(
        moe_engine, reqs, tmp_path / "m",
        clock=engine_mod.VirtualClock(VCOSTS))
    assert len(recs) == len(reqs)
    by_id = {r["id"]: r for r in recs}
    late = 1e3 * (VCOSTS["prefill"] + VCOSTS["decode"] - mid)
    for i, req in enumerate(reqs):
        rec = by_id[req.rid]
        assert 0 <= rec["queue_unseen_ms"] <= rec["queue_ms"]
        assert rec["queue_unseen_ms"] == pytest.approx(
            0.0 if i == 0 else late, abs=1e-6)
    # queue_ms and the conserved decomposition are what they were
    from tpu_hc_bench.obs import requests as requests_mod
    for rec in recs:
        parts = requests_mod.attribution_of(rec)
        assert abs(sum(parts.values()) - rec["e2e_ms"]) < 1e-6


def test_queue_unseen_keeps_its_first_stamp_across_requeue(
        moe_engine, moe_requests, tmp_path):
    mid = VCOSTS["prefill"] + VCOSTS["decode"] / 3
    reqs = [dataclasses.replace(r, arrival_s=0.0 if i == 0 else mid)
            for i, r in enumerate(moe_requests)]
    summary, recs = _run_with_records(
        moe_engine, reqs, tmp_path / "m",
        clock=engine_mod.VirtualClock(VCOSTS),
        faults=faults_mod.parse_serve_plan("pool_squeeze@0:3"),
        kv_preempt="on")
    assert summary["degrade"]["requeues"] >= 1
    requeued = [r for r in recs if r.get("preempts")]
    assert requeued, "squeeze + burst must preempt at least one resident"
    late = 1e3 * (VCOSTS["prefill"] + VCOSTS["decode"] - mid)
    first = min(r.rid for r in reqs)
    for rec in recs:
        assert 0 <= rec["queue_unseen_ms"] <= rec["queue_ms"]
        # a stamp taken again at the requeue would read the whole first
        # residency, not the 2 ms between falling due and being seen
        assert rec["queue_unseen_ms"] == pytest.approx(
            0.0 if rec["id"] == first else late, abs=1e-6)


# --- (d) --flight_recorder=off -------------------------------------------


def test_flight_recorder_off_silences_both_sinks_not_the_fold(
        moe_engine, moe_requests, tmp_path, monkeypatch):
    monkeypatch.setattr(
        moe_engine, "cfg",
        dataclasses.replace(moe_engine.cfg, flight_recorder="off"))
    try:
        summary, m0 = _traced_run(moe_engine, moe_requests,
                                  tmp_path / "tr")
    finally:
        tl.configure(enabled=True)
    assert _ring_since(m0) == []
    assert _trace_events(str(tmp_path / "tr")) == []
    phases = summary["loop_phases"]
    assert set(phases) == LOOP_PHASES
    assert sum(p["wall_s"] for p in phases.values()) == pytest.approx(
        summary["loop_wall_s"], rel=5e-3)
