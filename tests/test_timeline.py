"""Flight recorder (``obs.timeline``) + its satellites.

Seven sections, matching the round-17 acceptance contract:

1. Ring mechanics: bounded preallocated ring, drop accounting, the span
   context manager, instants, the coarse phase lane
   (``transition``/``current_phase``).
2. Persistence: flush/append/read round-trip, corrupt-line tolerance,
   never-fatal I/O.
3. Cross-rank merge: clock alignment through heartbeat ``(t_mono,
   t_unix)`` pairs AND the spans files' own ``clock`` records, the
   >= 2-rank aligned Chrome-trace export, summarize's
   straggler/bubble attribution lines.
4. Forensics: ``dump_timeline`` (live ring + other ranks' flushed
   files) and the watchdog wiring (in-process fire with an injected
   ``on_timeout`` — the subprocess e2e proof rides the slow-marked
   emergency-save test in test_memory_obs, which now asserts
   ``timeline_dump.json`` too).
5. ``obs regress``: the noise-aware gate flags an injected 10%
   throughput regression, passes an unchanged rerun, respects
   fingerprints and per-metric direction; the CLI exit codes.
6. The ``span-in-compiled-fn`` analysis lint (positive + negative
   fixtures; the repo baseline stays clean via test_analysis).
7. End-to-end against the SHARED session-scoped ``rewind_run`` driver
   fixture (conftest.py — no new default-lane driver runs): on-by-
   default spans.<k>.jsonl, recorder span names, heartbeat
   phase/incarnation/t_mono fields, `obs timeline` CLI, summarize and
   watch rendering, FleetWriter append-across-incarnations, and the
   bounded-overhead guard (<1% of the measured steady-state step).
"""

from __future__ import annotations

import io
import json
import os
import time
from pathlib import Path

import pytest

from tpu_hc_bench.analysis import lints
from tpu_hc_bench.obs import fleet
from tpu_hc_bench.obs import regress
from tpu_hc_bench.obs import timeline as tl
from tpu_hc_bench.obs.__main__ import main as obs_main

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------
# 1. ring mechanics


def test_ring_is_bounded_and_counts_drops(tmp_path):
    rec = tl.SpanRecorder(capacity=8)
    rec.attach(str(tmp_path), rank=0)
    for i in range(20):
        rec.record("s", float(i), float(i) + 0.5, step=i)
    # nothing flushed yet: 20 recorded, only the newest 8 live
    rec.flush()
    assert rec.dropped == 12
    spans = tl.read_spans(str(tmp_path))[0]
    assert len(spans) == 8
    assert [s["step"] for s in spans] == list(range(12, 20))
    rec.detach()


def test_span_context_manager_and_instant():
    rec = tl.SpanRecorder(capacity=16)
    with rec.span("work", step=3, detail="x"):
        pass
    rec.instant("mark", step=4)
    spans = rec.tail()
    assert spans[0]["name"] == "work" and spans[0]["step"] == 3
    assert spans[0]["detail"] == "x"
    assert spans[0]["t1"] >= spans[0]["t0"]
    assert spans[1]["name"] == "mark" and spans[1]["t0"] == spans[1]["t1"]


def test_span_keeps_its_clock_pair_whatever_the_switch():
    rec = tl.SpanRecorder(capacity=4)
    rec.enabled = False
    with rec.span("work") as sp:
        time.sleep(0.001)
    assert sp.t1 - sp.t0 >= 0.001 and rec.tail() == []


def test_live_span_is_in_the_profiler_trace(tmp_path):
    """The second sink: a live ``span()`` is an ``hc:<name>`` event in
    an open profiler session, lasting what its ring record lasts;
    ``record_span`` (after the fact) is ring-only, and a disabled
    recorder writes neither."""
    import glob

    import jax
    from jax.profiler import ProfileData

    rec = tl.SpanRecorder(capacity=16)
    off = tl.SpanRecorder(capacity=16)
    off.enabled = False
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("ckpt_write", step=3):
            with rec.span("ckpt_snapshot"):
                time.sleep(0.002)
        rec.record("device_step", 0.0, 1.0)
        with off.span("ckpt_restore"):
            pass
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    events = {e.name: e for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith(tl.TRACE_PREFIX)}
    assert set(events) == {"hc:ckpt_write", "hc:ckpt_snapshot"}
    outer, inner = events["hc:ckpt_write"], events["hc:ckpt_snapshot"]
    assert outer.start_ns <= inner.start_ns
    assert (inner.start_ns + inner.duration_ns
            <= outer.start_ns + outer.duration_ns)
    ring = {s["name"]: s for s in rec.tail()}
    assert set(ring) == {"ckpt_write", "ckpt_snapshot", "device_step"}
    for name in ("ckpt_write", "ckpt_snapshot"):
        assert abs(events["hc:" + name].duration_ns * 1e-9
                   - (ring[name]["t1"] - ring[name]["t0"])) < 1e-3


def test_phases_tile_the_wall_and_nest_under_a_parent():
    rec = tl.SpanRecorder(capacity=16)
    ph = tl.Phases(rec)
    t0 = time.monotonic()
    assert ph.enter("pack") is None
    assert ph.enter("decode_dispatch", parent="decode") == "pack"
    ph.enter("decode_wait", parent="decode")
    assert ph.enter("decode_wait", parent="decode") == "decode_wait"
    ph.enter("retire")
    ph.close()
    wall = time.monotonic() - t0
    assert {k: c for k, (c, _) in ph.fold.items()} == {
        "pack": 1, "decode_dispatch": 1, "decode_wait": 1, "retire": 1}
    assert sum(w for _, w in ph.fold.values()) == pytest.approx(
        wall, abs=1e-4)
    spans = {s["name"]: s for s in rec.tail()}
    # the parent spans its two phases exactly; neighbours share a boundary
    assert spans["decode"]["t0"] == spans["decode_dispatch"]["t0"]
    assert spans["decode"]["t1"] == spans["decode_wait"]["t1"]
    assert spans["pack"]["t1"] == spans["decode_dispatch"]["t0"]
    assert spans["decode_wait"]["t1"] == spans["retire"]["t0"]
    # the fold is kept with the recorder off; the sinks are not
    rec.enabled = False
    ph = tl.Phases(rec)
    ph.enter("pack")
    ph.close()
    assert ph.fold["pack"][0] == 1 and len(rec.tail()) == 5


def test_phase_lane_transitions_and_current_phase():
    rec = tl.SpanRecorder(capacity=16)
    rec.transition("init")
    assert rec.current_phase() == "init"
    rec.transition("step", step=1)
    # the closed init phase landed as a span
    assert rec.tail()[-1]["name"] == "init"
    assert rec.current_phase() == "step"
    rec.transition("end", step=5)
    # lane closed: current_phase falls back to the newest span
    assert rec.current_phase() == "step"


def test_disabled_recorder_is_a_noop():
    rec = tl.SpanRecorder(capacity=4)
    rec.enabled = False
    rec.record("s", 0.0, 1.0)
    assert rec.tail() == []


# ---------------------------------------------------------------------
# 2. persistence


def test_flush_appends_and_reader_skips_corrupt_lines(tmp_path):
    rec = tl.SpanRecorder(capacity=32)
    rec.attach(str(tmp_path), rank=2)
    rec.record("a", 1.0, 2.0)
    assert rec.flush() == 1
    rec.record("b", 2.0, 3.0)
    assert rec.flush() == 1
    # a flush interrupted by the death it documents: garbage tail
    path = tmp_path / "spans.2.jsonl"
    with open(path, "a") as f:
        f.write('{"name": "tru')
    spans = tl.read_spans(str(tmp_path))
    assert [s["name"] for s in spans[2]] == ["a", "b"]
    rec.detach()


def test_flush_without_run_dir_is_free():
    rec = tl.SpanRecorder(capacity=4)
    rec.record("a", 0.0, 1.0)
    assert rec.flush() == 0        # nowhere to persist, no error


def test_persistence_failure_never_raises(tmp_path):
    rec = tl.SpanRecorder(capacity=4)
    # attach to a path that cannot be a directory
    blocker = tmp_path / "f"
    blocker.write_text("x")
    rec.attach(str(blocker / "sub"), rank=0)
    rec.record("a", 0.0, 1.0)
    assert rec.flush() == 0        # disabled itself, run unharmed
    assert rec.enabled             # RING keeps recording for forensics


# ---------------------------------------------------------------------
# 3. cross-rank merge + clock alignment


def _write_spans(run_dir, rank, spans, clock=None):
    with open(os.path.join(run_dir, f"spans.{rank}.jsonl"), "w") as f:
        if clock is not None:
            f.write(json.dumps({"clock": clock}) + "\n")
        for s in spans:
            f.write(json.dumps(s) + "\n")


def _write_heartbeats(run_dir, rank, pairs):
    with open(os.path.join(run_dir, f"metrics.{rank}.jsonl"), "w") as f:
        for t_mono, t_unix in pairs:
            f.write(json.dumps({"kind": "heartbeat", "host": rank,
                                "step": 1, "step_ewma_ms": 1.0,
                                "t_mono": t_mono, "t_unix": t_unix}) + "\n")


def test_merge_aligns_two_ranks_via_heartbeats(tmp_path):
    """The acceptance merge: two ranks whose monotonic epochs differ by
    4000s but whose spans happened at the SAME wall instant land at the
    same aligned timestamp in one Chrome-trace file."""
    d = str(tmp_path)
    wall = 1.7e9
    _write_spans(d, 0, [{"name": "step_dispatch", "t0": 1000.5,
                         "t1": 1000.6, "step": 1}])
    _write_spans(d, 1, [{"name": "step_dispatch", "t0": 5000.5,
                         "t1": 5000.6, "step": 1}])
    _write_heartbeats(d, 0, [(1000.0, wall)])
    _write_heartbeats(d, 1, [(5000.0, wall)])
    trace = tl.merge_chrome_trace(d)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert sorted(e["pid"] for e in xs) == [0, 1]
    assert xs[0]["ts"] == xs[1]["ts"]      # aligned despite epoch skew
    assert trace["metadata"]["aligned_ranks"] == [0, 1]


def test_merge_falls_back_to_spans_clock_records(tmp_path):
    d = str(tmp_path)
    wall = 1.7e9
    _write_spans(d, 0, [{"name": "a", "t0": 10.0, "t1": 11.0}],
                 clock={"t_mono": 10.0, "t_unix": wall})
    _write_spans(d, 1, [{"name": "a", "t0": 90.0, "t1": 91.0}],
                 clock={"t_mono": 90.0, "t_unix": wall})
    trace = tl.merge_chrome_trace(d)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert xs[0]["ts"] == xs[1]["ts"]


def test_merge_without_spans_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        tl.merge_chrome_trace(str(tmp_path))


def test_merge_mixed_aligned_and_clockless_rank_is_loud(tmp_path):
    """Round-20 fallback hardening: a rank whose spans file has no
    ``clock`` records AND whose dir has no heartbeats merges with the
    identity offset and ONE loud warning — it is never silently
    dropped, and the aligned ranks stay aligned."""
    d = str(tmp_path)
    wall = 1.7e9
    _write_spans(d, 0, [{"name": "step_dispatch", "t0": 1000.5,
                         "t1": 1000.6}])
    _write_spans(d, 1, [{"name": "step_dispatch", "t0": 5000.5,
                         "t1": 5000.6}])
    # rank 2: NO clock record in its spans file, NO heartbeat file
    _write_spans(d, 2, [{"name": "ring_get", "t0": 77.0, "t1": 78.0}])
    _write_heartbeats(d, 0, [(1000.0, wall)])
    _write_heartbeats(d, 1, [(5000.0, wall)])
    trace = tl.merge_chrome_trace(d)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert sorted({e["pid"] for e in xs}) == [0, 1, 2]   # nobody dropped
    a = {e["pid"]: e["ts"] for e in xs}
    assert a[0] == a[1]                 # aligned pair still aligned
    assert trace["metadata"]["aligned_ranks"] == [0, 1]
    warns = trace["metadata"]["warnings"]
    assert len(warns) == 1 and "rank2" in warns[0]
    assert "IDENTITY offset" in warns[0]
    # the clockless rank's process lane is marked in the trace itself
    marks = [e for e in trace["traceEvents"]
             if e.get("ph") == "M" and e["pid"] == 2]
    assert any("unaligned clock" in e["args"]["name"] for e in marks)
    # the CLI surfaces it: WARNING on stderr, degraded exit code 1
    import io as _io

    from tpu_hc_bench.obs.__main__ import main as obs_main_fn

    buf = _io.StringIO()
    import contextlib
    import sys as _sys

    err = _io.StringIO()
    with contextlib.redirect_stderr(err):
        rc = obs_main_fn(["timeline", d], out=buf)
    assert rc == 1
    assert "WARNING" in err.getvalue() and "rank2" in err.getvalue()
    # all-aligned dirs keep exiting 0 (pin for the existing contract)
    for f in os.listdir(d):
        if f.startswith("spans.2."):
            os.unlink(os.path.join(d, f))
    with contextlib.redirect_stderr(_io.StringIO()):
        assert obs_main_fn(["timeline", d], out=_io.StringIO()) == 0


def test_alignment_survives_a_rebooted_incarnation(tmp_path):
    """Elastic resume on a REBOOTED host restarts CLOCK_MONOTONIC: one
    rank's spans file then carries two lives with wildly different
    mono->unix offsets.  Alignment must be per-sample (nearest clock
    pair), not one pooled median — the minority life's spans would
    otherwise land hours off, confidently."""
    d = str(tmp_path)
    wall = 1.7e9
    # life 0: mono epoch ~90000 (long-lived host); life 1 after reboot:
    # mono epoch ~100 (fresh boot), 50 wall-seconds later
    _write_heartbeats(d, 0, [(90000.0, wall), (90010.0, wall + 10.0),
                             (100.0, wall + 50.0), (110.0, wall + 60.0)])
    _write_spans(d, 0, [
        {"name": "step_dispatch", "t0": 90005.0, "t1": 90006.0},
        {"name": "step_dispatch", "t0": 105.0, "t1": 106.0},
    ])
    # reference rank with one life, for the shared t_base
    _write_heartbeats(d, 1, [(500.0, wall)])
    _write_spans(d, 1, [{"name": "step_dispatch", "t0": 505.0,
                         "t1": 506.0}])
    trace = tl.merge_chrome_trace(d)
    xs = sorted((e for e in trace["traceEvents"] if e["ph"] == "X"
                 and e["pid"] == 0), key=lambda e: e["ts"])
    # life 0's span at wall+5, life 1's at wall+55: 50s apart aligned,
    # NOT ~90000s apart (raw mono) or half-pooled-median garbage
    assert xs[1]["ts"] - xs[0]["ts"] == pytest.approx(50.0 * 1e6, rel=1e-3)


def test_offsets_use_median_not_mean(tmp_path):
    d = str(tmp_path)
    # one paused-VM outlier pair must not skew the rank's offset
    _write_heartbeats(d, 0, [(10.0, 110.0), (11.0, 111.0),
                             (12.0, 112.0), (13.0, 9999.0)])
    _write_spans(d, 0, [{"name": "a", "t0": 10.0, "t1": 11.0}])
    assert tl.rank_clock_offsets(d)[0] == pytest.approx(100.0)


def test_timeline_lines_bubble_attribution(tmp_path):
    d = str(tmp_path)
    wall = 1.7e9
    _write_spans(d, 0, [{"name": "step_dispatch", "t0": 100.0,
                         "t1": 110.0}],
                 clock={"t_mono": 100.0, "t_unix": wall})
    _write_spans(d, 1, [{"name": "ring_get", "t0": 200.0, "t1": 207.0}],
                 clock={"t_mono": 200.0, "t_unix": wall})
    lines = tl.timeline_lines(d)
    text = "\n".join(lines)
    assert "2 rank(s)" in text
    # rank1's aligned end is 3s before rank0's, stuck in ring_get
    assert "bubble: rank1" in text and "3.00s" in text
    assert "ring_get" in text


# ---------------------------------------------------------------------
# 4. forensics


def test_dump_timeline_merges_live_ring_and_flushed_ranks(tmp_path):
    d = str(tmp_path)
    _write_spans(d, 1, [{"name": "ring_get", "t0": 1.0, "t1": 2.0}])
    tl.configure(enabled=True, run_dir=None, rank=0)
    tl.record_span("step_dispatch", 0.0, 1.0, step=7)
    try:
        path = tl.dump_timeline(d, reason="watchdog", step=7)
        assert path is not None
        dump = json.loads(Path(path).read_text())
        assert dump["reason"] == "watchdog" and dump["step"] == 7
        assert any(s["name"] == "step_dispatch"
                   for s in dump["ranks"]["0"])
        assert any(s["name"] == "ring_get" for s in dump["ranks"]["1"])
        # summarize's attribution renders the dump line
        assert any("timeline dump" in ln for ln in tl.timeline_lines(d))
    finally:
        tl.configure(enabled=True, run_dir=None, rank=0)


def test_dump_timeline_is_best_effort():
    assert tl.dump_timeline(None, reason="oom") is None
    assert tl.dump_timeline("/nonexistent/nope/x", reason="oom") is None


def test_watchdog_fire_drops_timeline_dump(tmp_path):
    """The driver wires ``dump_timeline`` into the watchdog's
    ``forensics_fn``; an in-process fire (injected ``on_timeout``)
    must leave timeline_dump.json behind — the hang forensics."""
    from tpu_hc_bench.resilience import watchdog as watchdog_mod

    d = str(tmp_path)
    tl.configure(enabled=True, run_dir=None, rank=0)
    tl.record_span("device_step", 0.0, 1.0, step=3)
    fired = []
    dog = watchdog_mod.Watchdog(
        0.15, lambda: None, print_fn=lambda s: None,
        on_timeout=lambda age: fired.append(age), poll_s=0.05,
        forensics_fn=lambda: tl.dump_timeline(d, reason="watchdog"))
    dog.start()
    deadline = time.monotonic() + 5.0
    while not fired and time.monotonic() < deadline:
        time.sleep(0.05)
    dog.stop()
    assert fired
    dump = json.loads((tmp_path / tl.TIMELINE_DUMP_NAME).read_text())
    assert dump["reason"] == "watchdog"
    assert any(s["name"] == "device_step" for s in dump["ranks"]["0"])


# ---------------------------------------------------------------------
# 5. obs regress


def _bench_rec(value=2700.0, **extra_over):
    extra = {"global_batch": 128, "chips": 1, "dtype": "bfloat16",
             "peak_hbm_bytes": 1_000_000, "goodput": 0.5}
    extra.update(extra_over)
    return {"metric": "resnet50_synthetic_images_per_sec_per_chip",
            "value": value, "unit": "images/sec/chip", "extra": extra,
            "manifest": {"device_kind": "cpu", "process_count": 1}}


@pytest.fixture()
def bench_history(tmp_path):
    for i in range(5):
        (tmp_path / f"BENCH_r{i:02d}.json").write_text(
            json.dumps({"parsed": _bench_rec(2700.0 + i)}))
    return tmp_path


def test_regress_flags_injected_ten_percent_drop(bench_history):
    out = io.StringIO()
    rc = regress.run_regress(_bench_rec(2700.0 * 0.9),
                             [str(bench_history / "BENCH_*.json")],
                             out=out)
    assert rc == 1
    assert "REGRESSION" in out.getvalue()
    assert "headline" in out.getvalue()


def test_regress_passes_unchanged_rerun(bench_history):
    rc = regress.run_regress(_bench_rec(2702.0),
                             [str(bench_history / "BENCH_*.json")],
                             out=io.StringIO())
    assert rc == 0


def test_regress_improvement_never_flags(bench_history):
    rc = regress.run_regress(_bench_rec(2700.0 * 1.5),
                             [str(bench_history / "BENCH_*.json")],
                             out=io.StringIO())
    assert rc == 0


def test_regress_lower_better_direction(bench_history):
    # HBM peak DOUBLING is a regression even with throughput flat
    out = io.StringIO()
    rc = regress.run_regress(_bench_rec(2702.0, peak_hbm_bytes=2_000_000),
                             [str(bench_history / "BENCH_*.json")],
                             out=out)
    assert rc == 1 and "peak HBM" in out.getvalue()


def test_regress_fingerprint_mismatch_is_no_history(bench_history):
    rec = _bench_rec(1.0, global_batch=256)       # different config
    out = io.StringIO()
    rc = regress.run_regress(rec, [str(bench_history / "BENCH_*.json")],
                             out=out)
    assert rc == 0 and "no history" in out.getvalue()


def test_regress_mad_adapts_to_noisy_history(tmp_path):
    # noisy history (+-10%): a 10% drop is WITHIN the noise band and
    # must not flag — the fixed-threshold failure mode this gate avoids
    for i, v in enumerate([2400, 2700, 3000, 2500, 2900]):
        (tmp_path / f"BENCH_r{i:02d}.json").write_text(
            json.dumps({"parsed": _bench_rec(float(v))}))
    rc = regress.run_regress(_bench_rec(2700.0 * 0.9),
                             [str(tmp_path / "BENCH_*.json")],
                             out=io.StringIO())
    assert rc == 0


def test_regress_parses_driver_bench_wrapper(tmp_path):
    # the driver's wrapper shape: the bench.py record under "parsed",
    # beside the command, its exit code and the captured tail
    path = tmp_path / "BENCH_r00.json"
    path.write_text(json.dumps({
        "n": 0, "cmd": "python bench.py", "rc": 0, "tail": "...",
        "parsed": _bench_rec(2669.89)}))
    rec = regress.load_bench_record(str(path))
    assert rec is not None and rec["value"] > 0
    assert regress.fingerprint(rec)[0].startswith("resnet50")


def test_regress_cli_exit_codes(bench_history, capsys):
    fresh = bench_history / "fresh.json"
    fresh.write_text(json.dumps(_bench_rec(2700.0 * 0.9)))
    rc = obs_main(["regress", str(fresh), "--history",
                   str(bench_history / "BENCH_*.json")],
                  out=io.StringIO())
    assert rc == 1
    # the gate never compares a file against itself: the fresh path is
    # excluded even when the history glob matches it
    fresh2 = bench_history / "BENCH_fresh.json"
    fresh2.write_text(json.dumps(_bench_rec(2700.0 * 0.9)))
    rc = obs_main(["regress", str(fresh2), "--history",
                   str(bench_history / "BENCH_*.json")],
                  out=io.StringIO())
    assert rc == 1
    assert obs_main(["regress", str(bench_history / "nope.json")],
                    out=io.StringIO()) == 2


# ---------------------------------------------------------------------
# 6. span-in-compiled-fn lint


_LINT_BAD = """
import jax
from tpu_hc_bench.obs import timeline

@jax.jit
def step(x):
    timeline.record_span("step", 0.0, 1.0)
    return x * 2
"""

_LINT_BAD_NESTED = """
import jax
from tpu_hc_bench.obs import timeline as timeline_mod


def build(mesh):
    def step(x):
        timeline_mod.instant("mark")
        return x + 1
    return jax.jit(step)
"""

_LINT_GOOD = """
import jax, time
from tpu_hc_bench.obs import timeline


def run(step_fn, x):
    t0 = time.monotonic()
    y = step_fn(x)
    timeline.record_span("step_dispatch", t0, time.monotonic())
    return y
"""

_LINT_GOOD_OTHER_SPAN = """
import jax

@jax.jit
def step(tracer):
    return tracer.span(3)      # somebody else's .span — not the recorder
"""


def test_lint_flags_recorder_call_in_jit():
    f = [x for x in lints.lint_source_text(_LINT_BAD)
         if x.lint == lints.SPAN_IN_JIT]
    assert len(f) == 1 and f[0].severity == "error"
    assert "record_span" in f[0].message


def test_lint_flags_nested_traced_fn():
    f = [x for x in lints.lint_source_text(_LINT_BAD_NESTED)
         if x.lint == lints.SPAN_IN_JIT]
    assert len(f) == 1


_LINT_BAD_BARE_IMPORT = """
import jax
from tpu_hc_bench.obs.timeline import transition

@jax.jit
def step(x):
    transition("step")
    return x * 2
"""


def test_lint_flags_bare_imported_recorder_call():
    # `from ...timeline import transition` leaves no dotted prefix to
    # recognize — the import binding itself marks the call
    f = [x for x in lints.lint_source_text(_LINT_BAD_BARE_IMPORT)
         if x.lint == lints.SPAN_IN_JIT]
    assert len(f) == 1


def test_lint_allows_host_side_recording():
    assert not [x for x in lints.lint_source_text(_LINT_GOOD)
                if x.lint == lints.SPAN_IN_JIT]


def test_lint_ignores_unrelated_span_methods():
    assert not [x for x in lints.lint_source_text(_LINT_GOOD_OTHER_SPAN)
                if x.lint == lints.SPAN_IN_JIT]


def test_lint_suppression_token():
    src = _LINT_BAD.replace(
        'timeline.record_span("step", 0.0, 1.0)',
        'timeline.record_span("step", 0.0, 1.0)  '
        '# thb:lint-ok[span-in-compiled-fn]')
    assert not [x for x in lints.lint_source_text(src)
                if x.lint == lints.SPAN_IN_JIT]


# ---------------------------------------------------------------------
# 7. e2e against the shared rewind_run fixture + fleet satellites


def test_rewind_run_persists_spans_by_default(rewind_run):
    """On-by-default: the fixture sets no --flight_recorder flag, yet
    its run dir carries rank 0's span file with every driver lane."""
    spans = tl.read_spans(rewind_run["dir"])
    assert 0 in spans and spans[0]
    names = {s["name"] for s in spans[0]}
    # fine driver spans + the coarse goodput lane + checkpoint spans
    assert {"input_wait", "step_dispatch", "device_step",
            "compile", "ckpt_write"} <= names
    # rewind fault injected at step 1: the restore span is on the tape
    assert "ckpt_restore" in names


def test_rewind_run_chrome_trace_cli(rewind_run, tmp_path):
    out_path = str(tmp_path / "t.trace.json")
    buf = io.StringIO()
    assert obs_main(["timeline", rewind_run["dir"], "-o", out_path],
                    out=buf) == 0
    trace = json.loads(Path(out_path).read_text())
    assert any(e.get("name") == "device_step"
               for e in trace["traceEvents"])
    assert trace["metadata"]["aligned_ranks"] == [0]
    assert "chrome trace written" in buf.getvalue()


def test_rewind_run_summarize_renders_timeline(rewind_run):
    buf = io.StringIO()
    assert obs_main(["summarize", rewind_run["dir"]], out=buf) == 0
    text = buf.getvalue()
    assert "timeline: 1 rank(s)" in text


def test_rewind_run_heartbeat_phase_and_incarnation(rewind_run):
    recs = fleet.read_heartbeats(rewind_run["dir"])[0]
    assert recs
    for r in recs:
        assert r["incarnation"] == 0
        assert isinstance(r["t_mono"], float)
    assert any(r.get("phase") for r in recs)


def test_rewind_run_watch_renders_phase_column(rewind_run):
    from tpu_hc_bench.obs import metrics as obs_metrics
    from tpu_hc_bench.obs import watch as watch_mod

    manifest, records = obs_metrics.read_run(rewind_run["dir"])
    lines = watch_mod.render(rewind_run["dir"], manifest, records)
    row = [ln for ln in lines if ln.strip().startswith("rank0:")]
    assert row and "phase" in row[0]


def test_fleet_writer_appends_across_incarnations(tmp_path):
    """The round-17 fix: an elastic resume into the same run dir used
    to TRUNCATE the prior life's heartbeats; now it appends, tagged."""
    w1 = fleet.FleetWriter(str(tmp_path), process_index=0)
    assert w1.incarnation == 0
    w1.heartbeat(step=5, step_ewma_ms=1.0)
    w1.close()
    w2 = fleet.FleetWriter(str(tmp_path), process_index=0)
    assert w2.incarnation == 1
    w2.heartbeat(step=1, step_ewma_ms=2.0)
    w2.close()
    recs = fleet.read_heartbeats(str(tmp_path))[0]
    assert [r["step"] for r in recs] == [5, 1]     # both lives survive
    assert [r["incarnation"] for r in recs] == [0, 1]


def test_flight_recorder_off_flag(tmp_path):
    from tpu_hc_bench import flags

    cfg = flags.BenchmarkConfig(flight_recorder="off").resolve()
    assert cfg.flight_recorder == "off"
    with pytest.raises(ValueError, match="flight_recorder"):
        flags.BenchmarkConfig(flight_recorder="maybe").resolve()
    # the off switch stops the ring cold
    rec = tl.SpanRecorder()
    rec.enabled = False
    rec.record("x", 0.0, 1.0)
    assert rec.tail() == []


def _train_overhead(request):
    """The driver records <= 4 spans per step (input_wait,
    step_dispatch, one fetch-thread device_step, an amortized share of
    the sync-window flush), against the fixture's measured steady-state
    step."""
    rec = tl.SpanRecorder(capacity=1024)
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        rec.record("overhead_probe", 0.0, 1.0, step=i)
    per_span_s = (time.perf_counter() - t0) / n
    rewind_run = request.getfixturevalue("rewind_run")
    return 4 * per_span_s, rewind_run["result"].mean_step_ms / 1e3


def _serve_overhead(request):
    """The serve loop crosses ten phase boundaries an iteration (eight
    phases, the ``decode`` parent's open and close), each one clock
    read, a fold update, a ring store and an inactive TraceMe pair;
    against a tiny engine's measured decode step (dispatch + wait)."""
    import jax  # noqa: F401  (the annotation sink needs jax imported)

    engine = request.getfixturevalue("moe_engine")
    summary = engine.run(request.getfixturevalue("moe_requests"))
    lp = summary["loop_phases"]
    step_s = ((lp["decode_dispatch"]["wall_s"] + lp["decode_wait"]["wall_s"])
              / summary["decode_steps"])
    costs = []
    for _ in range(5):      # the least of five: the cost, not the noise
        ph = tl.Phases(tl.SpanRecorder(capacity=1024))
        n = 2_000
        t0 = time.perf_counter()
        for _ in range(n):
            ph.enter("arrivals")
            ph.enter("admit_host")
            ph.enter("telemetry")
            ph.enter("pack")
            ph.enter("decode_dispatch", parent="decode")
            ph.enter("decode_wait", parent="decode")
            ph.enter("retire")
            ph.enter("telemetry")
        costs.append((time.perf_counter() - t0) / n)
        assert ph.fold["decode_wait"][0] == n
    return min(costs), step_s


@pytest.mark.parametrize("lane", ["train", "serve"])
def test_recorder_overhead_under_one_percent(lane, request):
    """The bounded-overhead guard: what a lane records per step must
    stay under 1% of its measured step."""
    per_step_s, step_s = {"train": _train_overhead,
                          "serve": _serve_overhead}[lane](request)
    assert step_s > 0
    assert per_step_s < 0.01 * step_s, (
        f"recorder overhead {per_step_s * 1e6:.1f}us/step vs 1% of "
        f"step {0.01 * step_s * 1e6:.1f}us")


def test_serve_engine_records_spans(tmp_path):
    """Serving lane instrumentation without a new engine warmup: the
    span call sites live in ``_timed``/admit and the loop's phases,
    pinned here by source inspection (full engine runs are
    test_serve_phases' job)."""
    import inspect

    from tpu_hc_bench.serve import loop as loop_mod

    src = inspect.getsource(loop_mod.ServeLoop)
    assert 'phases.enter(kind + "_dispatch", parent=kind)' in src
    assert 'phases.enter("retire")' in src
    assert 'timeline_mod.instant("admit"' in src


_MERGE_WORKER = """
import sys
import tpu_hc_bench  # noqa: F401  (JAX version shims before config)
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)

from tpu_hc_bench.parallel import distributed
from tpu_hc_bench import flags
from tpu_hc_bench.train import driver

port, mdir = int(sys.argv[1]), sys.argv[2]
distributed.initialize(coordinator_port=port)
assert jax.process_count() == 2 and jax.device_count() == 4
cfg = flags.BenchmarkConfig(
    model="trivial", num_classes=10, batch_size=1,
    num_warmup_batches=1, num_batches=4, display_every=2,
    metrics_dir=mdir).resolve()
res = driver.run_benchmark(cfg, print_fn=lambda s: None)
print(f"TL_MERGE_OK process={jax.process_index()} "
      f"rate={res.total_images_per_sec:.1f}", flush=True)
"""


@pytest.mark.slow
def test_two_rank_run_merges_one_trace(tmp_path):
    """The acceptance merge on REAL processes: a 2-process driver run
    leaves spans.0.jsonl AND spans.1.jsonl in the shared run dir, and
    `obs timeline` merges them into one aligned Chrome-trace file."""
    import socket
    import subprocess
    import sys as _sys
    import textwrap

    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(_MERGE_WORKER))
    hostfile = tmp_path / "nodeips.txt"
    hostfile.write_text("127.0.0.1\n127.0.0.1\n")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    mdir = tmp_path / "m"
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update({
            "TPU_HC_BENCH_HOSTFILE": str(hostfile),
            "TPU_HC_BENCH_PROCESS_ID": str(pid),
            "PYTHONPATH": f"{REPO}:{env.get('PYTHONPATH', '')}",
            "JAX_PLATFORMS": "cpu",
        })
        procs.append(subprocess.Popen(
            [_sys.executable, str(script), str(port), str(mdir)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {i} failed:\n{out}"
        assert "TL_MERGE_OK" in out
    spans = tl.read_spans(str(mdir))
    assert sorted(spans) == [0, 1] and all(spans.values())
    buf = io.StringIO()
    assert obs_main(["timeline", str(mdir)], out=buf) == 0
    trace = json.loads((mdir / "timeline.trace.json").read_text())
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert sorted({e["pid"] for e in xs}) == [0, 1]
    assert trace["metadata"]["aligned_ranks"] == [0, 1]
    assert "2 rank(s)" in buf.getvalue()


@pytest.mark.slow
def test_input_service_spans_e2e(tmp_path):
    """The data-service lanes (svc_decode / ring_put / ring_get) land on
    the recorder when a service streams batches."""
    import numpy as np

    from tpu_hc_bench.data import service as service_mod

    tl.configure(enabled=True, run_dir=None, rank=0)
    layout = service_mod.BatchLayout(
        [service_mod.ArraySpec("x", (4, 8), "float32")])

    def make_stream(w):
        def gen():
            for i in range(3):
                yield (np.full((4, 8), i, np.float32),)
        return gen()

    svc = service_mod.InputService(
        f"thbtl{os.getpid() % 100000}", layout, num_workers=1,
        make_stream=make_stream, depth=2).start()
    client = service_mod.ServiceClient(svc.name, layout, worker=0,
                                       depth=2, copy=True)
    got = list(client)
    client.close()
    svc.stop()
    assert len(got) == 3
    names = {s["name"] for s in tl.get_recorder().tail(256)}
    assert {"svc_decode", "ring_put", "ring_get"} <= names
