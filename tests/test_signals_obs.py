"""Mergeable quantile sketches + the streaming health-signal engine
(round 24, ``tpu_hc_bench/obs/sketch.py`` + ``obs/signals.py`` + the
serve/driver/fleet wiring).

Default lane is host-only — the sketch and signal engines are pure
record processing, and every closed-loop assertion rides the session
serve fixtures from conftest (the ONE warmed moe engine and the shared
``moe_ab`` two-arm loop in virtual time) — zero new engine warmups and
zero driver runs.

The load-bearing pins:

- **merge algebra**: bucket-wise merge is associative and commutative
  — the merged sketch answers exactly what the sketch of the
  concatenated stream answers, which averaged per-host p99s do not;
- **relative-error bound**: every quantile lands inside the exact
  order-statistic bracket widened by alpha, on adversarial
  distributions (heavy tail, two-point, constant);
- **hysteresis**: a one-window spike never fires; a sustained breach
  fires after ``fire_windows``; clearing debounces across the dead
  band; a no-evidence window holds every streak;
- **bounded retention**: the engine's raw-sample ring is capped while
  the sketch keeps run-lifetime percentiles — the week-long-serve
  memory leak the sketch exists to close;
- **registry**: signal-name literals lint against ``KNOWN_SIGNALS``
  (the span-name-registry pattern), and the repo baseline stays clean.
"""

from __future__ import annotations

import json
import math
import os
import pathlib

import pytest

from tpu_hc_bench.obs import metrics as obs_metrics
from tpu_hc_bench.obs import regress
from tpu_hc_bench.obs import signals as signals_mod
from tpu_hc_bench.obs import sketch as sketch_mod
from tpu_hc_bench.obs.sketch import QuantileSketch
from tpu_hc_bench.serve import slo

from conftest import SERVE_VCOSTS


def _records_of(mdir: str) -> list[dict]:
    return [json.loads(l) for l in open(os.path.join(mdir,
                                                     "metrics.jsonl"))]


def _exact_bracket(values: list[float], q: float) -> tuple[float, float]:
    """The order-statistic bracket the sketch's answer must land in
    (rank convention matches slo.percentile / sketch.quantile)."""
    vs = sorted(values)
    rank = q / 100.0 * (len(vs) - 1)
    return vs[int(rank)], vs[min(int(rank) + 1, len(vs) - 1)]


def _assert_within(sk: QuantileSketch, values: list[float],
                   qs=(0, 10, 50, 90, 95, 99, 100)) -> None:
    for q in qs:
        lo, hi = _exact_bracket(values, q)
        got = sk.quantile(q)
        assert lo * (1 - sk.alpha) - 1e-12 <= got \
            <= hi * (1 + sk.alpha) + 1e-12, \
            f"q{q}: {got} outside [{lo}, {hi}] +/- alpha"


# --- sketch: algebra, error bound, edges ------------------------------

def test_sketch_error_bound_adversarial():
    # heavy tail spanning 6 decades, a two-point bimodal, a constant
    # stream, and near-zero values against the zero bucket
    heavy = [1.0001 ** i * 0.1 for i in range(0, 6000, 7)]
    two_point = [1.0] * 99 + [5000.0]
    const = [42.0] * 257
    # exact zeros ride the zero bucket; positives keep the alpha bound
    zeros = [0.0, 0.0, 0.0, 1e-6, 0.5, 1.0]
    for values in (heavy, two_point, const, zeros):
        _assert_within(sketch_mod.sketch_of(values), values)


def test_sketch_merge_associative_commutative():
    a = [0.5 * i for i in range(1, 40)]
    b = [100.0 + 3.0 * i for i in range(30)]
    c = [0.001, 0.01, 7000.0, 12.5]
    sks = {k: sketch_mod.sketch_of(v) for k, v in
           (("a", a), ("b", b), ("c", c))}

    def fresh(name):
        return QuantileSketch().merge(sks[name])

    ab_c = fresh("a").merge(fresh("b")).merge(fresh("c"))
    a_bc = fresh("a").merge(fresh("b").merge(fresh("c")))
    cba = fresh("c").merge(fresh("b")).merge(fresh("a"))
    direct = sketch_mod.sketch_of(a + b + c)
    for q in (0, 25, 50, 75, 90, 99, 100):
        assert ab_c.quantile(q) == a_bc.quantile(q) == cba.quantile(q) \
            == direct.quantile(q)
    assert ab_c.count == direct.count == len(a) + len(b) + len(c)
    _assert_within(ab_c, a + b + c)


def test_sketch_merge_alpha_mismatch_raises():
    with pytest.raises(ValueError):
        QuantileSketch(alpha=0.01).merge(QuantileSketch(alpha=0.02))


def test_sketch_empty_and_single():
    sk = QuantileSketch()
    assert sk.count == 0 and sk.quantile(50) == 0.0 and sk.mean() == 0.0
    sk.add(17.25)
    for q in (0, 50, 100):
        assert sk.quantile(q) == 17.25
    # merging an empty sketch is the identity
    merged = QuantileSketch().merge(sk)
    assert merged.quantile(99) == 17.25 and merged.count == 1
    # negative jitter clamps, never raises
    sk2 = QuantileSketch()
    sk2.add(-0.0)
    sk2.add(-5.0)
    assert sk2.quantile(100) == 0.0 and sk2.count == 2


def test_sketch_record_roundtrip_and_merge_records():
    values = [0.3 * i for i in range(1, 200)]
    halves = [values[:100], values[100:]]
    recs = [sketch_mod.sketch_of(h).to_record() for h in halves]
    # the jsonl trip must preserve the answers exactly
    recs = json.loads(json.dumps(recs))
    merged = sketch_mod.merge_records(recs)
    direct = sketch_mod.sketch_of(values)
    for q in (0, 50, 95, 99, 100):
        assert merged.quantile(q) == direct.quantile(q)
    # absent history folds to absent, never a KeyError
    assert sketch_mod.merge_records([]) is None
    assert sketch_mod.merge_records([None, "x"]) is None


def test_sketch_collapse_bounds_memory_keeps_tail():
    sk = QuantileSketch(max_buckets=32)
    values = [1.002 ** i for i in range(4000)]   # ~3.5 decades
    for v in values:
        sk.add(v)
    assert len(sk.buckets) <= 32
    assert sk.count == len(values)
    # collapse folds the LOW end: the SLO tail stays within bound (the
    # 32 surviving buckets cover the top few percent of this range),
    # and the collapsed low quantiles only ever bias UPWARD — a capped
    # sketch never understates a latency
    for q in (95, 99, 100):
        lo, hi = _exact_bracket(values, q)
        assert lo * (1 - sk.alpha) <= sk.quantile(q) <= hi * (1 + sk.alpha)
    lo50, _ = _exact_bracket(values, 50)
    assert sk.quantile(50) >= lo50 * (1 - sk.alpha)


def test_sketch_from_counts_matches_service_histogram():
    hist = [0, 5, 0, 3, 9, 0, 0, 2]     # counts[v] = occurrences of v
    sk = QuantileSketch.from_counts(hist)
    values = [float(v) for v, n in enumerate(hist) for _ in range(n)]
    assert sk.count == len(values)
    # small ints resolve exactly at alpha=1%
    for q in (0, 50, 90, 100):
        assert round(sk.quantile(q)) in values


# --- signal engine: hysteresis ----------------------------------------

def test_signal_one_window_spike_never_fires():
    eng = signals_mod.SignalEngine()
    eng.observe(1.0, {"SUSTAINED_OVERLOAD": 0.9})
    eng.observe(2.0, {"SUSTAINED_OVERLOAD": 0.0})
    eng.observe(3.0, {"SUSTAINED_OVERLOAD": 0.9})
    eng.observe(4.0, {"SUSTAINED_OVERLOAD": 0.0})
    assert eng.events == [] and eng.active == {} and eng.fired == {}


def test_signal_sustained_fires_then_debounced_clear():
    eng = signals_mod.SignalEngine()
    assert eng.observe(1.0, {"KV_PRESSURE": 0.8}) == []
    evs = eng.observe(2.0, {"KV_PRESSURE": 0.7},
                      causes={"KV_PRESSURE": {"pool_starved_s": 1.2}})
    assert len(evs) == 1 and evs[0]["state"] == "fire"
    assert evs[0]["signal"] == "KV_PRESSURE" and evs[0]["t"] == 2.0
    assert evs[0]["cause"] == {"pool_starved_s": 1.2}
    assert "KV_PRESSURE" in eng.active
    # 0.3 is under fire (0.5) but NOT under clear (0.25): holds active
    assert eng.observe(3.0, {"KV_PRESSURE": 0.3}) == []
    # one recovered window is not enough (clear_windows=2)
    assert eng.observe(4.0, {"KV_PRESSURE": 0.1}) == []
    evs = eng.observe(5.0, {"KV_PRESSURE": 0.1})
    assert len(evs) == 1 and evs[0]["state"] == "clear"
    assert evs[0]["since"] == 2.0
    assert eng.active == {}
    assert signals_mod.fired_count(eng.events, "KV_PRESSURE") == 1


def test_signal_none_holds_streaks_and_active_state():
    eng = signals_mod.SignalEngine()
    eng.observe(1.0, {"SUSTAINED_OVERLOAD": 0.9})
    # silence is not health: the breach streak survives the gap
    eng.observe(2.0, {"SUSTAINED_OVERLOAD": None})
    evs = eng.observe(3.0, {"SUSTAINED_OVERLOAD": 0.9})
    assert [e["state"] for e in evs] == ["fire"]
    # and an active signal never clears on no-evidence windows
    eng.observe(4.0, {})
    eng.observe(5.0, {"SUSTAINED_OVERLOAD": None})
    assert "SUSTAINED_OVERLOAD" in eng.active


def test_signal_direction_below_goodput_collapse():
    eng = signals_mod.SignalEngine()
    for t in (1.0, 2.0):
        eng.observe(t, {"GOODPUT_COLLAPSE": 0.01})
    assert eng.events == []       # fire_windows=3
    evs = eng.observe(3.0, {"GOODPUT_COLLAPSE": 0.01})
    assert [e["state"] for e in evs] == ["fire"]
    # 0.1 is above fire (0.05) but below clear (0.15): holds active
    eng.observe(4.0, {"GOODPUT_COLLAPSE": 0.10})
    eng.observe(5.0, {"GOODPUT_COLLAPSE": 0.30})
    evs = eng.observe(6.0, {"GOODPUT_COLLAPSE": 0.30})
    assert [e["state"] for e in evs] == ["clear"]


def test_signal_registry_surface():
    for name in signals_mod.KNOWN_SIGNALS:
        spec = signals_mod.spec_of(name)
        assert spec.name == name
        assert signals_mod.advice_for(name)
        if spec.direction == "above":
            assert spec.clear_threshold < spec.fire_threshold
        else:
            assert spec.clear_threshold > spec.fire_threshold
    bogus = "NOT_" + "A_SIGNAL"   # built, not literal: the lint's out
    with pytest.raises(ValueError, match="unknown signal"):
        signals_mod.spec_of(bogus)
    with pytest.raises(ValueError):
        signals_mod.fired_count([], bogus)


def test_signal_events_roundtrip_and_folds(tmp_path):
    eng = signals_mod.SignalEngine()
    for t in (1.0, 2.0):
        eng.observe(t, {"KV_PRESSURE": 0.9, "SUSTAINED_OVERLOAD": 0.9})
    path = signals_mod.signals_path(str(tmp_path))
    signals_mod.append_events(path, eng.events)
    signals_mod.append_events(path, [])      # no-op, never truncates
    back = signals_mod.read_signals(str(tmp_path))
    assert back == eng.events
    assert set(signals_mod.active_of(back)) == {"KV_PRESSURE",
                                                "SUSTAINED_OVERLOAD"}
    assert signals_mod.fired_counts(back) == {"KV_PRESSURE": 1,
                                              "SUSTAINED_OVERLOAD": 1}
    lines = signals_mod.signal_lines(back)
    assert any("still active" in ln for ln in lines)
    watch = signals_mod.watch_lines(str(tmp_path))
    assert len(watch) == 1 and "KV_PRESSURE" in watch[0]
    # a run that never signalled renders nothing (no file, no noise)
    assert signals_mod.read_signals(str(tmp_path / "nowhere")) == []
    assert signals_mod.watch_lines(str(tmp_path / "nowhere")) == []


# --- serve-lane wiring (rides the session moe_ab fixture) -------------

def test_summary_carries_sketch_fields(moe_ab):
    for arm in ("static", "continuous"):
        s = moe_ab[arm]["summary"]
        assert s["latency_source"] == "sketch"
        assert s["sketch_windows"] >= 1
        assert s["latency_sample_cap"] >= 1
        # single host: the run sketch IS the merge of its windows
        assert s["p99_merged_ms"] == pytest.approx(s["p99_e2e_ms"])


def test_stream_carries_window_sketches_merged_matches_exact(moe_ab):
    for arm in ("static", "continuous"):
        records = _records_of(moe_ab[arm]["mdir"])
        wins = [r for r in records if r.get("kind") == slo.SKETCH_KIND]
        assert wins, "no latency_sketch records in the stream"
        assert all("window" in r and isinstance(r.get("fields"), dict)
                   for r in wins)
        merged = sketch_mod.merge_records(
            (r["fields"].get("e2e_ms") for r in wins))
        e2e = [float(r["e2e_ms"]) for r in records
               if r.get("kind") == "request"]
        assert merged.count == len(e2e)
        _assert_within(merged, e2e)
        # the offline fold agrees with the engine's own summary
        fold = slo.fold_window_sketches(records)
        assert fold["latency_source"] == "sketch"
        assert fold["sketch_windows"] == len(wins)
        assert fold["p99_merged_ms"] == pytest.approx(
            moe_ab[arm]["summary"]["p99_merged_ms"], abs=1e-3)


def test_fold_window_sketches_absent_on_pre_r24_streams():
    # pre-round-24 stream: no latency_sketch records -> {} (absent and
    # labeled downstream, never a KeyError)
    assert slo.fold_window_sketches(
        [{"kind": "request", "e2e_ms": 5.0}]) == {}
    lines = slo.slo_lines(slo.fold_requests(
        [{"kind": "request", "ttft_ms": 1.0, "e2e_ms": 2.0,
          "queue_ms": 0.5}]))
    assert not any("merged" in ln for ln in lines)


def test_summarize_renders_merged_sketch_line(moe_ab):
    lines = obs_metrics.summarize_run(moe_ab["continuous"]["mdir"])
    assert any("[sketch" in ln and "p99" in ln for ln in lines)


def test_obs_signals_cli(moe_ab, tmp_path, capsys):
    from tpu_hc_bench.obs.__main__ import main as obs_main

    mdir = moe_ab["continuous"]["mdir"]
    rc = obs_main(["signals", mdir])
    rep_out = capsys.readouterr().out
    assert "offline re-evaluation" in rep_out
    # rc contract: 1 iff anything fired (live or offline), 2 when the
    # path is unusable
    fired = signals_mod.fired_counts(
        signals_mod.read_signals(mdir)) or signals_mod.fired_counts(
        signals_mod.evaluate_records(_records_of(mdir), run_dir=mdir))
    assert rc == (1 if fired else 0)
    assert obs_main(["signals", str(tmp_path / "missing")]) == 2
    rc = obs_main(["signals", mdir, "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert set(payload) == {"recorded", "evaluated", "fired"}


def test_bounded_retention_long_trace(moe_engine, monkeypatch):
    """The round-24 memory pin: a long VirtualClock trace through the
    warmed engine with the raw ring pinned tiny — completion counting,
    percentiles, and the burn fold must all keep working off the
    run-lifetime sketches while raw retention stays at the cap."""
    from tpu_hc_bench import flags
    from tpu_hc_bench.serve import arrivals
    from tpu_hc_bench.serve import engine as engine_mod
    from tpu_hc_bench.serve import loop as loop_mod

    monkeypatch.setattr(loop_mod, "_DONE_SAMPLE_CAP", 6)
    cfg = flags.BenchmarkConfig(
        model="moe_tiny", workload="serve", arrival_rate=200.0,
        num_requests=24, max_prompt_len=8, max_output_len=4,
        max_in_flight=2, kv_page_size=4, seed=3).resolve()
    reqs = arrivals.build_requests(cfg, moe_engine.spec.vocab_size)
    summary = moe_engine.run(
        reqs, batching="continuous",
        clock=engine_mod.VirtualClock(SERVE_VCOSTS))
    # every completion counted, even though only 6 raw records survive
    assert summary["completed"] == 24
    assert summary["latency_sample_cap"] == 6
    # the sketch percentiles cover the WHOLE run, not the ring
    assert summary["p99_merged_ms"] == pytest.approx(
        summary["p99_e2e_ms"])
    assert summary["p99_e2e_ms"] >= summary["p50_e2e_ms"] > 0
    assert summary["sketch_windows"] >= 1


def test_engine_emits_signals_on_sustained_overload(moe_engine,
                                                    tmp_path):
    """A deliberately-impossible e2e target over a burst trace: the
    live engine must fire SUSTAINED_OVERLOAD (hysteresis-gated, so
    only after consecutive breached windows) and journal it into
    signals.jsonl beside the stream."""
    from tpu_hc_bench import flags
    from tpu_hc_bench.serve import arrivals
    from tpu_hc_bench.serve import engine as engine_mod

    cfg = flags.BenchmarkConfig(
        model="moe_tiny", workload="serve", arrival_rate=5000.0,
        num_requests=24, max_prompt_len=8, max_output_len=4,
        max_in_flight=2, kv_page_size=4, seed=1).resolve()
    reqs = arrivals.build_requests(cfg, moe_engine.spec.vocab_size)
    mdir = str(tmp_path / "overload")
    writer = obs_metrics.MetricsWriter(
        mdir, obs_metrics.run_manifest(cfg=moe_engine.cfg,
                                       extra={"workload": "serve"}))
    try:
        summary = moe_engine.run(
            reqs, batching="continuous", writer=writer,
            clock=engine_mod.VirtualClock(SERVE_VCOSTS),
            deadline_ms=1.0, shed="off", kv_preempt="off")
    finally:
        writer.close()
    assert summary["signals_fired"].get("SUSTAINED_OVERLOAD", 0) >= 1
    assert summary["signals_fired_total"] >= 1
    events = signals_mod.read_signals(mdir)
    fires = [e for e in events if e.get("state") == "fire"
             and e.get("signal") == "SUSTAINED_OVERLOAD"]
    assert fires and fires[0].get("cause", {}).get("target_ms") == 1.0
    # hysteresis: the fire credits >= fire_windows consecutive windows
    assert fires[0]["windows"] >= signals_mod.spec_of(
        "SUSTAINED_OVERLOAD").fire_windows
    # the live column renders it
    assert any("SUSTAINED_OVERLOAD" in ln
               for ln in signals_mod.watch_lines(mdir))
    # and a clean run fires nothing: the moe_ab arms carry no target
    # (no deadline/slo), so the engine holds "no evidence" forever


def test_clean_run_fires_nothing(moe_ab):
    for arm in ("static", "continuous"):
        s = moe_ab[arm]["summary"]
        assert s["signals_fired"] == {}
        assert s["signals_fired_total"] == 0
        assert signals_mod.read_signals(moe_ab[arm]["mdir"]) == []


# --- fleet supervisor: advisory journaling ----------------------------

def test_supervisor_journals_signals_log_only(tmp_path):
    from tpu_hc_bench.fleet.pool import DevicePool, JobSpec
    from tpu_hc_bench.fleet.supervisor import RUNNING, FleetController

    out = str(tmp_path / "fleet")
    ctl = FleetController(DevicePool(4), [], out,
                          print_fn=lambda s: None)
    st = ctl.supervisor.add(JobSpec(
        name="j0", model="trivial", batch_size=2,
        world_pref=2, world_min=2))
    st.status = RUNNING
    st.run_dir = str(tmp_path / "j0")
    mdir = os.path.join(st.run_dir, "m")
    os.makedirs(mdir)
    sig_path = signals_mod.signals_path(mdir)
    fire = {"kind": "signal", "t": 3.25, "signal": "KV_PRESSURE",
            "state": "fire", "measure": 0.9, "threshold": 0.5,
            "windows": 2}
    with open(sig_path, "w") as f:
        f.write(json.dumps(fire) + "\n")
        f.write('{"kind": "signal", "t": 4.0, "sig')   # mid-write tail
    ctl._scan_signals()
    events = [json.loads(l)
              for l in open(os.path.join(out, "fleet_events.jsonl"))]
    sigs = [e for e in events if e["kind"] == "signal"]
    advs = [e for e in events if e["kind"] == "signal_advice"]
    assert len(sigs) == 1 and sigs[0]["signal"] == "KV_PRESSURE"
    assert sigs[0]["t_sig"] == 3.25 and sigs[0]["job"] == "j0"
    # actuation is ADVISORY by contract: journaled advice, no lever
    assert len(advs) == 1 and advs[0]["actuation"] == "log-only"
    assert advs[0]["advice"] == signals_mod.advice_for("KV_PRESSURE")
    assert st.status == RUNNING
    # the partial line was NOT consumed; completing it lands it once
    with open(sig_path, "a") as f:
        f.write('nal": "STRAGGLER", "state": "clear"}\n')
    ctl._scan_signals()
    ctl._scan_signals()     # idempotent: offsets advance past consumed
    events = [json.loads(l)
              for l in open(os.path.join(out, "fleet_events.jsonl"))]
    sigs = [e for e in events if e["kind"] == "signal"]
    assert len(sigs) == 2 and sigs[1]["signal"] == "STRAGGLER"
    assert len([e for e in events
                if e["kind"] == "signal_advice"]) == 1


# --- lint + regress satellites ----------------------------------------

def test_lint_signal_name_registry():
    from tpu_hc_bench.analysis import lints

    bad = [f for f in lints.lint_source_text(
        'from tpu_hc_bench.obs import signals as signals_mod\n'
        'n = signals_mod.fired_count([], "KV_PRESURE")\n',
        filename="x.py") if f.lint == lints.SIGNAL_REGISTRY]
    assert len(bad) == 1 and "KV_PRESURE" in bad[0].message
    ok = [f for f in lints.lint_source_text(
        'from tpu_hc_bench.obs.signals import spec_of\n'
        'spec_of("SUSTAINED_OVERLOAD")\n'
        'def g(events, name):\n'
        '    return spec_of(name)\n',
        filename="x.py") if f.lint == lints.SIGNAL_REGISTRY]
    assert ok == []
    # suppression spelling works for this pass too
    sup = [f for f in lints.lint_source_text(
        'from tpu_hc_bench.obs.signals import spec_of\n'
        'spec_of("LEGACY")  # tpu-hc: disable=signal-name-registry\n',
        filename="x.py") if f.lint == lints.SIGNAL_REGISTRY]
    assert sup == []
    assert lints.SIGNAL_REGISTRY in lints.ALL_SOURCE_LINTS


def test_lint_repo_baseline_clean_of_signal_findings():
    # the full-tree gate (test_analysis's repo source gate) already runs
    # every registered pass including this one; here we lint only the
    # files that can trigger it — anything naming a registry callee —
    # so the check stays honest without re-paying the repo-scope passes
    from tpu_hc_bench.analysis import lints

    root = pathlib.Path(lints.__file__).resolve().parents[2]
    callees = tuple(lints._FileLinter._SIGNAL_NAME_CALLEES)
    findings = []
    for sub in ("tpu_hc_bench", "scripts"):
        for path in sorted((root / sub).rglob("*.py")):
            text = path.read_text()
            if not any(c in text for c in callees):
                continue
            findings += [f for f in lints.lint_source_text(
                             text, str(path.relative_to(root)))
                         if f.lint == lints.SIGNAL_REGISTRY]
    assert findings == [], findings


def test_regress_gates_merged_p99_direction_aware():
    base = {"metric": "m", "value": 1.0, "unit": "u",
            "extra": {"p99_merged_ms": 50.0, "signals_fired_total": 0}}
    hist = [json.loads(json.dumps(base)) for _ in range(4)]
    # pre-r24 history lacks the fields entirely: structural skip
    old = {"metric": "m", "value": 1.0, "unit": "u", "extra": {}}
    verdict = regress.regress_check(base, [old] * 4)
    assert not any(c["metric"] == "p99 merged ms"
                   for c in verdict["checked"])
    # a big rise regresses; a drop never does
    worse = json.loads(json.dumps(base))
    worse["extra"]["p99_merged_ms"] = 80.0
    verdict = regress.regress_check(worse, hist)
    assert any(r["metric"] == "p99 merged ms"
               for r in verdict["regressions"])
    better = json.loads(json.dumps(base))
    better["extra"]["p99_merged_ms"] = 30.0
    assert regress.regress_check(better, hist)["regressions"] == []
    # ONE fire on a clean-history config flags (abs floor = 1 fire)
    fired = json.loads(json.dumps(base))
    fired["extra"]["signals_fired_total"] = 1
    verdict = regress.regress_check(fired, hist)
    assert any(r["metric"] == "signals fired"
               for r in verdict["regressions"])


def test_driver_step_sketch_weighted():
    from tpu_hc_bench.train import driver as driver_mod

    # __new__ skips the fetcher thread: only the timed intervals matter
    tl = driver_mod._AsyncTimeline.__new__(driver_mod._AsyncTimeline)
    tl.per_step_times = [(0.010, 1), (0.010, 1), (0.010, 1), (0.070, 1)]
    sk = tl.step_sketch()
    assert sk is not None and sk.count == 4
    # three 10ms intervals and one 70ms straggler: the p50 is 10ms
    # within the sketch's relative error
    assert tl.p50_step_ms() == pytest.approx(10.0, rel=0.02)
    # a coalesced-over stretch weights as the steps it spans, not one
    tl.per_step_times = [(0.010, 9), (0.070, 1)]
    assert tl.p50_step_ms() == pytest.approx(10.0, rel=0.02)
    tl.per_step_times = []
    assert tl.step_sketch() is None
    assert math.isnan(tl.p50_step_ms())
