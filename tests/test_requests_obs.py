"""Request-level tracing & tail-latency attribution (round 20,
``tpu_hc_bench/obs/requests.py`` + serve-lane wiring).

Default lane rides the session serve fixtures from conftest (ONE
warmed moe engine, one classify engine, the shared two-arm ``moe_ab``
closed loop in virtual time) — zero new engine warmups beyond one
extra VirtualClock replay for the SLO-burn path.

The load-bearing pins:

- **conservation invariant**: for every request in every default-lane
  engine run, the five attribution components sum to the measured e2e
  — exactly (float precision) under VirtualClock;
- **back-compat**: pre-round-20 records (no component fields) flow
  through fold/diff/regress normalizing to zero, labeled, never
  KeyError;
- **bounded overhead**: the per-request stamp costs well under the
  round-17 1%-of-step recorder guard;
- span-name-registry lint: typo'd literal span names flag, the repo
  baseline stays clean.
"""

from __future__ import annotations

import json
import os
import time

import pytest

from tpu_hc_bench import flags
from tpu_hc_bench.analysis import lints
from tpu_hc_bench.obs import metrics as obs_metrics
from tpu_hc_bench.obs import regress
from tpu_hc_bench.obs import requests as rq
from tpu_hc_bench.obs import timeline as timeline_mod
from tpu_hc_bench.serve import engine as engine_mod
from tpu_hc_bench.serve import slo

from conftest import SERVE_VCOSTS


def _requests_of(mdir: str) -> list[dict]:
    recs = [json.loads(l) for l in open(os.path.join(mdir,
                                                     "metrics.jsonl"))]
    return [r for r in recs if r.get("kind") == "request"]


# --- the conservation invariant ---------------------------------------


def test_components_conserved_exactly_in_virtual_time(moe_ab):
    """The tentpole pin: every request's components sum to its measured
    e2e — exact under VirtualClock, for BOTH scheduler arms."""
    for arm in ("static", "continuous"):
        reqs = _requests_of(moe_ab[arm]["mdir"])
        assert reqs, arm
        for r in reqs:
            comps = rq.attribution_of(r)
            assert sum(comps.values()) == pytest.approx(
                r["e2e_ms"], abs=1e-6), (arm, r["id"], comps)
            assert all(v >= 0.0 for v in comps.values()), (arm, r)


def test_components_measure_real_work(moe_ab):
    """The decomposition is measurement, not padding: prefill matches
    the modeled prefill cost, multi-token requests accumulate
    decode_active, and the static arm's tail waits in queue_wait."""
    ct = _requests_of(moe_ab["continuous"]["mdir"])
    for r in ct:
        assert r["prefill_ms"] == pytest.approx(
            1e3 * SERVE_VCOSTS["prefill"], abs=1e-6)
        if r["output_len"] > 1:
            assert r["decode_active_ms"] >= 1e3 * SERVE_VCOSTS["decode"]
        else:
            assert r["decode_active_ms"] == 0.0
    st = _requests_of(moe_ab["static"]["mdir"])
    # static batching makes arrivals wait for a full batch: SOME
    # request must see queue_wait the continuous arm's tail doesn't
    assert max(r["queue_ms"] for r in st) > \
        max(r["queue_ms"] for r in ct)


def test_classify_member_components_conserved(trivial_engine):
    from tpu_hc_bench.serve import arrivals

    reqs = arrivals.build_requests(trivial_engine.cfg, None)
    events = []
    writer = obs_metrics.MetricsWriter(None)
    writer.event = lambda kind, **f: events.append({"kind": kind, **f})
    s = trivial_engine.run(reqs,
                           clock=engine_mod.VirtualClock(SERVE_VCOSTS),
                           writer=writer)
    recs = [e for e in events if e["kind"] == "request"]
    assert len(recs) == len(reqs) and s["completed"] == len(reqs)
    for r in recs:
        comps = rq.attribution_of(r)
        assert sum(comps.values()) == pytest.approx(r["e2e_ms"],
                                                    abs=1e-6)
        # classify members have no prompt pass: the resident window is
        # all decode-lane (active + stall), never "prefill"
        assert comps["prefill"] == 0.0
        assert comps["decode_active"] > 0.0


def test_stall_appears_under_batching_interference(moe_engine):
    """A resident request's wall during a batch-mate's prefill is
    decode_stall — the batching-interference component endpoint
    percentiles cannot see.  Everything arrives at once so admissions
    interleave with decode steps."""
    from tpu_hc_bench.serve import arrivals

    cfg = flags.BenchmarkConfig(
        model="moe_tiny", workload="serve", arrival_rate=10000.0,
        num_requests=8, max_prompt_len=8, max_output_len=4,
        max_in_flight=2, kv_page_size=4, seed=0).resolve()
    reqs = arrivals.build_requests(cfg, moe_engine.spec.vocab_size)
    events = []
    writer = obs_metrics.MetricsWriter(None)
    writer.event = lambda kind, **f: events.append({"kind": kind, **f})
    moe_engine.run(reqs, batching="continuous", writer=writer,
                   clock=engine_mod.VirtualClock(SERVE_VCOSTS))
    recs = [e for e in events if e["kind"] == "request"]
    assert any(r["decode_stall_ms"] > 0 for r in recs), recs
    for r in recs:
        assert sum(rq.attribution_of(r).values()) == pytest.approx(
            r["e2e_ms"], abs=1e-6)


# --- the fold ----------------------------------------------------------


def test_fold_attribution_tail_selection():
    recs = [{"e2e_ms": float(10 * (i + 1)), "queue_ms": float(i),
             "prefill_ms": 1.0, "decode_active_ms": 2.0,
             "decode_stall_ms": 0.5, "retire_ms": 0.0}
            for i in range(20)]
    fold = rq.fold_attribution(recs)
    assert fold["n"] == 20 and fold["tail_n"] == 2
    assert fold["tail_cut_ms"] == 190.0
    assert fold["tail_e2e_ms"] == pytest.approx(195.0)
    assert fold["tail_ms"]["queue_wait"] == pytest.approx(18.5)
    assert fold["has_components"]
    flat = rq.flatten_attribution(fold)
    assert flat["tail_queue_wait_frac"] == \
        fold["tail_frac"]["queue_wait"]
    assert rq.fold_attribution([]) is None


def test_fold_normalizes_pre_r20_records_to_zero():
    """The back-compat seam: round-16 records (queue_ms only) fold to
    zero components, labeled — never KeyError."""
    old = [{"e2e_ms": 50.0, "queue_ms": 10.0, "ttft_ms": 20.0}]
    fold = rq.fold_attribution(old)
    assert not fold["has_components"]
    assert fold["tail_ms"]["decode_stall"] == 0.0
    assert fold["tail_ms"]["queue_wait"] == 10.0   # queue_ms predates r20
    lines = rq.attribution_lines(fold, p99_e2e_ms=50.0)
    assert len(lines) == 1 and "pre-round-20" in lines[0]


def test_attribution_lines_name_the_dominant_component(moe_ab):
    fold = moe_ab["continuous"]["summary"]["attribution"]
    lines = rq.attribution_lines(fold, p99_e2e_ms=13.0)
    assert len(lines) == 1
    assert "p99 e2e 13ms" in lines[0]
    assert "decode_active" in lines[0] and "%" in lines[0]


def test_engine_summary_carries_attribution_and_flat_fracs(moe_ab):
    for arm in ("static", "continuous"):
        s = moe_ab[arm]["summary"]
        assert s["attribution"]["n"] == s["completed"]
        assert "tail_queue_wait_frac" in s
        assert "tail_decode_stall_frac" in s
        # fractions of the conserved decomposition live in [0, 1]
        assert all(0.0 <= v <= 1.0
                   for v in s["attribution"]["tail_frac"].values())


# --- bucket utilization ------------------------------------------------


def test_engine_summary_bucket_util(moe_ab):
    bu = moe_ab["continuous"]["summary"]["bucket_util"]
    assert any(k.startswith("decode@") for k in bu)
    assert any(k.startswith("prefill@") for k in bu)
    for k, u in bu.items():
        assert 0.0 <= u["occupancy"] <= 1.0, k
        assert u["rows"] >= u["active_rows"] >= 0
        assert u["steps"] > 0
    lines = rq.bucket_util_lines(bu)
    assert lines and "bucket util" in lines[0]
    assert any("decode@" in ln and "%" in ln for ln in lines[1:])
    assert rq.bucket_util_lines(None) == []


def test_watch_renders_live_bucket_occupancy():
    recs = [{"kind": "serve", "t": 1.0, "queue_depth": 2, "in_flight": 2,
             "tokens": 9, "bucket_occ": {"decode@2": 0.81,
                                         "prefill@8": 0.5}}]
    lines = slo.watch_lines(recs)
    text = "\n".join(lines)
    assert "bucket occ:" in text and "decode@2 81%" in text


# --- summarize / diff / regress surfaces -------------------------------


def test_summarize_renders_attribution_and_buckets(moe_ab):
    text = "\n".join(obs_metrics.summarize_run(
        moe_ab["continuous"]["mdir"]))
    assert "p99 e2e" in text and "queue ms p50" in text
    assert "bucket util" in text
    assert "slowest" in text        # the tail-attribution line


def test_diff_renders_component_deltas(moe_ab):
    lines = obs_metrics.diff_runs(moe_ab["static"]["mdir"],
                                  moe_ab["continuous"]["mdir"])
    text = "\n".join(lines)
    assert "tail attribution" in text
    assert "queue_wait" in text and "pp" in text
    assert "p99 queue ms" in text   # the new DIFF_METRICS row


def test_diff_normalizes_pre_r20_side_to_zero():
    """Satellite pin: a pre-r20 fold (no attribution) against an r20
    fold renders labeled deltas, no KeyError."""
    new = rq.fold_attribution([{
        "e2e_ms": 100.0, "queue_ms": 60.0, "prefill_ms": 10.0,
        "decode_active_ms": 25.0, "decode_stall_ms": 5.0,
        "retire_ms": 0.0}])
    old = rq.fold_attribution([{"e2e_ms": 80.0, "queue_ms": 20.0}])
    lines = rq.attribution_diff_lines(old, new)
    text = "\n".join(lines)
    assert "queue_wait" in text
    assert "predates request attribution" in text
    # both None (two training runs): nothing renders
    assert rq.attribution_diff_lines(None, None) == []
    # one side entirely absent still renders the present side
    assert rq.attribution_diff_lines(None, new)


def test_serve_diff_lines_old_vs_new_streams(moe_ab):
    """obs diff end-to-end back-compat: an r20 fold against a
    synthesized pre-r20 fold (records stripped of component fields)."""
    recs = _requests_of(moe_ab["continuous"]["mdir"])
    old_recs = [{k: v for k, v in r.items()
                 if k not in ("prefill_ms", "decode_active_ms",
                              "decode_stall_ms", "retire_ms")}
                for r in recs]
    fold_new = slo.fold_serve_records(
        [{"kind": "request", **r} for r in recs])
    fold_old = slo.fold_serve_records(
        [{"kind": "request", **r} for r in old_recs])
    lines = slo.serve_diff_lines(fold_old, fold_new)
    text = "\n".join(lines)
    assert "tail attribution" in text
    assert "note: run a predates request attribution" in text


def test_regress_gates_on_attribution_shift(tmp_path):
    """A tail that shifted from compute to waiting flags even when p99
    itself moved little; pre-r20 history (no fields) skips the checks
    instead of KeyError-ing."""
    base = {"metric": "moe_tiny_serve_tokens_per_s", "value": 100.0,
            "unit": "tokens/sec",
            "extra": {"batching": "continuous", "arrival_rate": 16.0,
                      "p99_ms": 100.0, "goodput": 0.5,
                      "tokens_per_s": 100.0,
                      "tail_queue_wait_frac": 0.10,
                      "tail_decode_stall_frac": 0.05}}
    hist = [json.loads(json.dumps(base)) for _ in range(4)]
    fresh = json.loads(json.dumps(base))
    fresh["extra"]["tail_queue_wait_frac"] = 0.60   # tail now waits
    verdict = regress.regress_check(fresh, hist)
    assert any(r["metric"] == "tail queue_wait frac"
               for r in verdict["regressions"])
    # pre-r20 history: the attribution fields are simply absent
    old_hist = []
    for h in hist:
        h = json.loads(json.dumps(h))
        del h["extra"]["tail_queue_wait_frac"]
        del h["extra"]["tail_decode_stall_frac"]
        old_hist.append(h)
    verdict = regress.regress_check(fresh, old_hist)
    assert not any("frac" in r["metric"] for r in verdict["regressions"])
    assert verdict["history_n"] == 4   # still gated on the old metrics


def test_regress_zero_median_fraction_has_absolute_floor():
    """A well-provisioned config's history legitimately sits at
    tail_*_frac == 0.0 — rel_floor*|0| is a zero threshold, so the
    fraction checks carry an absolute floor: sub-floor jitter never
    flags, a real shift still does."""
    base = {"metric": "m", "value": 100.0, "unit": "u",
            "extra": {"tokens_per_s": 100.0,
                      "tail_queue_wait_frac": 0.0,
                      "tail_decode_stall_frac": 0.0}}
    hist = [json.loads(json.dumps(base)) for _ in range(4)]
    jitter = json.loads(json.dumps(base))
    jitter["extra"]["tail_queue_wait_frac"] = 0.003   # one 0.3ms blip
    assert not regress.regress_check(jitter, hist)["regressions"]
    real = json.loads(json.dumps(base))
    real["extra"]["tail_queue_wait_frac"] = 0.30
    assert any(r["metric"] == "tail queue_wait frac"
               for r in regress.regress_check(real, hist)["regressions"])


# --- SLO burn rate -----------------------------------------------------


def test_fold_burn_rate_burst_vs_sustained():
    # transient burst: violations confined to one window
    burst = [{"arrival_s": i * 1.0, "e2e_ms": 500.0 if i == 4 else 10.0}
             for i in range(16)]
    b = slo.fold_burn_rate(burst, 100.0, window_s=2.0)
    assert b["violations"] == 1 and b["max_violation_streak"] == 1
    # sustained overload: every window violates
    over = [{"arrival_s": i * 1.0, "e2e_ms": 500.0} for i in range(16)]
    o = slo.fold_burn_rate(over, 100.0, window_s=2.0)
    assert o["violation_rate"] == 1.0
    assert o["max_violation_streak"] == len(o["windows"])
    # ceil-based bins: the boundary completion clamps into the last
    # FULL window instead of sitting alone in a degenerate ninth one
    assert len(o["windows"]) == 8
    assert all(w["n"] >= 2 for w in o["windows"])
    assert "SUSTAINED" in slo.burn_lines(o)[0]
    assert "SUSTAINED" not in slo.burn_lines(b)[0]
    # off / empty
    assert slo.fold_burn_rate(over, 0.0) is None
    assert slo.fold_burn_rate([], 100.0) is None


def test_slo_flag_wires_burn_into_summary(moe_engine, moe_requests):
    saved = moe_engine.cfg.slo_e2e_ms
    try:
        moe_engine.cfg.slo_e2e_ms = 8.0
        s = moe_engine.run(moe_requests, batching="continuous",
                           clock=engine_mod.VirtualClock(SERVE_VCOSTS))
    finally:
        moe_engine.cfg.slo_e2e_ms = saved
    burn = s["slo"]
    assert burn["slo_e2e_ms"] == 8.0
    assert burn["completed"] == len(moe_requests)
    assert burn["violations"] == sum(
        w["violations"] for w in burn["windows"])
    assert any("slo:" in ln for ln in slo.slo_lines(s))


def test_slo_flag_validation_and_lane():
    with pytest.raises(ValueError, match="slo_e2e_ms"):
        flags.BenchmarkConfig(model="moe_tiny", workload="serve",
                              slo_e2e_ms=-1.0).resolve()
    with pytest.raises(ValueError, match="serving-lane"):
        flags.parse_flags(["--model", "trivial", "--slo_e2e_ms", "50"])
    cfg = flags.parse_flags(["--model", "moe_tiny", "--slo_e2e_ms",
                             "50"], workload="serve")
    assert cfg.slo_e2e_ms == 50.0


# --- timeline request lanes -------------------------------------------


def test_serve_clock_record_on_stream(moe_ab):
    recs = [json.loads(l) for l in open(
        os.path.join(moe_ab["continuous"]["mdir"], "metrics.jsonl"))]
    clocks = [r for r in recs if r.get("kind") == "serve_clock"]
    assert len(clocks) == 1
    assert isinstance(clocks[0]["t_unix"], float)


def test_timeline_merges_request_lanes(moe_ab, serve_cfg):
    """Each request renders as its own Chrome-trace lane (pid
    'requests', tid=rid) with queue_wait/prefill/decode sub-slices
    beside the engine's span view."""
    trace = timeline_mod.merge_chrome_trace(moe_ab["continuous"]["mdir"])
    lanes = [e for e in trace["traceEvents"]
             if e.get("pid") == rq.REQUEST_LANE_PID]
    assert trace["metadata"]["request_lanes"] == serve_cfg.num_requests
    tids = {e["tid"] for e in lanes if e["ph"] == "X"}
    assert len(tids) == serve_cfg.num_requests
    names = {e["name"] for e in lanes}
    assert {"queue_wait", "prefill", "decode",
            "process_name"} <= names
    # decode slices carry the stall/active split for the hover view
    dec = [e for e in lanes if e["name"] == "decode"]
    assert dec and all("active_ms" in e["args"] for e in dec)
    # the engine's own span lane is still there beside the requests
    assert any(e.get("pid") == 0 for e in trace["traceEvents"])


def test_request_lanes_skip_pre_r20_streams():
    # no serve_clock record -> no lanes, never wrongly-placed ones
    assert rq.request_trace_events(
        [{"kind": "request", "e2e_ms": 5.0, "arrival_s": 0.0}]) == []


# --- overhead guard ----------------------------------------------------


def test_attribution_stamp_overhead_bounded(moe_ab):
    """The per-request stamp (components_ms) must cost well under the
    round-17 1%-of-step guard — it runs once per retirement on the
    engine's hot path."""
    step_s = SERVE_VCOSTS["decode"]
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        rq.components_ms(0.0, 0.001, 0.005, 0.040, 0.040, 0.030)
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 0.01 * step_s, \
        f"components_ms {per_call * 1e6:.1f}us vs 1% of " \
        f"{step_s * 1e3:.0f}ms step"


# --- span-name-registry lint ------------------------------------------


BAD_SPAN_SRC = """
from tpu_hc_bench.obs import timeline as timeline_mod
def f(t0, t1):
    timeline_mod.record_span("step_dispach", t0, t1)
    timeline_mod.instant("retire")
"""


def test_span_registry_lint_flags_typo():
    found = [f for f in lints.lint_source_text(
        BAD_SPAN_SRC, filename="tpu_hc_bench/train/driver.py")
        if f.lint == lints.SPAN_REGISTRY]
    assert len(found) == 1
    assert "step_dispach" in found[0].message
    assert "KNOWN_SPANS" in found[0].message


def test_span_registry_lint_reads_phase_names():
    """``Phases.enter`` names phases (and their ``parent=``) the way
    ``span`` names spans: a literal outside the registry is flagged, a
    computed one (``kind + "_wait"``) is the caller's contract."""
    src = """
from tpu_hc_bench.obs import timeline as timeline_mod
def f(kind):
    phases = timeline_mod.Phases()
    phases.enter("retire")
    phases.enter("retir")
    phases.enter("decode_wait", parent="decod")
    phases.enter(kind + "_wait", parent=kind)
    phases.enter(None)
    other.enter("not_a_phase")
"""
    found = [f.message for f in lints.lint_source_text(
        src, filename="tpu_hc_bench/serve/engine.py")
        if f.lint == lints.SPAN_REGISTRY]
    assert len(found) == 2
    assert "'retir'" in found[0] and "'decod'" in found[1]


def test_span_registry_lint_skips_variables_and_foreign_calls():
    src = """
from tpu_hc_bench.obs import timeline as timeline_mod
def f(kind, t0, t1, thing):
    timeline_mod.record_span(kind, t0, t1)     # variable: caller's contract
    thing.instant("definitely_not_a_span")     # not the recorder's
"""
    found = [f for f in lints.lint_source_text(
        src, filename="tpu_hc_bench/serve/engine.py")
        if f.lint == lints.SPAN_REGISTRY]
    assert found == []


def test_span_registry_lint_suppression():
    src = BAD_SPAN_SRC.replace(
        'timeline_mod.record_span("step_dispach", t0, t1)',
        'timeline_mod.record_span("step_dispach", t0, t1)'
        '  # thb:lint-ok[span-name-registry]')
    found = [f for f in lints.lint_source_text(
        src, filename="tpu_hc_bench/train/driver.py")
        if f.lint == lints.SPAN_REGISTRY]
    assert found == []


def test_repo_span_names_all_registered():
    """The repo baseline stays clean: every literal span name the
    instrumented lanes record is in KNOWN_SPANS."""
    found = [f for f in lints.lint_repo_sources()
             if f.lint == lints.SPAN_REGISTRY]
    assert found == [], [f.message for f in found]


def test_known_spans_cover_engine_kinds():
    # the engine's variable record_span(kind, ...) call records these
    # three — the registry must know them even though the lint can't
    # see through the variable
    assert {"prefill", "decode", "classify", "admit",
            "retire"} <= timeline_mod.KNOWN_SPANS
