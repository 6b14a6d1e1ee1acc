"""KV-pool utilization ledger & admission forensics (round 22,
``tpu_hc_bench/obs/kv.py`` + serve-lane wiring).

Default lane rides the session serve fixtures from conftest (the ONE
warmed moe engine and the shared two-arm ``moe_ab`` closed loop in
virtual time) — zero new engine warmups; the extra closed loops below
are VirtualClock replays on the warmed engine, the same budget shape as
test_requests_obs.

The load-bearing pins:

- **ledger honesty**: every ``kv_pool`` snapshot obeys written <=
  reserved, the page-second integrals are monotone, and the
  per-request footprint reproduces ceil(length / page_size) exactly;
- **cause attribution**: a batch-bound burst charges ``batch_full``,
  a starved pool charges ``pool_starved``, and the split never exceeds
  the measured queue_ms;
- **back-compat**: pre-round-22 streams (no ``kv_pool`` records, no
  footprint fields) flow through fold/diff/regress absent-and-labeled,
  never KeyError — mirroring the r20 ``attribution_of`` seam;
- **bounded overhead**: the per-step ledger bookkeeping costs well
  under the round-17 1%-of-step recorder guard.
"""

from __future__ import annotations

import json
import os
import time

import pytest

from tpu_hc_bench import flags
from tpu_hc_bench.obs import fleet as fleet_mod
from tpu_hc_bench.obs import kv
from tpu_hc_bench.obs import metrics as obs_metrics
from tpu_hc_bench.obs import regress
from tpu_hc_bench.obs import timeline as timeline_mod
from tpu_hc_bench.serve import cache as cache_mod
from tpu_hc_bench.serve import engine as engine_mod
from tpu_hc_bench.serve import slo

from conftest import SERVE_VCOSTS


def _records_of(mdir: str) -> list[dict]:
    return [json.loads(l) for l in open(os.path.join(mdir,
                                                     "metrics.jsonl"))]


def _burst_run(moe_engine, batching="continuous", num_pages=None):
    """One VirtualClock replay on the warmed session engine with every
    request arriving at once (admission must queue), records captured
    in memory; optionally with the pool pinned smaller for the run."""
    from tpu_hc_bench.serve import arrivals

    cfg = flags.BenchmarkConfig(
        model="moe_tiny", workload="serve", arrival_rate=10000.0,
        num_requests=8, max_prompt_len=8, max_output_len=4,
        max_in_flight=2, kv_page_size=4, seed=0).resolve()
    reqs = arrivals.build_requests(cfg, moe_engine.spec.vocab_size)
    events = []
    writer = obs_metrics.MetricsWriter(None)
    writer.event = lambda kind, **f: events.append({"kind": kind, **f})
    saved = moe_engine.num_pages
    try:
        if num_pages is not None:
            moe_engine.num_pages = num_pages
        summary = moe_engine.run(
            reqs, batching=batching, writer=writer,
            clock=engine_mod.VirtualClock(SERVE_VCOSTS))
    finally:
        moe_engine.num_pages = saved
    return summary, events


# --- the engine-side ledger -------------------------------------------


def test_kv_pool_records_on_stream(moe_ab):
    for arm in ("static", "continuous"):
        pools = [r for r in _records_of(moe_ab[arm]["mdir"])
                 if r.get("kind") == "kv_pool"]
        assert pools, arm
        prev_rs = prev_ws = 0.0
        for p in pools:
            # written pages are a subset of reserved pages, always
            assert 0 <= p["pages_written"] <= p["pages_reserved"]
            assert p["free_pages"] >= 0
            # cumulative page-second integrals are monotone
            assert p["reserved_page_s"] >= prev_rs
            assert p["written_page_s"] >= prev_ws
            assert p["written_page_s"] <= p["reserved_page_s"] + 1e-9
            prev_rs, prev_ws = p["reserved_page_s"], p["written_page_s"]
        # the terminal snapshot: everything retired, nothing leaked
        assert pools[-1]["pages_reserved"] == 0
        assert pools[-1]["pages_written"] == 0


def test_request_footprints_reproduce_page_math(moe_ab, serve_cfg):
    page = serve_cfg.kv_page_size
    for arm in ("static", "continuous"):
        reqs = [r for r in _records_of(moe_ab[arm]["mdir"])
                if r.get("kind") == "request"]
        assert reqs
        for r in reqs:
            fp = kv.footprint_of(r)
            assert fp is not None, r
            # worst-case reservation: every request reserves the full
            # table width regardless of its actual lengths
            assert fp["pages_reserved"] == 3
            # tokens that ever landed in the pool: the prompt plus
            # every generated token except the last (sampled and
            # returned, never written back)
            want = -(-(r["prompt_len"] + r["output_len"] - 1) // page)
            assert fp["pages_final"] == want, r
            # peak == final until mid-flight release exists
            assert fp["pages_peak_used"] == fp["pages_final"]
            assert 1 <= fp["pages_final"] <= fp["pages_reserved"]


def test_engine_summary_carries_kv_ledger(moe_ab):
    for arm in ("static", "continuous"):
        s = moe_ab[arm]["summary"]
        kvf = s["kv_pool"]
        assert kvf is not None
        assert 0.0 < kvf["util"] <= 1.0
        assert s["kv_pool_util"] == kvf["util"]
        # the trace's outputs run short of max: the gap is real
        assert kvf["req_gap_frac"] > 0.0
        assert s["kv_req_gap_frac"] == kvf["req_gap_frac"]
        assert kvf["req_n"] == s["completed"]
        assert kvf["pages_peak"] <= s["kv_pages"] - 1
        # satellite: the pool geometry is measured off the real arrays
        assert s["kv_pool_bytes"] > 0
        assert s["kv_layers"] > 0
        assert s["kv_scale_bytes"] == 0      # quant=off arm


def test_offline_fold_matches_engine_summary(moe_ab):
    s = moe_ab["continuous"]["summary"]
    fold = slo.fold_serve_records(_records_of(moe_ab["continuous"]["mdir"]))
    # the stream's terminal snapshot rounds to 6dp; the folds agree
    assert fold["kv_pool"]["util"] == pytest.approx(
        s["kv_pool"]["util"], abs=1e-3)
    assert fold["kv_pool"]["req_gap_frac"] == s["kv_pool"]["req_gap_frac"]
    assert fold["kv_pool_util"] == fold["kv_pool"]["util"]


def test_allocator_counts_peak_and_recycling():
    a = cache_mod.PageAllocator(7)
    p1 = a.alloc(3)
    assert a.pages_peak == 3 and a.recycled == 0
    a.free(p1)
    p2 = a.alloc(3)
    # LIFO free list: the same physical pages come back — recycled
    assert a.recycled == 3 and a.pages_peak == 3
    p3 = a.alloc(3)
    assert a.pages_peak == 6 and a.recycled == 3
    a.free(p2)
    a.free(p3)
    assert a.used_pages == 0


# --- the queue-wait cause split ---------------------------------------


def test_burst_charges_batch_full(moe_engine):
    """Everything arrives at once with cap=2: the queue blocks on the
    full batch (precedence: freeing pool pages would not open a slot),
    and the split never exceeds the measured queue_ms."""
    summary, events = _burst_run(moe_engine, batching="continuous")
    reqs = [e for e in events if e["kind"] == "request"]
    assert any(r["queue_batch_full_ms"] > 0 for r in reqs)
    assert all(r["queue_pool_starved_ms"] == 0.0 for r in reqs)
    for r in reqs:
        assert (r["queue_pool_starved_ms"] + r["queue_batch_full_ms"]
                <= r["queue_ms"] + 1e-3), r
    wc = summary["kv_pool"]["wait_causes"]
    assert wc["has_causes"]
    assert wc["tail_frac"]["batch_full"] >= 0.0


def test_starved_pool_charges_pool_starved(moe_engine):
    """With the pool pinned to ONE request's worst case, cap=2 never
    binds — the queue blocks on pages, and the tail names the pool."""
    table_width = moe_engine.table_width
    summary, events = _burst_run(
        moe_engine, batching="continuous", num_pages=1 + table_width)
    reqs = [e for e in events if e["kind"] == "request"]
    assert any(r["queue_pool_starved_ms"] > 0 for r in reqs)
    wc = summary["kv_pool"]["wait_causes"]
    assert wc["tail_ms"]["pool_starved"] > 0.0
    # at most one in flight: the batch never fills
    assert all(r["queue_batch_full_ms"] == 0.0 for r in reqs)


def test_static_arm_charges_batch_policy(moe_engine):
    """Static's run-to-completion policy is the binding resource even
    when the pool is also full — scale-out, not pool growth, is the
    remedy the attribution must name."""
    _, events = _burst_run(moe_engine, batching="static")
    reqs = [e for e in events if e["kind"] == "request"]
    assert any(r["queue_batch_full_ms"] > 0 for r in reqs)
    assert all(r["queue_pool_starved_ms"] == 0.0 for r in reqs)


def test_fold_wait_causes_tail_selection():
    recs = [{"e2e_ms": float(10 * (i + 1)), "queue_ms": float(i),
             "prefill_ms": 1.0, "decode_active_ms": 2.0,
             "decode_stall_ms": 0.5, "retire_ms": 0.0,
             "queue_pool_starved_ms": float(i) * 0.25,
             "queue_batch_full_ms": float(i) * 0.75}
            for i in range(20)]
    wc = kv.fold_wait_causes(recs)
    assert wc["n"] == 20 and wc["tail_n"] == 2
    # the slowest decile's queue wait splits 25/75 by construction
    assert wc["tail_frac"]["pool_starved"] == pytest.approx(0.25, abs=0.01)
    assert wc["tail_frac"]["batch_full"] == pytest.approx(0.75, abs=0.01)
    assert wc["has_causes"]
    assert kv.fold_wait_causes([]) is None


# --- back-compat: pre-round-22 streams --------------------------------


def test_pre_r22_stream_folds_absent_not_error(moe_ab):
    recs = _records_of(moe_ab["continuous"]["mdir"])
    old = []
    for r in recs:
        if r.get("kind") == "kv_pool":
            continue            # pre-r22: the record kind doesn't exist
        old.append({k: v for k, v in r.items()
                    if k not in ("pages_reserved", "pages_peak_used",
                                 "pages_final", "queue_pool_starved_ms",
                                 "queue_batch_full_ms", "kv_pool",
                                 "kv_pool_util", "kv_req_gap_frac",
                                 "kv_pool_bytes", "kv_scale_bytes",
                                 "kv_layers")})
    assert kv.fold_kv(old) is None
    fold = slo.fold_serve_records(old)
    assert fold is not None and "kv_pool" not in fold
    # rendering an old fold adds no kv lines and raises nothing
    assert all("kv_pool_util" not in ln for ln in slo.slo_lines(fold))
    # normalizers: absent fields read as absent / zero
    old_reqs = [r for r in old if r.get("kind") == "request"]
    assert old_reqs and all(kv.footprint_of(r) is None for r in old_reqs)
    assert not kv.has_footprints(old_reqs)
    assert kv.wait_cause_of({"queue_ms": 5.0}) == {
        "pool_starved": 0.0, "batch_full": 0.0}


def test_diff_labels_pre_r22_side(moe_ab):
    recs = _records_of(moe_ab["continuous"]["mdir"])
    old = [{k: v for k, v in r.items()
            if k not in ("pages_reserved", "pages_peak_used",
                         "pages_final", "kv_pool", "kv_pool_util",
                         "kv_req_gap_frac")}
           for r in recs if r.get("kind") != "kv_pool"]
    fold_old = slo.fold_serve_records(old)
    fold_new = slo.fold_serve_records(recs)
    lines = slo.serve_diff_lines(fold_old, fold_new)
    text = "\n".join(lines)
    assert "kv_pool_util" in text
    assert "note: run a predates the KV-pool ledger" in text
    # both sides pre-r22: no kv section at all
    assert kv.kv_diff_lines(fold_old, fold_old) == []
    assert kv.kv_diff_lines(None, None) == []


# --- summarize / diff / regress / timeline surfaces -------------------


def test_summarize_renders_kv_headline(moe_ab):
    text = "\n".join(obs_metrics.summarize_run(
        moe_ab["continuous"]["mdir"]))
    assert "kv_pool_util" in text
    assert "reservation honesty" in text and "gap" in text
    assert "kv pool geometry" in text and "MiB" in text
    assert "queue_wait cause" in text


def test_diff_renders_kv_delta_rows(moe_ab):
    lines = obs_metrics.diff_runs(moe_ab["static"]["mdir"],
                                  moe_ab["continuous"]["mdir"])
    text = "\n".join(lines)
    assert "kv pool" in text
    assert "kv_pool_util" in text and "pp" in text


def test_regress_gates_on_util_drop():
    """An injected utilization drop flags direction-aware (down =
    regression); pre-r22 history (no field) skips, never KeyError."""
    base = {"metric": "moe_tiny_serve_tokens_per_s", "value": 100.0,
            "unit": "tokens/sec",
            "extra": {"batching": "continuous", "arrival_rate": 16.0,
                      "p99_ms": 100.0, "goodput": 0.5,
                      "tokens_per_s": 100.0,
                      "kv_pool_util": 0.50}}
    hist = [json.loads(json.dumps(base)) for _ in range(4)]
    fresh = json.loads(json.dumps(base))
    fresh["extra"]["kv_pool_util"] = 0.20       # admission got wasteful
    verdict = regress.regress_check(fresh, hist)
    assert any(r["metric"] == "kv pool util"
               for r in verdict["regressions"])
    # a RISE in utilization is an improvement, never a regression
    better = json.loads(json.dumps(base))
    better["extra"]["kv_pool_util"] = 0.90
    assert not any(r["metric"] == "kv pool util" for r in
                   regress.regress_check(better, hist)["regressions"])
    # sub-floor jitter on the fraction never flags (5pp absolute floor)
    jitter = json.loads(json.dumps(base))
    jitter["extra"]["kv_pool_util"] = 0.47
    assert not any(r["metric"] == "kv pool util" for r in
                   regress.regress_check(jitter, hist)["regressions"])
    # pre-r22 history: the field is simply absent, checks skip
    old_hist = []
    for h in hist:
        h = json.loads(json.dumps(h))
        del h["extra"]["kv_pool_util"]
        old_hist.append(h)
    verdict = regress.regress_check(fresh, old_hist)
    assert not any(r["metric"] == "kv pool util"
                   for r in verdict["regressions"])
    assert verdict["history_n"] == 4


def test_timeline_exports_kv_counter_track(moe_ab):
    trace = timeline_mod.merge_chrome_trace(moe_ab["continuous"]["mdir"])
    counters = [e for e in trace["traceEvents"]
                if e.get("pid") == kv.KV_COUNTER_PID
                and e.get("ph") == "C"]
    assert counters
    assert trace["metadata"]["kv_counter_samples"] == len(counters)
    for e in counters:
        assert e["name"] == "kv pool pages"
        assert set(e["args"]) == {"written", "reserved_unwritten", "free"}
        assert "ts" in e and "ts_unix" not in e   # rebased like lanes
    # the track is named beside the request lanes
    assert any(e.get("ph") == "M" and e.get("pid") == kv.KV_COUNTER_PID
               for e in trace["traceEvents"])


def test_kv_counter_skips_unanchored_streams():
    # no serve_clock record -> no counter track, never a misplaced one
    assert kv.kv_counter_events(
        [{"kind": "kv_pool", "t": 1.0, "pages_reserved": 3,
          "pages_written": 2, "free_pages": 3}]) == []
    # a serve_clock but no kv_pool records (pre-r22) -> empty
    assert kv.kv_counter_events(
        [{"kind": "serve_clock", "t_unix": 100.0, "t": 0.0}]) == []


# --- heartbeats + watch ------------------------------------------------


def test_heartbeats_carry_kv_peak_pages(tmp_path, moe_engine,
                                        moe_requests):
    """run_serve wires a FleetWriter beside the metrics stream: the
    heartbeat carries kv_peak_pages and the reader accessor returns it
    (writer + reader in one PR, per the r15 mem_peak_bytes lesson)."""
    from tpu_hc_bench.serve import cli as serve_cli

    mdir = str(tmp_path / "hb")
    writer = obs_metrics.MetricsWriter(
        mdir, obs_metrics.run_manifest(
            cfg=moe_engine.cfg, extra={"workload": "serve"}))
    summary = serve_cli.run_serve(
        moe_engine, moe_requests, writer, batching="continuous",
        clock=engine_mod.VirtualClock(SERVE_VCOSTS))
    beats = fleet_mod.read_heartbeats(mdir)
    assert beats, os.listdir(mdir)
    last = beats[0][-1]
    peak = fleet_mod.heartbeat_kv_peak(last)
    # the final beat carries the run's pool high-water, exactly as the
    # summary ledger reports it
    assert peak == summary["kv_pool"]["pages_peak"]
    assert moe_engine.table_width <= peak <= moe_engine.num_pages - 1
    assert last.get("phase") == "serve"
    # train-lane / pre-r22 beats read absent, never KeyError
    assert fleet_mod.heartbeat_kv_peak({"kind": "heartbeat"}) is None
    # the fleet view renders the per-host pressure column
    from tpu_hc_bench.obs import watch as watch_mod

    text = "\n".join(watch_mod.render(mdir, {}, _records_of(mdir)))
    assert "kv peak pages" in text


def test_watch_renders_live_pool_occupancy():
    recs = [{"kind": "kv_pool", "t": 1.0, "pages_reserved": 6,
             "pages_written": 4, "free_pages": 0, "pages_peak": 6,
             "pages_recycled": 9}]
    text = "\n".join(slo.watch_lines(recs))
    assert "kv pool:" in text
    assert "6 reserved / 4 written / 0 free" in text


# --- overhead guard + registry ----------------------------------------


def test_ledger_stamp_overhead_bounded():
    """The per-step ledger bookkeeping (one token() + one charge())
    must cost well under the round-17 1%-of-step guard — it runs every
    decode step on the hot path."""
    step_s = SERVE_VCOSTS["decode"]
    ledger = cache_mod.KVLedger(4)
    ledger.admit(3, 5)
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        ledger.token(5 + (i % 7))
        ledger.charge(step_s)
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 0.01 * step_s, \
        f"KVLedger step cost {per_call * 1e6:.1f}us vs 1% of " \
        f"{step_s * 1e3:.0f}ms step"


def test_known_spans_cover_kv_instants():
    # the engine's edge-triggered cause instants are literal names the
    # span-name-registry lint checks against KNOWN_SPANS
    assert {"pool_starved", "batch_full"} <= timeline_mod.KNOWN_SPANS


# --- round 25: growth/sharing counters, back-compat + regress ---------


def test_pre_r25_stream_folds_growth_absent_not_error(moe_ab):
    """Records predating round 25 carry neither the growth counters on
    kv_pool nor the pages_grown/prefix_pages_shared footprint fields:
    the fold omits the section fields entirely (no fake zeros) and the
    footprint normalizer reads 0, labeled — the same seam as r20/r22."""
    r25_keys = ("pages_grown", "prefix_pages_shared", "pages_cow",
                "prefix_hits", "prefix_lookups", "prefix_hit_frac")
    old = [{k: v for k, v in r.items() if k not in r25_keys}
           for r in _records_of(moe_ab["continuous"]["mdir"])]
    fold = kv.fold_kv(old)
    assert fold is not None and fold["util"] is not None
    assert "pages_grown" not in fold
    assert "prefix_hit_frac" not in fold and "prefix_lookups" not in fold
    for r in old:
        if r.get("kind") == "request":
            fp = kv.footprint_of(r)
            assert fp["pages_grown"] == 0
            assert fp["prefix_pages_shared"] == 0
    flat = kv.flatten_kv(fold)
    assert "prefix_hit_frac" not in flat
    assert "pages_grown_total" not in flat
    # rendering an old fold raises nothing and adds no prefix line
    assert all("prefix cache" not in ln for ln in kv.kv_lines(
        {"kv_pool": fold}))


def test_r25_stream_carries_growth_counters(moe_ab):
    """The post-r25 engine always stamps the counters (0 on a cache-off
    run) so the offline fold and the engine's own summary agree."""
    recs = _records_of(moe_ab["continuous"]["mdir"])
    pools = [r for r in recs if r.get("kind") == "kv_pool"]
    assert all("pages_grown" in p and "prefix_pages_shared" in p
               for p in pools)
    fold = kv.fold_kv(recs)
    assert fold["pages_grown"] == 0 and fold["cow_copies"] == 0
    # cache off: no lookups -> structurally absent hit rate, never 0.0
    assert fold["prefix_lookups"] == 0
    assert fold["prefix_hit_frac"] is None
    reqs = [r for r in recs if r.get("kind") == "request"]
    assert all(kv.footprint_of(r)["pages_grown"] == 0 for r in reqs)


def test_regress_gates_on_prefix_hit_drop():
    """A prefix-cache hit-rate drop flags direction-aware (down =
    regression, the pool re-pays prefill writes it had been sharing);
    cache-off and pre-r25 records lack the field and skip structurally."""
    base = {"metric": "moe_tiny_serve_tokens_per_s", "value": 100.0,
            "unit": "tokens/sec",
            "extra": {"batching": "continuous", "arrival_rate": 16.0,
                      "p99_ms": 100.0, "goodput": 0.5,
                      "tokens_per_s": 100.0,
                      "kv_reserve": "lazy", "prefix_cache": "on",
                      "prefix_hit_frac": 0.40}}
    hist = [json.loads(json.dumps(base)) for _ in range(4)]
    fresh = json.loads(json.dumps(base))
    fresh["extra"]["prefix_hit_frac"] = 0.05     # sharing collapsed
    verdict = regress.regress_check(fresh, hist)
    assert any(r["metric"] == "prefix hit frac"
               for r in verdict["regressions"])
    # a RISE in hit rate is an improvement, never a regression
    better = json.loads(json.dumps(base))
    better["extra"]["prefix_hit_frac"] = 0.90
    assert not any(r["metric"] == "prefix hit frac" for r in
                   regress.regress_check(better, hist)["regressions"])
    # sub-floor jitter never flags (5pp absolute floor)
    jitter = json.loads(json.dumps(base))
    jitter["extra"]["prefix_hit_frac"] = 0.37
    assert not any(r["metric"] == "prefix hit frac" for r in
                   regress.regress_check(jitter, hist)["regressions"])
    # history with the cache on but no hit field (truncated runs):
    # the check skips, the rest of the gate still runs
    old_hist = []
    for h in hist:
        h = json.loads(json.dumps(h))
        del h["extra"]["prefix_hit_frac"]
        old_hist.append(h)
    verdict = regress.regress_check(fresh, old_hist)
    assert verdict["history_n"] == 4
    assert not any(r["metric"] == "prefix hit frac"
                   for r in verdict["regressions"])


def test_regress_fingerprints_reservation_arms():
    """A lazy+prefix run must never gate against worst-case history —
    the arms are config identity; pre-r25 records (no fields at all)
    normalize to worst/off and keep comparing against fresh
    default-arm runs instead of being orphaned."""
    base = {"metric": "moe_tiny_serve_tokens_per_s", "value": 100.0,
            "unit": "tokens/sec",
            "extra": {"batching": "continuous", "arrival_rate": 16.0,
                      "tokens_per_s": 100.0}}
    pre_r25 = [json.loads(json.dumps(base)) for _ in range(4)]
    shared = json.loads(json.dumps(base))
    shared["extra"].update(kv_reserve="lazy", prefix_cache="on")
    shared["extra"]["tokens_per_s"] = 10.0       # huge drop, wrong arm
    verdict = regress.regress_check(shared, pre_r25)
    assert verdict["history_n"] == 0             # never cross-gated
    # a fresh default-arm run (explicit worst/off) still compares
    # against the same pre-r25 history via the fingerprint defaults
    default_arm = json.loads(json.dumps(base))
    default_arm["extra"].update(kv_reserve="worst", prefix_cache="off")
    default_arm["extra"]["tokens_per_s"] = 10.0
    verdict = regress.regress_check(default_arm, pre_r25)
    assert verdict["history_n"] == 4
    assert any(r["metric"] == "tokens/s"
               for r in verdict["regressions"])
