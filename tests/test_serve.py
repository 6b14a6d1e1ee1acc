"""Serving lane (tpu_hc_bench/serve/, round 16).

Default lane shares ONE session-scoped warmed engine (``moe_engine``,
3 AOT buckets of the tiny MoE member) plus one classify engine on
``trivial`` — zero driver runs, every closed-loop test drives the
scheduler in VIRTUAL time (``VirtualClock``: sleeps are instant, step
costs are modeled), so the whole module costs a few engine warmups.

The load-bearing pins:

- **decode parity**: the engine's incremental paged decode reproduces
  the model's own full-context forward token-for-token (greedy), for
  the MoE/GPT family — the correctness claim under the paged KV cache;
- **zero lowering after warmup**: ``lower_count`` and the compiled
  ladder are frozen across runs, off-ladder shapes raise instead of
  compiling, and the ``serve-bucket-recompile`` lint guards the source;
- **the A/B property**: at the same offered load, continuous batching
  beats the static control on p99 latency and goodput-under-load
  (deterministic in virtual time);
- **request-only obs streams**: ``obs summarize``/``diff``/``watch``
  render a serving run (zero ``step``-keyed records) labeled, with no
  traceback and no empty training table — the pinned regression for
  the step-keyed assumption;
- serve tuner space / ``<model>@serve`` registry rows / staleness lint
  lane checks.

Subprocess e2e (CLI exit codes, bench_serve A/B) and the closed-loop
arrival sweep are slow-marked.
"""

from __future__ import annotations

import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tpu_hc_bench import flags
from tpu_hc_bench.analysis import lints
from tpu_hc_bench.data.tokens import PromptSampler
from tpu_hc_bench.obs import metrics as obs_metrics
from tpu_hc_bench.serve import arrivals, slo
from tpu_hc_bench.serve import cache as cache_mod
from tpu_hc_bench.serve import engine as engine_mod
from tpu_hc_bench.tune import prune, registry, space

# the session engine fixtures (serve_cfg/moe_engine/moe_requests/
# moe_ab/trivial_engine) live in conftest.py since round 20 — shared
# with test_requests_obs; the shared cost table keeps this module's
# VirtualClock replays deterministic against the moe_ab fixture runs
from conftest import SERVE_VCOSTS as VCOSTS  # noqa: E402


def _quiet(_msg):
    pass


# --- arrivals ---------------------------------------------------------


def test_arrival_processes_deterministic_and_sorted():
    for proc in arrivals.PROCESSES:
        a = arrivals.arrival_times(proc, rate=20.0, n=64, seed=3)
        b = arrivals.arrival_times(proc, rate=20.0, n=64, seed=3)
        np.testing.assert_array_equal(a, b)
        assert (np.diff(a) >= 0).all() and a.shape == (64,)
        c = arrivals.arrival_times(proc, rate=20.0, n=64, seed=4)
        assert not np.array_equal(a, c)


def test_arrival_mean_rate_shared_across_processes():
    # all three shapes hold the same MEAN rate (the A/B axis): n
    # arrivals at rate r span ~n/r seconds
    n, rate = 4096, 50.0
    for proc in arrivals.PROCESSES:
        t = arrivals.arrival_times(proc, rate=rate, n=n, seed=0)
        assert t[-1] == pytest.approx(n / rate, rel=0.25), proc


def test_arrival_validation_loud():
    with pytest.raises(ValueError, match="process"):
        arrivals.arrival_times("uniform", 1.0, 4)
    with pytest.raises(ValueError, match="rate"):
        arrivals.arrival_times("poisson", 0.0, 4)
    with pytest.raises(ValueError, match="arrival"):
        arrivals.arrival_times("poisson", 1.0, 0)


def test_sampled_lengths_in_bounds():
    lens = arrivals.sample_lengths(512, max_len=32, seed=1)
    assert lens.min() >= 1 and lens.max() <= 32
    assert len(np.unique(lens)) > 4     # a distribution, not a constant


def test_build_requests_deterministic(serve_cfg, moe_engine, moe_requests):
    again = arrivals.build_requests(serve_cfg, moe_engine.spec.vocab_size)
    assert len(again) == serve_cfg.num_requests
    for r1, r2 in zip(moe_requests, again):
        assert r1.arrival_s == r2.arrival_s
        assert r1.output_len == r2.output_len
        np.testing.assert_array_equal(r1.prompt, r2.prompt)


def test_build_requests_classify_member(trivial_engine):
    reqs = arrivals.build_requests(trivial_engine.cfg, None)
    assert all(r.prompt is None and r.output_len == 1 for r in reqs)


# --- prompt sampler ---------------------------------------------------


def test_prompt_sampler_synthetic_deterministic():
    s = PromptSampler(vocab_size=64, seed=5)
    a, b = s.sample(3, 10), s.sample(3, 10)
    np.testing.assert_array_equal(a, b)
    assert a.dtype == np.int32 and a.shape == (10,)
    assert a.min() >= 1 and a.max() < 64      # 0 reserved for eod/pad
    assert not np.array_equal(a, s.sample(4, 10))
    with pytest.raises(ValueError, match="length"):
        s.sample(0, 0)


# --- flag surface -----------------------------------------------------


def test_serve_buckets_parsing():
    assert flags.parse_serve_buckets("auto", 8) == (1, 2, 4, 8)
    assert flags.parse_serve_buckets("auto", 6) == (1, 2, 4, 6)
    assert flags.parse_serve_buckets("2,8,4", 8) == (2, 4, 8)
    with pytest.raises(ValueError, match="serve_buckets"):
        flags.parse_serve_buckets("2,x", 8)
    with pytest.raises(ValueError, match="positive"):
        flags.parse_serve_buckets("0,2", 8)
    with pytest.raises(ValueError, match="max_in_flight"):
        flags.parse_serve_buckets("auto", 0)


def test_train_only_flags_rejected_in_serve_lane():
    argv = ["--model", "moe_tiny", "--gradient_accumulation_steps", "8"]
    with pytest.raises(SystemExit):
        # argparse errors exit; the resolve-level rejection needs valid
        # parse first
        flags.parse_flags(["--no_such_flag"], workload="serve")
    with pytest.raises(ValueError, match="training-only"):
        flags.parse_flags(argv, workload="serve")
    # an explicitly typed DEFAULT value still rejects (loudness is
    # about what the operator said, not what changed)
    with pytest.raises(ValueError, match="training-only"):
        flags.parse_flags(
            ["--model", "moe_tiny", "--optimizer", "sgd"],
            workload="serve")


def test_serve_only_flags_rejected_in_train_lane():
    with pytest.raises(ValueError, match="serving-lane"):
        flags.parse_flags(["--model", "trivial", "--arrival_rate", "4"])
    # programmatic construction: non-default serve field on a training
    # config dies too
    with pytest.raises(ValueError, match="serving-lane"):
        flags.BenchmarkConfig(model="trivial", batching="static").resolve()


def test_serve_resolve_validations_loud():
    def cfg(**kw):
        return flags.BenchmarkConfig(
            model="moe_tiny", workload="serve", **kw)

    with pytest.raises(ValueError, match="arrival_rate"):
        cfg(arrival_rate=0.0).resolve()
    with pytest.raises(ValueError, match="num_requests"):
        cfg(num_requests=0).resolve()
    with pytest.raises(ValueError, match="kv_page_size"):
        cfg(kv_page_size=0).resolve()
    with pytest.raises(ValueError, match="batching"):
        cfg(batching="dynamic").resolve()
    c = cfg().resolve()
    assert c.workload == "serve"
    assert "serve" in " ".join(c.summary_lines())


# --- page allocator / bucket ladder -----------------------------------


def test_page_allocator_reserves_trash_page():
    alloc = cache_mod.PageAllocator(5)
    assert alloc.free_pages == 4
    pages = alloc.alloc(4)
    assert 0 not in pages and sorted(pages) == [1, 2, 3, 4]
    assert alloc.alloc(1) is None       # exhausted, never page 0
    alloc.free(pages)
    assert alloc.free_pages == 4
    with pytest.raises(ValueError, match="trash"):
        cache_mod.PageAllocator(1)


def test_pick_bucket_off_ladder_raises():
    assert engine_mod.pick_bucket((1, 2, 4), 3) == 4
    with pytest.raises(ValueError, match="no bucket"):
        engine_mod.pick_bucket((1, 2, 4), 5)


# --- the engine: closed loop in virtual time --------------------------


def test_all_requests_complete_both_arms(moe_ab, serve_cfg):
    for arm in ("static", "continuous"):
        s = moe_ab[arm]["summary"]
        assert s["completed"] == s["requests"] == serve_cfg.num_requests
        assert s["batching"] == arm
        assert s["tokens"] > 0 and s["tokens_per_s"] > 0
        assert 0.0 < s["goodput"] <= 1.0
        assert s["decode_steps"] > 0 and s["prefill_steps"] == 8


def test_continuous_beats_static_in_virtual_time(moe_ab):
    """The headline A/B property, deterministic under VirtualClock: at
    the same offered load, admit/retire-per-step beats run-to-
    completion batching on the latency tail AND on goodput."""
    st = moe_ab["static"]["summary"]
    ct = moe_ab["continuous"]["summary"]
    assert ct["p99_e2e_ms"] < st["p99_e2e_ms"]
    assert ct["goodput"] > st["goodput"]


def test_zero_lowering_after_warmup(moe_engine, moe_requests):
    """The compiled ladder is frozen at construction: replaying traffic
    never lowers a new program or grows the bucket set."""
    before = (moe_engine.lower_count, set(moe_engine.compiled))
    moe_engine.run(moe_requests, batching="continuous",
                   clock=engine_mod.VirtualClock(VCOSTS))
    assert (moe_engine.lower_count, set(moe_engine.compiled)) == before


def test_kv_pool_temp_ratio_in_compile_record_and_summary(moe_engine,
                                                         moe_ab):
    """The counter that says the programs hold no second copy of a pool
    leaf: the largest decode/prefill AOT temp over one leaf's bytes.
    Its VALUE is the chip's to judge (the CPU backend neither donates
    nor lays the pool out as the TPU does); here it must exist, be a
    finite number, and reach the run summary and its rendering."""
    ratio = moe_engine.compile_record["kv_pool_temp_ratio"]
    assert isinstance(ratio, float) and np.isfinite(ratio) and ratio > 0
    assert ratio == moe_engine.kv_pool_temp_ratio()
    summary = moe_ab["continuous"]["summary"]
    assert summary["kv_pool_temp_ratio"] == ratio
    assert any(f"kv_pool_temp_ratio {ratio:.3f}" in ln
               for ln in slo.slo_lines(summary))


def test_off_ladder_request_rejected(moe_engine, serve_cfg):
    big = arrivals.Request(
        rid=0, arrival_s=0.0,
        prompt=np.ones(serve_cfg.max_prompt_len + 1, np.int32),
        output_len=1)
    with pytest.raises(ValueError, match="compiled ladder"):
        moe_engine.run([big], clock=engine_mod.VirtualClock(VCOSTS))


def test_engine_run_deterministic(moe_engine, moe_requests, moe_ab):
    """Same trace + same virtual clock -> identical generated tokens
    and step counts (arms share one engine; no hidden state)."""
    replay = moe_engine.run(moe_requests, batching="continuous",
                            clock=engine_mod.VirtualClock(VCOSTS))
    first = moe_ab["continuous"]["summary"]
    for k in ("decode_steps", "prefill_steps", "tokens", "completed"):
        assert replay[k] == first[k], k


def test_classify_member_serves_single_forward(trivial_engine):
    reqs = arrivals.build_requests(trivial_engine.cfg, None)
    s = trivial_engine.run(reqs, clock=engine_mod.VirtualClock(VCOSTS))
    assert s["completed"] == len(reqs)
    assert s["classify_steps"] > 0 and s["decode_steps"] == 0
    assert s["p99_ttft_ms"] == s["p99_e2e_ms"]   # one forward, no decode


def test_non_servable_member_rejected():
    cfg = flags.BenchmarkConfig(
        model="bert_tiny", workload="serve").resolve()
    with pytest.raises(ValueError, match="MLM"):
        engine_mod.ServeEngine(cfg, print_fn=_quiet)


# --- decode parity: incremental paged decode vs full forward ----------


def test_paged_decode_matches_full_forward(moe_engine, moe_ab):
    """Token-for-token greedy parity: for every request, the engine's
    incremental paged decode (per-step KV gather over page tables)
    reproduces the model's own full-context forward.  The engine
    dispatches MoE ragged (zero-drop) for exactly this property."""
    import jax.numpy as jnp

    from tpu_hc_bench.models import create_model

    ref_model, _ = create_model(
        "moe_tiny", dtype=jnp.float32, seq_len=moe_engine.max_ctx,
        moe_impl="ragged")

    recs = [json.loads(l) for l in open(
        os.path.join(moe_ab["continuous"]["mdir"], "metrics.jsonl"))]
    requests = {r.rid: r for r in arrivals.build_requests(
        moe_engine.cfg, moe_engine.spec.vocab_size)}
    checked = 0
    for rec in recs:
        if rec.get("kind") != "request" or checked >= 3:
            continue
        req = requests[rec["id"]]
        seq = list(np.asarray(req.prompt))
        want = rec["generated"]
        got = []
        for _ in range(len(want)):
            toks = np.zeros((1, moe_engine.max_ctx), np.int32)
            toks[0, :len(seq)] = seq
            logits = ref_model.apply(
                moe_engine.variables, jnp.asarray(toks), train=False)
            nxt = int(np.asarray(logits)[0, len(seq) - 1].argmax())
            got.append(nxt)
            seq.append(nxt)
        assert got == want, f"request {rec['id']}: {got} != {want}"
        checked += 1
    assert checked == 3


def test_static_arm_admission_bounded_by_kv_pool(moe_engine):
    """Regression: the static arm sized its batch by max_in_flight
    alone, so a pool smaller than a full batch's worst-case pages
    (legal per resolve(), which only guarantees ONE request, and
    exactly what the tuner's half-pool lever produces) crashed the
    alloc assert at admission.  Page-bounded admission completes the
    trace with smaller batches instead."""
    cfg = flags.BenchmarkConfig(
        model="moe_tiny", workload="serve", arrival_rate=50.0,
        num_requests=6, max_prompt_len=8, max_output_len=4,
        max_in_flight=2, kv_page_size=4, seed=0).resolve()
    reqs = arrivals.build_requests(cfg, moe_engine.spec.vocab_size)
    saved = moe_engine.num_pages
    try:
        # 1 trash page + exactly one request's worst case: a full
        # cap=2 batch can never fit (the warmed KV pool is larger, so
        # page indices stay in range)
        moe_engine.num_pages = 1 + moe_engine.table_width
        s = moe_engine.run(reqs, batching="static",
                           clock=engine_mod.VirtualClock(VCOSTS))
    finally:
        moe_engine.num_pages = saved
    assert s["completed"] == 6


# --- SLO fold + obs stream --------------------------------------------


def test_percentile_matches_numpy_convention():
    vals = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.3]
    for q in (50, 95, 99):
        assert slo.percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q)))
    assert slo.percentile([], 99) == 0.0
    assert slo.percentile([7.0], 50) == 7.0


def test_metrics_stream_carries_request_records(moe_ab, serve_cfg):
    recs = [json.loads(l) for l in open(
        os.path.join(moe_ab["continuous"]["mdir"], "metrics.jsonl"))]
    reqs = [r for r in recs if r.get("kind") == "request"]
    assert len(reqs) == serve_cfg.num_requests
    for r in reqs:
        assert r["e2e_ms"] >= r["ttft_ms"] >= 0
        assert r["queue_ms"] >= 0 and r["output_len"] >= 1
    assert sum(1 for r in recs if r.get("kind") == "serve_summary") == 1
    assert not any(r.get("kind") == "window" for r in recs)


def test_fold_serve_records_recomputes_truncated_stream(moe_ab):
    recs = [json.loads(l) for l in open(
        os.path.join(moe_ab["continuous"]["mdir"], "metrics.jsonl"))]
    # a stream truncated before its serve_summary still reports
    # percentiles from the request records
    cut = [r for r in recs if r.get("kind") != "serve_summary"]
    fold = slo.fold_serve_records(cut)
    assert fold is not None and fold["completed"] == 8
    assert "p99_e2e_ms" in fold and fold.get("wall_s") is None
    # training streams cost one scan and fold to None
    assert slo.fold_serve_records(
        [{"kind": "window", "step": 3}]) is None


def test_summarize_labels_request_only_stream(moe_ab):
    """The pinned regression: a stream with request records and ZERO
    step-keyed records renders labeled — no traceback, no empty
    training table."""
    lines = obs_metrics.summarize_run(moe_ab["continuous"]["mdir"])
    text = "\n".join(lines)
    assert "serving run (request-keyed metrics" in text
    assert "serve: 8/8 requests" in text
    assert "ttft ms p50" in text
    assert "ex/sec" not in text          # no empty step table header


def test_diff_renders_serving_delta(moe_ab):
    lines = obs_metrics.diff_runs(moe_ab["static"]["mdir"],
                                  moe_ab["continuous"]["mdir"])
    text = "\n".join(lines)
    assert "serve metrics:" in text
    assert "p99 e2e ms" in text and "serve goodput" in text
    assert "batching arm differs: static -> continuous" in text
    assert "total ex/s" not in text      # no empty training table
    # serving-vs-training diff: serve rows only render when BOTH runs
    # serve; nothing crashes
    assert slo.serve_diff_lines({"p99_e2e_ms": 1.0}, None) == []


def test_watch_renders_and_completes_on_serving_run(moe_ab):
    from tpu_hc_bench.obs import watch as watch_mod

    out = io.StringIO()
    rc = watch_mod.watch(moe_ab["continuous"]["mdir"], out=out,
                         interval=0.01, timeout_s=5.0)
    assert rc == 0                       # serve_summary ends the watch
    text = out.getvalue()
    assert "p99 ttft" in text and "done" in text
    assert "(no progress records yet)" not in text


# --- serve tuner space / registry -------------------------------------


def test_serve_space_seed_first_and_valid():
    sp = space.serve_member_space("moe_tiny")
    assert sp[0] == space.serve_seed_candidate("moe_tiny")
    assert len({c.key for c in sp}) == len(sp) > 4
    assert all(c.workload == "serve" for c in sp)
    # every candidate resolves under the serving validity matrix
    res = prune.static_prune(sp)
    assert [s.journal_record() for s in res.skipped] == []
    assert len(res.survivors) == len(sp)


def test_serve_candidate_lever_validation():
    with pytest.raises(ValueError, match="serve lane"):
        space.Candidate.make("moe_tiny", {"batch_size": 8},
                             workload="serve")
    with pytest.raises(ValueError, match="train lane"):
        space.Candidate.make("moe_tiny", {"max_in_flight": 8})


def test_serve_search_promotes_lane_keyed_row(tmp_path):
    """Regression: promote() keyed a serve-lane search's row under the
    bare member name — unreachable by the serving lane's own
    ``--config=auto`` lookup (which reads ``<model>@serve``) AND
    clobbering the member's training row."""
    from tpu_hc_bench.tune import search

    stub = lambda c, rung, batches: {  # noqa: E731
        "per_chip": 100.0, "goodput": 0.9, "wall_s": 0.1}
    journal = search.run_search(
        "moe_tiny", str(tmp_path / "s"), "cpu-test-w1",
        settings=search.SearchSettings(budget_s=1e9),
        space=space.serve_member_space("moe_tiny"),
        runner=stub, print_fn=_quiet)
    assert journal["workload"] == "serve"
    regdir = tmp_path / "reg"
    registry.promote(journal, registry_dir=regdir)
    rows = registry.load_rows("cpu-test-w1", regdir)
    assert set(rows) == {"moe_tiny@serve"}


def test_serve_hbm_budget_checked_at_warmup(moe_engine):
    """``--hbm_budget`` in the serving lane is a real check, not a
    parsed-then-discarded knob: the warmed ladder's verdict prints
    before traffic and the compile record carries the accounting."""
    lines = []
    saved = moe_engine.cfg.hbm_budget
    try:
        moe_engine.cfg.hbm_budget = "1GB"
        moe_engine._check_hbm_budget(lines.append)
    finally:
        moe_engine.cfg.hbm_budget = saved
    # either a measured verdict against the budget or the loud
    # no-AOT-report warning — never silence
    assert any("budget" in ln for ln in lines)
    rec = moe_engine.compile_record["hbm_budget"]
    assert rec["budget_bytes"] == 2**30


def test_config_auto_resolves_serve_row(tmp_path, monkeypatch):
    hw = "cpu-test-w1"
    monkeypatch.setenv(registry.HW_ENV, hw)
    monkeypatch.setenv(registry.REGISTRY_ENV, str(tmp_path))
    (tmp_path / f"{hw}.json").write_text(json.dumps({
        "hardware": hw, "members": {
            "moe_tiny": {"overrides": {"batch_size": 32}, "score": 1.0},
            "moe_tiny@serve": {"overrides": {
                "max_in_flight": 4,       # applies
                "batch_size": 96,         # train lever: skipped w/ note
                "gone_flag": 1,           # dead: skipped w/ note
            }, "score": 2.0},
        }}))
    cfg = flags.BenchmarkConfig(
        model="moe_tiny", workload="serve", config="auto").resolve()
    assert cfg.max_in_flight == 4
    assert cfg.batch_size == flags.BenchmarkConfig.batch_size
    assert cfg.config_source == "auto"
    note = cfg.translations["config"]
    assert "moe_tiny@serve" in note
    assert "not a serve-lane lever" in note and "unknown flag" in note
    # the training lane never sees the @serve row
    tcfg = flags.BenchmarkConfig(model="moe_tiny", config="auto").resolve()
    assert tcfg.batch_size == 32 and tcfg.max_in_flight == \
        flags.BenchmarkConfig.max_in_flight


def test_config_auto_serve_falls_back_loudly(tmp_path, monkeypatch):
    monkeypatch.setenv(registry.HW_ENV, "cpu-test-w1")
    monkeypatch.setenv(registry.REGISTRY_ENV, str(tmp_path))
    cfg = flags.BenchmarkConfig(
        model="moe_tiny", workload="serve", config="auto").resolve()
    assert cfg.config_source == "baseline"
    assert "moe_tiny@serve" in cfg.translations["config"]


def test_staleness_lint_covers_serving_rows(tmp_path):
    (tmp_path / "hw.json").write_text(json.dumps({
        "hardware": "hw", "members": {
            "moe_tiny@serve": {"overrides": {
                "dead_knob": 1,           # no longer a field
                "batch_size": 8,          # the other lane's lever
                "max_in_flight": 4,       # fine
            }},
            "trivial": {"overrides": {"kv_pages": 9}},   # lane-crossed
        }}))
    found = lints.check_tuned_registry(tmp_path)
    msgs = {f.location.split(":", 1)[1]: f.message for f in found}
    assert "moe_tiny@serve/dead_knob" in msgs
    assert "serving row records the other lane's lever" in \
        msgs["moe_tiny@serve/batch_size"]
    assert "training row records the other lane's lever" in \
        msgs["trivial/kv_pages"]
    assert "moe_tiny@serve/max_in_flight" not in msgs


# --- serve-bucket-recompile lint --------------------------------------


BAD_ENGINE = """
import jax
class E:
    def decode_step(self, x):
        return jax.jit(lambda v: v + 1)(x)
"""

WARM_ENGINE = """
import jax
from tpu_hc_bench.obs import efficiency
class E:
    def __init__(self):
        self._warm()
    def _aot(self, fn, x):
        self.c = efficiency.aot_compile(jax.jit(fn), x)
    def _warm(self):
        self._aot(lambda v: v, 1)
    def decode_step(self, x):
        return self.c(x)
"""


def test_serve_recompile_lint_flags_traffic_path_jit():
    found = lints.lint_source_text(
        BAD_ENGINE, filename="tpu_hc_bench/serve/engine.py")
    assert [f.lint for f in found] == [lints.SERVE_RECOMPILE]
    assert "decode_step" in found[0].message
    # same source outside the serve package: not this lint's business
    assert not [f for f in lints.lint_source_text(
        BAD_ENGINE, filename="tpu_hc_bench/train/driver.py")
        if f.lint == lints.SERVE_RECOMPILE]


def test_serve_recompile_lint_exempts_warmup_namespace():
    found = [f for f in lints.lint_source_text(
        WARM_ENGINE, filename="tpu_hc_bench/serve/engine.py")
        if f.lint == lints.SERVE_RECOMPILE]
    assert found == []


def test_serve_recompile_lint_suppression():
    src = BAD_ENGINE.replace(
        "return jax.jit(lambda v: v + 1)(x)",
        "return jax.jit(lambda v: v + 1)(x)  "
        "# thb:lint-ok[serve-bucket-recompile]")
    found = [f for f in lints.lint_source_text(
        src, filename="tpu_hc_bench/serve/engine.py")
        if f.lint == lints.SERVE_RECOMPILE]
    assert found == []


def test_repo_serve_sources_lint_clean():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    serve_dir = os.path.join(repo, "tpu_hc_bench", "serve")
    found = []
    for name in sorted(os.listdir(serve_dir)):
        if name.endswith(".py"):
            found.extend(lints.lint_file(
                os.path.join(serve_dir, name)))
    found = [f for f in found if f.lint == lints.SERVE_RECOMPILE]
    assert found == [], [f.message for f in found]


# --- slow lane: subprocess e2e + closed-loop sweep --------------------


@pytest.mark.slow
def test_arrival_sweep_latency_monotone(moe_engine):
    """Closed-loop arrival sweep: deeper offered load never IMPROVES
    the p99 tail (virtual time keeps it deterministic), and every rate
    completes all requests with the ladder frozen."""
    p99s = []
    for rate in (10.0, 50.0, 200.0):
        cfg = flags.BenchmarkConfig(
            model="moe_tiny", workload="serve", arrival_rate=rate,
            num_requests=16, max_prompt_len=8, max_output_len=4,
            max_in_flight=2, kv_page_size=4, seed=0).resolve()
        reqs = arrivals.build_requests(cfg, moe_engine.spec.vocab_size)
        s = moe_engine.run(reqs, batching="continuous",
                           clock=engine_mod.VirtualClock(VCOSTS))
        assert s["completed"] == 16
        p99s.append(s["p99_e2e_ms"])
    assert p99s == sorted(p99s), p99s


@pytest.mark.slow
def test_llama_paged_decode_matches_full_forward(tmp_path):
    """Token-for-token greedy parity for the LlamaLM family — the
    RoPE per-row positions, GQA kv-head repeat, and SwiGLU param
    re-walk in serve/decode.py against the model's own full-context
    forward (the gpt/moe twin of this pin runs in the default lane;
    this one pays its own engine warmup, hence slow-marked)."""
    import jax.numpy as jnp

    from tpu_hc_bench.models import create_model

    cfg = flags.BenchmarkConfig(
        model="llama_tiny", workload="serve", arrival_rate=50.0,
        num_requests=3, max_prompt_len=8, max_output_len=4,
        max_in_flight=2, kv_page_size=4, seed=0).resolve()
    eng = engine_mod.ServeEngine(cfg, print_fn=_quiet)
    reqs = arrivals.build_requests(cfg, eng.spec.vocab_size)
    mdir = str(tmp_path / "llama")
    writer = obs_metrics.MetricsWriter(
        mdir, obs_metrics.run_manifest(
            cfg=cfg, extra={"workload": "serve"}))
    try:
        s = eng.run(reqs, batching="continuous", writer=writer,
                    clock=engine_mod.VirtualClock(VCOSTS))
    finally:
        writer.close()
    assert s["completed"] == 3 and s["post_warmup_compiles"] == 0

    ref_model, _ = create_model(
        "llama_tiny", dtype=jnp.float32, seq_len=eng.max_ctx)
    requests = {r.rid: r for r in reqs}
    recs = [json.loads(l) for l in open(
        os.path.join(mdir, "metrics.jsonl"))]
    checked = 0
    for rec in recs:
        if rec.get("kind") != "request":
            continue
        req = requests[rec["id"]]
        seq = list(np.asarray(req.prompt))
        want = rec["generated"]
        got = []
        for _ in range(len(want)):
            toks = np.zeros((1, eng.max_ctx), np.int32)
            toks[0, :len(seq)] = seq
            logits = ref_model.apply(
                eng.variables, jnp.asarray(toks), train=False)
            nxt = int(np.asarray(logits)[0, len(seq) - 1].argmax())
            got.append(nxt)
            seq.append(nxt)
        assert got == want, f"request {rec['id']}: {got} != {want}"
        checked += 1
    assert checked == 3


@pytest.mark.slow
def test_serve_cli_end_to_end(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    mdir = tmp_path / "run"
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_hc_bench", "serve",
         "--model", "moe_tiny", "--arrival_rate", "50",
         "--num_requests", "8", "--max_prompt_len", "8",
         "--max_output_len", "4", "--max_in_flight", "2",
         "--kv_page_size", "4", "--metrics_dir", str(mdir)],
        capture_output=True, text=True, env=env, timeout=570,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "post-warmup compiles: 0" in proc.stdout
    assert "workload=serve" in proc.stdout
    assert (mdir / "metrics.jsonl").exists()
    # the summarize CLI renders the run labeled, exit 0, no traceback
    proc2 = subprocess.run(
        [sys.executable, "-m", "tpu_hc_bench.obs", "summarize",
         str(mdir)],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr
    assert "serving run" in proc2.stdout
    assert "Traceback" not in proc2.stderr


@pytest.mark.slow
def test_bench_serve_ab_harness(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               BENCH_ARRIVAL_RATE="40", BENCH_REQUESTS="16",
               BENCH_SERVE_BUCKETS="auto")
    proc = subprocess.run(
        [sys.executable, "scripts/bench_serve.py",
         "--max_prompt_len", "8", "--max_output_len", "4",
         "--max_in_flight", "2", "--kv_page_size", "4",
         "--metrics_root", str(tmp_path / "ab")],
        capture_output=True, text=True, env=env, timeout=570,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.loads(proc.stdout)
    v = rec["extra"]["verdict"]
    assert v["continuous_beats_static_p99"]
    assert v["continuous_beats_static_goodput"]
    assert v["zero_post_warmup_compiles"]
    assert rec["extra"]["p99_ms"] > 0
