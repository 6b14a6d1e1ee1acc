"""The seams ``ServeEngine.run`` is made of (PR 33): the run's policy as
one immutable record, the loop's state as one object that can be played
and inspected without ``run``, the summary as one function — and the
record shapes those three must keep, as literals copied from the output
of the method they replaced.

Every closed loop rides the session's warmed engines in VIRTUAL time.
"""

from __future__ import annotations

import ast
import dataclasses
import inspect

import pytest

from tpu_hc_bench import flags
from tpu_hc_bench.serve import arrivals
from tpu_hc_bench.serve import cache as cache_mod
from tpu_hc_bench.serve import engine as engine_mod
from tpu_hc_bench.serve import faults as faults_mod
from tpu_hc_bench.serve import loop as loop_mod

from conftest import SERVE_VCOSTS  # noqa: E402

# --- the record shapes, copied from the parent's output ----------------

_SUMMARY_HEAD = [
    "workload", "model", "batching", "arrival", "arrival_rate",
    "requests", "completed", "wall_s", "tokens", "tokens_per_s",
    "goodput", "queue_depth_max", "queue_depth_mean", "buckets",
    "max_in_flight", "kv_page_size", "kv_pages", "kv_layers",
    "kv_pool_bytes", "kv_scale_bytes", "kv_pool"]
_SUMMARY_KV = ["kv_pool_util", "kv_req_gap_frac", "pages_grown_total"]
_SUMMARY_ARMS = [
    "kv_reserve", "prefix_cache", "decode_attention", "quant",
    "decode_block_pages", "aot_decode_temp_bytes", "kv_pool_temp_ratio",
    "state_pool_bytes", "kv_read", "state_slots", "state_slot_steps",
    "ssd_kernel_calls", "kda_kernel_calls"]
_SUMMARY_TAIL = [
    "post_warmup_compiles", "attribution", "tail_queue_wait_frac",
    "tail_decode_stall_frac", "bucket_util", "loop_phases", "loop_wall_s",
    "prefill_steps", "decode_steps", "classify_steps", "p50_ttft_ms",
    "p95_ttft_ms", "p99_ttft_ms", "p50_e2e_ms", "p95_e2e_ms",
    "p99_e2e_ms", "p50_queue_ms", "p95_queue_ms", "p99_queue_ms",
    "latency_source", "latency_sample_cap", "sketch_windows",
    "p99_merged_ms", "signals_fired", "signals_fired_total", "shed_frac",
    "degrade", "op_parts"]
_REQUEST_HEAD = [
    "kind", "id", "status", "arrival_s", "ttft_ms", "e2e_ms",
    "prompt_len", "output_len", "queue_ms", "prefill_ms",
    "decode_active_ms", "retire_ms", "decode_stall_ms",
    "queue_pool_starved_ms", "queue_batch_full_ms", "queue_unseen_ms"]
_REQUEST_KV = [
    "generated", "pages_reserved", "pages_peak_used", "pages_final",
    "pages_grown", "prefix_pages_shared"]

SHAPES = {
    "decode": (_SUMMARY_HEAD + _SUMMARY_KV + _SUMMARY_ARMS
               + _SUMMARY_TAIL, _REQUEST_HEAD + _REQUEST_KV),
    "state_pool": (_SUMMARY_HEAD + _SUMMARY_KV + _SUMMARY_ARMS
                   + ["moe_picks_held", "moe_picks"] + _SUMMARY_TAIL,
                   _REQUEST_HEAD + _REQUEST_KV),
    "classify": (_SUMMARY_HEAD + _SUMMARY_ARMS + _SUMMARY_TAIL,
                 _REQUEST_HEAD),
}
KV_POOL_KEYS = [
    "kind", "t", "pages_reserved", "pages_written", "free_pages",
    "pages_peak", "pages_recycled", "reserved_page_s", "written_page_s",
    "pages_grown", "pages_cow", "prefix_hits", "prefix_lookups",
    "prefix_pages_shared"]
SERVE_KEYS = [
    "kind", "t", "queue_depth", "in_flight", "free_pages", "tokens",
    "bucket_occ", "prefill_steps", "decode_steps", "classify_steps"]


class Keep:
    """An enabled in-memory writer: the periodic records land too."""

    enabled = True
    out_dir = None
    last_record = None

    def __init__(self):
        self.records: list[dict] = []

    def event(self, kind, **fields):
        self.last_record = {"kind": kind, **fields}
        self.records.append(self.last_record)

    def close(self):
        pass


def _quiet(_msg):
    pass


@pytest.fixture(scope="module")
def solar_engine():
    cfg = flags.BenchmarkConfig(
        model="solar_open2_tiny", workload="serve", arrival_rate=1000.0,
        num_requests=10, max_prompt_len=16, max_output_len=6,
        max_in_flight=4, kv_page_size=4, seed=0).resolve()
    return engine_mod.ServeEngine(cfg, print_fn=_quiet)


@pytest.fixture
def engines(request):
    """Family name -> its warmed engine, built on first use."""
    def get(family):
        return request.getfixturevalue({
            "decode": "moe_engine", "state_pool": "solar_engine",
            "classify": "trivial_engine"}[family])
    return get


@pytest.mark.parametrize("family", ["decode", "state_pool", "classify"])
def test_summary_and_request_keys_are_the_parents(engines, family):
    engine = engines(family)
    reqs = arrivals.build_requests(
        engine.cfg, engine.spec.vocab_size if engine.decode_mode else None)
    w = Keep()
    summary = engine.run(reqs, writer=w,
                         clock=engine_mod.VirtualClock(SERVE_VCOSTS))
    want_summary, want_request = SHAPES[family]
    assert list(summary) == want_summary
    by_kind = {}
    for rec in w.records:
        by_kind.setdefault(rec["kind"], rec)
    assert list(by_kind["request"]) == want_request
    landed = next(r for r in w.records if r["kind"] == "serve_summary")
    assert list(landed) == ["kind"] + want_summary[:-1]  # op_parts: after
    assert summary["post_warmup_compiles"] == 0
    assert summary["completed"] == len(reqs)
    if engine.decode_mode:
        assert list(by_kind["kv_pool"]) == KV_POOL_KEYS
    # the phases still tile the loop, in the names the benchmark folds
    phases = summary["loop_phases"]
    kind = "decode" if engine.decode_mode else "classify"
    assert {"arrivals", "admit_host", "telemetry", "pack", "retire",
            kind + "_dispatch", kind + "_wait"} <= set(phases)
    tiled = sum(p["wall_s"] for p in phases.values())
    assert tiled == pytest.approx(summary["loop_wall_s"], rel=2e-3,
                                  abs=2e-4)


def test_serve_record_keys_are_the_parents(moe_engine):
    cfg = dataclasses.replace(moe_engine.cfg, num_requests=40,
                              arrival_rate=200.0, seed=3)
    reqs = arrivals.build_requests(cfg, moe_engine.spec.vocab_size)
    w = Keep()
    moe_engine.run(reqs, writer=w,
                   clock=engine_mod.VirtualClock(SERVE_VCOSTS))
    serve = [r for r in w.records if r["kind"] == "serve"]
    assert serve and all(list(r) == SERVE_KEYS for r in serve)
    kinds = [r["kind"] for r in w.records]
    assert kinds[0] == "serve_clock"
    assert kinds[-2:] == ["serve_summary", "serve_compile"]
    # every periodic serve record is followed by its pool snapshot
    assert all(kinds[i + 1] == "kv_pool"
               for i, k in enumerate(kinds) if k == "serve")


# --- the policy record ------------------------------------------------


def _resolve(engine, **kw):
    base = dict(batching=None, shed=None, deadline_ms=None,
                kv_preempt=None, kv_reserve=None, prefix_cache=None,
                faults=None)
    base.update(kw)
    return loop_mod.RunPolicy.resolve(engine, **base)


@pytest.mark.parametrize("shed, kv_preempt, guard", [
    ("off", "off", False), ("admit", "off", True),
    ("deadline", "off", True), ("off", "on", True)])
def test_policy_is_immutable_and_arms_the_guard(moe_engine, shed,
                                                kv_preempt, guard):
    p = _resolve(moe_engine, shed=shed, kv_preempt=kv_preempt,
                 deadline_ms=50.0)
    assert p.guard is guard
    assert (p.batching, p.kv_reserve, p.prefix_cache) == (
        moe_engine.cfg.batching, "worst", "off")
    assert p.deadline_s == pytest.approx(0.05)
    with pytest.raises(dataclasses.FrozenInstanceError):
        p.shed = "off"


@pytest.mark.parametrize("overrides, match", [
    ({"batching": "sometimes"}, "batching"),
    ({"shed": "admit"}, "needs a deadline"),
    ({"kv_reserve": "maybe"}, "kv_reserve"),
    ({"prefix_cache": "on"}, "requires kv_reserve=lazy")])
def test_policy_refuses_what_run_refused(moe_engine, overrides, match):
    with pytest.raises(ValueError, match=match):
        _resolve(moe_engine, **overrides)
    with pytest.raises(ValueError, match=match):
        moe_engine.run([], **overrides)


def test_policy_knows_the_family(trivial_engine, solar_engine):
    with pytest.raises(ValueError, match="classify"):
        _resolve(trivial_engine, kv_preempt="on")
    with pytest.raises(ValueError, match="recurrent state"):
        _resolve(solar_engine, kv_reserve="lazy", prefix_cache="on")


# --- the loop object, played without run() ----------------------------


class FakeHandler:
    def __init__(self, after):
        self.after, self.polls = after, 0

    def requested(self):
        self.polls += 1
        return self.polls > self.after


def _play(engine, reqs, tmp_path, handler=None, plan=None, **overrides):
    faults = faults_mod.parse_serve_plan(plan) if plan else None
    policy = _resolve(engine, faults=faults, **overrides)
    w = Keep()
    loop = loop_mod.ServeLoop(
        engine, reqs, policy, kv=engine._kv, writer=w,
        clock=engine_mod.VirtualClock(SERVE_VCOSTS), faults=faults,
        journal_path=str(tmp_path / "journal.json"))
    try:
        loop.play(drain_handler=handler)
    finally:
        engine._kv = loop.kv
    loop.close()
    return loop, w


EXITS = {
    # how a resident leaves -> (run arguments, what must have happened)
    "ok": ({}, lambda lp, w: lp.completed_ok == lp.n),
    "shed_resident": (
        {"shed": "admit", "deadline_ms": 12.0},
        lambda lp, w: lp.degrade["shed"].get("resident_expired")),
    "quarantined_at_prefill": (
        {"kv_preempt": "on", "plan": "nan_logits@0"},
        lambda lp, w: any(
            r["kind"] == "quarantine" and r["output_len"] == 1
            for r in w.records)),
    "quarantined_at_decode": (
        {"kv_preempt": "on", "plan": "nan_logits@3"},
        lambda lp, w: lp.degrade["quarantined"] == 1),
    "preempted": (
        {"kv_preempt": "on", "plan": "pool_squeeze@0:3"},
        lambda lp, w: lp.degrade["preempts"] and lp.degrade["requeues"]),
    "drained": (
        {"handler": 2}, lambda lp, w: lp.drained["unfinished"] >= 1),
}


@pytest.mark.parametrize("how", list(EXITS))
def test_every_exit_leaves_the_cache_empty(moe_engine, moe_requests,
                                           tmp_path, how):
    kw, happened = EXITS[how]
    kw = dict(kw)
    after = kw.pop("handler", None)
    burst = [dataclasses.replace(r, arrival_s=0.0) for r in moe_requests]
    if how == "quarantined_at_prefill":
        # rid 0's FIRST program is its prefill: poisoned there
        burst = burst[:1] + [dataclasses.replace(r, output_len=1)
                             for r in burst[1:]]
    loop, w = _play(moe_engine, burst, tmp_path,
                    handler=FakeHandler(after) if after else None, **kw)
    assert happened(loop, w), how
    cache = loop.cache
    assert not loop.active
    assert cache.free_pages == moe_engine.num_pages - 1
    assert cache.ledger.reserved_now == 0
    assert cache.ledger.written_now == 0
    assert loop.finished + (loop.drained or {}).get("unfinished", 0) \
        == loop.n


def test_summarize_is_a_function_of_the_closed_loop(moe_engine,
                                                    moe_requests,
                                                    tmp_path):
    loop, w = _play(moe_engine, moe_requests, tmp_path)
    one = loop_mod.summarize(loop, 0)
    two = loop_mod.summarize(loop, 0)
    assert one == two and list(one) == SHAPES["decode"][0][:-1]
    assert one["tokens"] == loop.tokens_out
    assert one["kv_pool"]["pages_peak"] == loop.cache.pages_peak
    # summarizing wrote nothing: the events are run()'s
    assert "serve_summary" not in {r["kind"] for r in w.records}
    via_run = moe_engine.run(
        moe_requests, clock=engine_mod.VirtualClock(SERVE_VCOSTS))
    wall = ("loop_phases", "loop_wall_s", "op_parts")
    assert {k: v for k, v in via_run.items() if k not in wall} \
        == {k: v for k, v in one.items() if k not in wall}


def test_a_run_counts_its_own_compiles_not_the_cache_dirs_entries(
        moe_engine, moe_requests):
    """``post_warmup_compiles`` is what THIS process compiled during the
    run: entries that another process (another pytest worker, another
    engine) writes into the shared cache dir meanwhile are not the run's
    — what failed eight serve tests in the driver's six-worker run on a
    cold cache (PR 35)."""
    import os

    if moe_engine.cache_dir:
        stranger = os.path.join(moe_engine.cache_dir, "another-process")
        with open(stranger, "w") as f:
            f.write("x")
    try:
        summary = moe_engine.run(
            moe_requests, clock=engine_mod.VirtualClock(SERVE_VCOSTS))
    finally:
        if moe_engine.cache_dir:
            os.remove(stranger)
    assert summary["post_warmup_compiles"] == 0
    assert summary["completed"] == len(moe_requests)


def test_process_compiles_counts_a_compile_of_this_process():
    import jax
    import jax.numpy as jnp

    from tpu_hc_bench.utils import compile_cache

    x = jnp.zeros((7, 13, 3), jnp.int8)
    before = compile_cache.process_compiles()
    # a shape no other test compiles: one fresh program
    jax.jit(lambda x: x * 3 + 1)(x)
    assert compile_cache.process_compiles() == before + 1


# --- the sizes and the one door, pinned at the source ------------------


def _functions(module):
    tree = ast.parse(inspect.getsource(module))
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def test_run_is_short_and_no_function_is_longer_than_a_screen_or_two():
    run = next(f for f in _functions(engine_mod) if f.name == "run")
    assert run.end_lineno - run.lineno + 1 < 150
    # run shares no state with a nested function: it has none
    assert not [n for n in ast.walk(run) if n is not run and isinstance(
        n, (ast.FunctionDef, ast.Lambda))]
    for module in (loop_mod, cache_mod):
        for f in _functions(module):
            assert f.end_lineno - f.lineno + 1 <= 150, (
                f"{module.__name__}.{f.name}")
    for module in (engine_mod, loop_mod, cache_mod):
        assert not [n for n in ast.walk(ast.parse(inspect.getsource(
            module))) if isinstance(n, ast.Nonlocal)], module.__name__


@pytest.mark.parametrize("module", [engine_mod, loop_mod],
                         ids=["engine", "loop"])
def test_pages_slots_and_ledger_are_reached_through_the_manager(module):
    src = inspect.getsource(module)
    for direct in ("allocator.", "ledger.", "PrefixCache(", ".slots.free",
                   "prefix_cache import"):
        assert direct not in src, f"{module.__name__}: {direct!r}"
    if module is engine_mod:
        for moved in ("PageAllocator", "SlotAllocator", "KVLedger",
                      "CacheManager"):
            assert not hasattr(module, moved)
