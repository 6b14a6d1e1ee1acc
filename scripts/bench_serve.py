"""Serving-lane A/B benchmarks: batching arms, and decode-kernel arms.

``--mode batching`` (default) is the round-16 acceptance experiment:
ONE warmed engine (every (batch, seqlen) bucket AOT-compiled once,
through the shared compile cache), ONE identical seeded request
trace, TWO scheduler arms —

- ``static``: the classic control — collect a full batch, run it to
  completion, only then admit again; arrivals queue while stragglers
  finish.
- ``continuous``: Orca-style — admission and retirement per decode
  step; a retired request's slot is refilled at the very next step.

``--mode decode`` (round 18) is the decode-kernel/quantization A/B:
one engine PER arm (the arms compile different decode programs), same
trace, continuous batching —

- ``gather/off``: the dense-gather reference (``_attend_rows``);
- ``paged/off``: the Pallas flash-decode kernel reading K/V through
  the page tables (``ops.paged_attention``);
- ``paged/int8_kv``: + int8 KV pool with per-page scales consumed
  inside the kernel;
- ``paged/int8_w``: + per-channel int8 weights dequantized at the
  matmul.

The verdict checks the worst decode bucket's AOT ``memory_analysis``
temp bytes (the dense-gather temporaries the kernel eliminates), the
int8 pool's argument-byte shrink, ZERO post-warmup compiles on every
arm, and token-for-token parity of the f32 arms (read back from the
per-arm request records).

``--mode kv`` (round 25, supersedes the round-22 honesty A/B) is the
allocation A/B: ONE warmed engine, one fixed constrained pool, one
trace with an imposed shared prompt prefix, THREE ``(kv_reserve,
prefix_cache)`` arms — worst-case reservation (the round-22 control),
lazy on-demand growth, and lazy + the COW shared-prefix cache.  The
headline is the lazy+prefix arm's ``kv_pool_util``; the verdict
requires strictly more admitted req/s than the control at the SAME
pool bytes, util above the round-22 waste line, and token-for-token
parity on every arm.

``--mode faults`` (round 23) is the overload-survival A/B: one warmed
engine, one overload trace, one fixed fault schedule (NaN-poisoned
requests + a sticky KV-pool squeeze), shedding+preemption+quarantine
vs the no-degradation control.  Headline: served-within-SLO goodput —
the degrading arm must answer MORE of the trace correctly within
``--deadline_ms`` than the arm that heroically serves everything late.

``--mode signals`` (round 24) is the sensing A/B: one warmed engine,
policy knobs pinned OFF, a clean control trace vs an injected
overload + sticky pool squeeze.  The health-signal engine must fire
``SUSTAINED_OVERLOAD`` and ``KV_PRESSURE`` on the overload arm (the
KV onset at/after the injection instant) and NOTHING on the control
arm, and both arms' merged-sketch p99 must land inside the exact
stored-sample bracket widened by the sketch's relative-error bound.

Every mode folds the per-arm KV-pool ledger (``kv_pool`` /
``kv_pool_util`` / ``kv_req_gap_frac``) into its arms.

Both modes emit a BENCH-style JSON record with
``decode_attention``/``quant``/``aot_decode_temp_bytes`` in ``extra``
(the fields ``obs regress``/``obs diff`` track) plus ``obs
diff``-renderable per-arm metrics dirs under ``--metrics_root``.

Env knobs (CI parity with bench.py):

- ``BENCH_MODEL`` (default moe_tiny), ``BENCH_ARRIVAL_RATE``,
  ``BENCH_SERVE_BUCKETS``, ``BENCH_REQUESTS``, ``BENCH_MAX_IN_FLIGHT``,
  ``BENCH_DECODE_ATTENTION``, ``BENCH_QUANT``, ``BENCH_MODE``.  The
  compile cache is the one every entry point shares
  (``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``), so the
  zero-recompile assertion is a measured cache-entry delta.

Usage:
  JAX_PLATFORMS=cpu python scripts/bench_serve.py \
      [--mode batching|decode] [--json OUT.json] [--metrics_root DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, ".")


def _build_cfg(args, **overrides):
    from tpu_hc_bench import flags as flags_mod

    kw = dict(
        model=args.model,
        workload="serve",
        arrival=args.arrival,
        arrival_rate=args.arrival_rate,
        num_requests=args.num_requests,
        serve_buckets=args.serve_buckets,
        max_in_flight=args.max_in_flight,
        kv_page_size=args.kv_page_size,
        max_prompt_len=args.max_prompt_len,
        max_output_len=args.max_output_len,
        decode_attention=args.decode_attention,
        quant=args.quant,
        decode_block_pages=args.decode_block_pages,
        seed=args.seed,
    )
    kw.update(overrides)
    return flags_mod.BenchmarkConfig(**kw).resolve()


def run_ab(args) -> dict:
    from tpu_hc_bench import flags as flags_mod
    from tpu_hc_bench.obs import metrics as obs_metrics
    from tpu_hc_bench.serve import cli as serve_cli

    cfg = _build_cfg(args)

    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    engine, requests = serve_cli.build_engine_and_requests(cfg, log)

    arms: dict[str, dict] = {}
    for arm in ("static", "continuous"):
        mdir = None
        arm_cfg = cfg
        if args.metrics_root:
            mdir = os.path.join(args.metrics_root, arm)
            # per-arm manifest: obs diff renders the batching flip as
            # config drift next to the serve-metric delta rows
            arm_cfg = flags_mod.BenchmarkConfig(
                **{**cfg.__dict__,
                   "translations": {}, "batching": arm,
                   "explicit_flags": None, "tuned_config": None})
        log(f"--- arm: {arm} ---")
        summary = serve_cli.run_serve(
            engine, requests, serve_cli.serve_writer(arm_cfg, mdir),
            batching=arm)
        arms[arm] = {
            "tokens_per_s": summary["tokens_per_s"],
            "p99_e2e_ms": summary["p99_e2e_ms"],
            "p99_ttft_ms": summary["p99_ttft_ms"],
            "p50_e2e_ms": summary["p50_e2e_ms"],
            "p99_queue_ms": summary.get("p99_queue_ms"),
            "goodput": summary["goodput"],
            "queue_depth_max": summary["queue_depth_max"],
            "wall_s": summary["wall_s"],
            "completed": summary["completed"],
            "post_warmup_compiles": summary["post_warmup_compiles"],
            # round 20: the tail-attribution fold (obs.requests) — the
            # A/B's WHY column: static's p99 lives in queue_wait/
            # decode_stall, continuous moves it back to decode_active
            "attribution": summary.get("attribution"),
            # round 22 (obs.kv): the pool ledger per arm — static's
            # fill-then-drain pattern and continuous' refill-per-step
            # produce different written/reserved integrals on the SAME
            # reservation policy
            "kv_pool": summary.get("kv_pool"),
            "kv_pool_util": summary.get("kv_pool_util"),
            "kv_req_gap_frac": summary.get("kv_req_gap_frac"),
            # round 24: the merged-sketch tail + any fired health
            # signals per arm
            "p99_merged_ms": summary.get("p99_merged_ms"),
            "signals_fired": summary.get("signals_fired"),
            "signals_fired_total": summary.get("signals_fired_total"),
            "metrics_dir": mdir,
        }

    from tpu_hc_bench.obs import requests as requests_mod

    st, ct = arms["static"], arms["continuous"]
    st_attr, ct_attr = st["attribution"], ct["attribution"]
    verdict = {
        # the two acceptance properties: continuous beats static on the
        # p99 tail AND on goodput-under-load, at the same offered load
        "continuous_beats_static_p99": ct["p99_e2e_ms"] < st["p99_e2e_ms"],
        "continuous_beats_static_goodput": ct["goodput"] > st["goodput"],
        "p99_e2e_delta_pct": round(
            100.0 * (ct["p99_e2e_ms"] - st["p99_e2e_ms"])
            / max(st["p99_e2e_ms"], 1e-9), 1),
        "goodput_delta_pct": round(
            100.0 * (ct["goodput"] - st["goodput"])
            / max(st["goodput"], 1e-9), 1),
        "zero_post_warmup_compiles": (
            ct["post_warmup_compiles"] == 0
            and st["post_warmup_compiles"] == 0),
        # the attribution story: continuous batching's tail spends a
        # smaller share of its e2e waiting (queue + resident-starved)
        # than static's, at the same offered load
        "continuous_tail_waits_less": (
            (ct_attr["tail_frac"]["queue_wait"]
             + ct_attr["tail_frac"]["decode_stall"])
            < (st_attr["tail_frac"]["queue_wait"]
               + st_attr["tail_frac"]["decode_stall"])
            if st_attr and ct_attr else None),
        "compile_cache": engine.cache_dir,
        "compile_record": engine.compile_record,
    }
    manifest = obs_metrics.manifest_subset(
        obs_metrics.run_manifest(cfg=cfg))
    return {
        "metric": f"{cfg.model}_serve_tokens_per_s",
        "value": ct["tokens_per_s"],
        "unit": "tokens/sec",
        # continuous over the classic static arm at the same load — the
        # serving analog of bench.py's vs-reference ratio
        "vs_baseline": round(
            ct["tokens_per_s"] / max(st["tokens_per_s"], 1e-9), 3),
        "extra": {
            "workload": "serve",
            "model": cfg.model,
            "arrival": cfg.arrival,
            "arrival_rate": cfg.arrival_rate,
            "num_requests": cfg.num_requests,
            "max_prompt_len": cfg.max_prompt_len,
            "max_output_len": cfg.max_output_len,
            "buckets": list(engine.batch_buckets),
            "max_in_flight": engine.cap,
            "kv_page_size": engine.page_size,
            "kv_pages": engine.num_pages,
            "decode_attention": cfg.decode_attention,
            "quant": cfg.quant,
            "aot_decode_temp_bytes": engine.compile_record.get(
                "aot_decode_temp_bytes"),
            "p99_ms": ct["p99_e2e_ms"],
            "goodput": ct["goodput"],
            "tokens_per_s": ct["tokens_per_s"],
            # the regress gate's attribution-shift metrics (headline =
            # continuous arm, matching the other extras)
            **requests_mod.flatten_attribution(ct_attr),
            # round 22: the regress gate's allocation-honesty metric
            "kv_pool_util": ct.get("kv_pool_util"),
            "kv_req_gap_frac": ct.get("kv_req_gap_frac"),
            # round 24: the regress gate's merged tail + fire count
            # (headline = continuous arm, matching the other extras)
            "p99_merged_ms": ct.get("p99_merged_ms"),
            "signals_fired_total": ct.get("signals_fired_total"),
            # the static-vs-continuous attribution delta as `obs diff`
            # renders it (also viewable live: obs diff <root>/static
            # <root>/continuous)
            "attribution_diff": requests_mod.attribution_diff_lines(
                st_attr, ct_attr),
            "arms": arms,
            "verdict": verdict,
        },
        "manifest": manifest,
    }


DECODE_ARMS = (("gather", "off"), ("paged", "off"),
               ("paged", "int8_kv"), ("paged", "int8_w"))


def run_decode_ab(args) -> dict:
    """The round-18 decode-kernel/quant A/B: one engine per arm (the
    arms compile different decode programs), same seeded trace,
    continuous batching, zero post-warmup compiles everywhere."""
    import tempfile

    from tpu_hc_bench.obs import metrics as obs_metrics
    from tpu_hc_bench.serve import cli as serve_cli

    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    root = args.metrics_root or tempfile.mkdtemp(prefix="bench_decode_")
    arms: dict[str, dict] = {}
    tokens: dict[str, dict] = {}
    base_cfg = None
    for da, q in DECODE_ARMS:
        arm = f"{da}+{q}"
        cfg = _build_cfg(args, decode_attention=da, quant=q,
                         decode_block_pages=(args.decode_block_pages
                                             if da == "paged" else 0))
        base_cfg = base_cfg or cfg
        log(f"--- decode arm: {arm} ---")
        engine, requests = serve_cli.build_engine_and_requests(cfg, log)
        mdir = os.path.join(root, arm.replace("+", "_"))
        summary = serve_cli.run_serve(
            engine, requests, serve_cli.serve_writer(cfg, mdir),
            batching="continuous")
        toks = {}
        with open(os.path.join(mdir, "metrics.jsonl")) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("kind") == "request":
                    toks[rec["id"]] = rec.get("generated")
        tokens[arm] = toks
        arms[arm] = {
            "decode_attention": da,
            "quant": q,
            "tokens_per_s": summary["tokens_per_s"],
            "p99_e2e_ms": summary["p99_e2e_ms"],
            "p99_ttft_ms": summary["p99_ttft_ms"],
            "goodput": summary["goodput"],
            "completed": summary["completed"],
            "aot_decode_temp_bytes": summary["aot_decode_temp_bytes"],
            "post_warmup_compiles": summary["post_warmup_compiles"],
            "attribution": summary.get("attribution"),
            # round 22 (obs.kv): the pool ledger per arm
            "kv_pool": summary.get("kv_pool"),
            "kv_pool_util": summary.get("kv_pool_util"),
            "kv_req_gap_frac": summary.get("kv_req_gap_frac"),
            "kv_pool_bytes": summary.get("kv_pool_bytes"),
            "metrics_dir": mdir,
        }
        wk, wma = engine.aot_memory_worst(kinds=("decode",))
        if wma:
            arms[arm]["aot_decode_args_bytes"] = wma.get("argument_bytes")

    ga, pa = arms["gather+off"], arms["paged+off"]
    kv = arms["paged+int8_kv"]
    tmp_g, tmp_p = ga["aot_decode_temp_bytes"], pa["aot_decode_temp_bytes"]
    int8_match = sum(
        1 for rid, t in tokens["gather+off"].items()
        if tokens["paged+int8_kv"].get(rid) == t)
    verdict = {
        # the kernel eliminates the dense-gather temporaries: worst
        # decode bucket's AOT temp bytes must drop vs the reference
        "paged_temp_lt_gather": (
            tmp_g is not None and tmp_p is not None and tmp_p < tmp_g),
        "temp_bytes_delta_pct": (
            round(100.0 * (tmp_p - tmp_g) / max(tmp_g, 1), 1)
            if tmp_g and tmp_p is not None else None),
        # the int8 pool quarters the KV argument bytes
        "int8_kv_args_lt_gather": (
            kv.get("aot_decode_args_bytes") or 0)
            < (ga.get("aot_decode_args_bytes") or 0),
        # pinned parity: f32 paged decode is token-for-token identical
        # to the gather reference; int8 arms are tolerance arms, their
        # match count is reported, not asserted
        "paged_token_parity": tokens["gather+off"] == tokens["paged+off"],
        "int8_kv_token_matches": f"{int8_match}/"
                                 f"{len(tokens['gather+off'])}",
        "zero_post_warmup_compiles": all(
            a["post_warmup_compiles"] == 0 for a in arms.values()),
        "all_completed": all(a["completed"] == args.num_requests
                             for a in arms.values()),
    }
    manifest = obs_metrics.manifest_subset(
        obs_metrics.run_manifest(cfg=base_cfg))
    return {
        "metric": f"{args.model}_decode_kernel_ab",
        "value": pa["tokens_per_s"],
        "unit": "tokens/sec",
        # the paged kernel over the dense-gather reference at the same
        # load — the decode-kernel analog of the batching A/B ratio
        "vs_baseline": round(
            pa["tokens_per_s"] / max(ga["tokens_per_s"], 1e-9), 3),
        "extra": {
            "workload": "serve",
            "mode": "decode",
            "model": args.model,
            "arrival_rate": args.arrival_rate,
            "num_requests": args.num_requests,
            "max_prompt_len": args.max_prompt_len,
            "max_output_len": args.max_output_len,
            "kv_page_size": args.kv_page_size,
            "decode_attention": "paged",
            "quant": "off",
            "aot_decode_temp_bytes": tmp_p,
            "p99_ms": pa["p99_e2e_ms"],
            "goodput": pa["goodput"],
            "tokens_per_s": pa["tokens_per_s"],
            "kv_pool_util": pa.get("kv_pool_util"),
            "kv_req_gap_frac": pa.get("kv_req_gap_frac"),
            "arms": arms,
            "verdict": verdict,
        },
        "manifest": manifest,
    }


#: round 25: (kv_reserve, prefix_cache) policy arms over ONE warmed
#: engine at one FIXED constrained pool — worst-case reservation is
#: the round-22 control whose measured waste this A/B must reclaim
KV_ARMS = (("worst", "off"), ("lazy", "off"), ("lazy", "on"))

#: virtual per-step costs (seconds) — page_copy included so the COW
#: device copy is charged deterministically like any other program
KV_VCLOCK = {"prefill": 0.004, "decode": 0.003, "classify": 0.002,
             "page_copy": 0.001}


def run_kv_ab(args) -> dict:
    """The round-25 allocation A/B: ONE warmed engine (gather/off —
    the arms differ ONLY in allocation policy, never in kernels), ONE
    seeded trace with an imposed shared prompt prefix, ONE fixed
    constrained pool sized well below ``max_in_flight`` worst-case
    tables, THREE ``(kv_reserve, prefix_cache)`` arms —

    - ``worst+off``: the round-22 control — admission reserves the
      full table width up front; the pool admits few residents and
      ~45% of reserved page-seconds are never written.
    - ``lazy+off``: admission reserves ``ceil(prompt/page)`` + headroom
      and decode grows pages on demand (``--kv_preempt=on`` absorbs
      growth failure); same pool now holds more residents.
    - ``lazy+on``: + the COW shared-prefix cache — requests repeating
      a page-aligned prefix map those slots to shared physical pages
      and skip the prefill page writes for them.

    The headline is the lazy+prefix arm's ``kv_pool_util``; the
    verdict requires it to admit strictly more req/s than the control
    AT THE SAME POOL BYTES, util above the round-22 waste line, a
    shrunken honesty gap, and token-for-token parity of every arm
    (sharing and growth are allocation tricks — they must never change
    what a request decodes).  VirtualClock (with an explicit
    ``page_copy`` cost) keeps the artifact deterministic."""
    import dataclasses
    import tempfile

    import numpy as np

    from tpu_hc_bench.obs import metrics as obs_metrics
    from tpu_hc_bench.serve import cli as serve_cli
    from tpu_hc_bench.serve import engine as engine_mod

    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    root = args.metrics_root or tempfile.mkdtemp(prefix="bench_kv_")

    # the FIXED constrained pool: far below max_in_flight worst-case
    # tables (the worst arm can only hold a few residents), page 0
    # reserved as trash — identical bytes for every arm by design
    table_width = -(-(args.max_prompt_len + args.max_output_len)
                    // args.kv_page_size)
    kv_pages = 1 + max(2, args.max_in_flight // 2) * table_width
    # offered at overload so the POOL, not the arrival process, is the
    # bottleneck — admitted req/s then measures what each reservation
    # policy fits into the same bytes; headroom 0 makes decode growth
    # real (every page past the prompt's is allocated on demand)
    cfg = _build_cfg(args, decode_attention="gather", quant="off",
                     decode_block_pages=0, kv_pages=kv_pages,
                     kv_growth_headroom=0,
                     arrival_rate=max(args.arrival_rate,
                                      args.overload_rate))
    engine, requests = serve_cli.build_engine_and_requests(cfg, log)

    # impose the shared prefix the cache exists for: every prompt's
    # first page worth of tokens becomes one fixed seeded block (kept
    # inside each prompt's own length — arrival times and lengths are
    # untouched, so the trace's offered load is identical)
    vocab = engine.spec.vocab_size
    block = np.random.default_rng((args.seed, 25)).integers(
        0, vocab, size=args.kv_page_size, dtype=np.int32)
    requests = [
        dataclasses.replace(
            r, prompt=np.concatenate(
                [block[:min(len(r.prompt), args.kv_page_size)],
                 r.prompt[min(len(r.prompt), args.kv_page_size):]]))
        if r.prompt is not None and len(r.prompt) else r
        for r in requests]

    arms: dict[str, dict] = {}
    tokens: dict[str, dict] = {}
    for kr, pc in KV_ARMS:
        arm = f"{kr}+{pc}"
        mdir = os.path.join(root, arm.replace("+", "_"))
        log(f"--- kv arm: kv_reserve={kr} prefix_cache={pc} ---")
        writer = serve_cli.serve_writer(cfg, mdir)
        try:
            summary = engine.run(
                requests, batching="continuous", writer=writer,
                clock=engine_mod.VirtualClock(KV_VCLOCK),
                kv_reserve=kr, prefix_cache=pc,
                # lazy admission can over-admit; growth failure must
                # preempt-and-requeue instead of stalling
                kv_preempt=("on" if kr == "lazy" else "off"))
        finally:
            writer.close()
        toks = {}
        with open(os.path.join(mdir, "metrics.jsonl")) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("kind") == "request":
                    toks[rec["id"]] = rec.get("generated")
        tokens[arm] = toks
        kvf = summary.get("kv_pool") or {}
        arms[arm] = {
            "kv_reserve": kr,
            "prefix_cache": pc,
            "kv_pool": summary.get("kv_pool"),
            "kv_pool_util": summary.get("kv_pool_util"),
            "kv_req_gap_frac": summary.get("kv_req_gap_frac"),
            "kv_pool_bytes": summary.get("kv_pool_bytes"),
            "kv_pages": engine.num_pages,
            "kv_page_size": engine.page_size,
            "prefix_hit_frac": summary.get("prefix_hit_frac"),
            "pages_grown_total": summary.get("pages_grown_total"),
            "cow_copies": kvf.get("cow_copies"),
            "prefix_pages_shared": kvf.get("prefix_pages_shared"),
            # the fraction of reserved page-seconds never written,
            # restated in pool bytes at this (shared) page cost
            "wasted_pool_bytes": (
                round((1.0 - kvf["util"]) * summary["kv_pool_bytes"])
                if isinstance(kvf.get("util"), (int, float))
                and summary.get("kv_pool_bytes") else None),
            # the admission headline at the FIXED pool: how fast the
            # constrained pool drains the same offered trace
            "req_per_s": round(
                summary["completed"] / max(summary["wall_s"], 1e-9), 3),
            "wall_s": summary["wall_s"],
            "tokens_per_s": summary["tokens_per_s"],
            "p99_e2e_ms": summary["p99_e2e_ms"],
            "goodput": summary["goodput"],
            "completed": summary["completed"],
            "preempts": (summary.get("degrade") or {}).get("preempts"),
            "post_warmup_compiles": summary["post_warmup_compiles"],
            "metrics_dir": mdir,
        }

    ctl = arms["worst+off"]
    lzy = arms["lazy+off"]
    shr = arms["lazy+on"]
    util = shr.get("kv_pool_util")
    gap = shr.get("kv_req_gap_frac")
    verdict = {
        # round-22 carryover: the control still measures a real gap
        "gap_measured": (
            isinstance(ctl.get("kv_pool_util"), (int, float))
            and ctl["kv_pool_util"] < 1.0
            and isinstance(ctl.get("kv_req_gap_frac"), (int, float))
            and ctl["kv_req_gap_frac"] > 0.0),
        "control_kv_pool_util": ctl.get("kv_pool_util"),
        "control_req_gap_frac": ctl.get("kv_req_gap_frac"),
        # the round-25 acceptance: same pool bytes, more admitted req/s
        "lazy_prefix_beats_control_req_per_s": (
            shr["req_per_s"] > ctl["req_per_s"]),
        "same_pool_bytes_across_arms": (
            len({a["kv_pool_bytes"] for a in arms.values()}) == 1),
        "lazy_prefix_kv_pool_util": util,
        "lazy_prefix_req_gap_frac": gap,
        "util_above_waste_line": (
            isinstance(util, (int, float)) and util > 0.55),
        "gap_below_r22_waste": (
            isinstance(gap, (int, float)) and gap < 0.382),
        "prefix_hit_frac": shr.get("prefix_hit_frac"),
        "pages_grown_total": lzy.get("pages_grown_total"),
        "cow_copies": shr.get("cow_copies"),
        # allocation tricks never change tokens: both lazy arms decode
        # the exact streams of the worst-case control
        "lazy_token_parity": tokens["lazy+off"] == tokens["worst+off"],
        "prefix_token_parity": tokens["lazy+on"] == tokens["worst+off"],
        "zero_post_warmup_compiles": all(
            a["post_warmup_compiles"] == 0 for a in arms.values()),
        "all_completed": all(a["completed"] == args.num_requests
                             for a in arms.values()),
    }
    manifest = obs_metrics.manifest_subset(
        obs_metrics.run_manifest(cfg=cfg))
    return {
        "metric": f"{args.model}_kv_pool_util",
        "value": util,
        "unit": "written_page_s/reserved_page_s",
        "vs_baseline": (
            round(util / max(ctl.get("kv_pool_util") or 1e-9, 1e-9), 3)
            if isinstance(util, (int, float)) else None),
        "extra": {
            "workload": "serve",
            "mode": "kv",
            "model": args.model,
            "arrival_rate": args.arrival_rate,
            "num_requests": args.num_requests,
            "max_prompt_len": args.max_prompt_len,
            "max_output_len": args.max_output_len,
            "kv_page_size": args.kv_page_size,
            "kv_pages": kv_pages,
            "decode_attention": "gather",
            "quant": "off",
            # headline arm = lazy+prefix (what the regress gate tracks)
            "kv_reserve": "lazy",
            "prefix_cache": "on",
            "kv_pool_util": util,
            "kv_req_gap_frac": gap,
            "prefix_hit_frac": shr.get("prefix_hit_frac"),
            "pages_grown_total": shr.get("pages_grown_total"),
            "goodput": shr["goodput"],
            "tokens_per_s": shr["tokens_per_s"],
            "arms": arms,
            "verdict": verdict,
        },
        "manifest": manifest,
    }


#: the round-23 fixed fault schedule: three poisoned requests spread
#: through the trace; the pool squeeze lands just after traffic starts
#: and is sized at run time so the squeezed pool still fits two
#: residents (a deeper squeeze would stall the no-degradation control
#: outright and the A/B would measure a crash, not a policy)
FAULT_NAN_RIDS = (5, 11, 23)
FAULT_SQUEEZE_T = 0.05


def run_faults_ab(args) -> dict:
    """The round-23 overload-survival A/B: ONE warmed engine, one
    seeded overload trace (arrival rate far above service capacity),
    one fixed fault schedule (NaN-poisoned requests + a sticky KV-pool
    squeeze), TWO policy arms —

    - ``control``: no degradation (``--shed=off``, ``--kv_preempt=off``)
      — the pre-round-23 engine: poisoned requests serve garbage,
      squeezed admission head-of-line blocks, every request is served
      arbitrarily late.
    - ``degrade``: ``--shed=deadline`` + ``--kv_preempt=on`` — expired
      and hopeless requests are shed with a cause, poisoned requests
      are quarantined, pool pressure preempts/requeues instead of
      blocking.

    The headline is served-within-SLO goodput: the fraction of the
    offered trace answered CORRECTLY (known-poisoned rids never count —
    the control serves them, but serves NaN garbage) within
    ``--deadline_ms``.  Runs under VirtualClock so the artifact is a
    deterministic property of the policies, not of host load."""
    from tpu_hc_bench.obs import metrics as obs_metrics
    from tpu_hc_bench.serve import cli as serve_cli
    from tpu_hc_bench.serve import engine as engine_mod
    from tpu_hc_bench.serve import faults as faults_mod

    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    import tempfile

    root = args.metrics_root or tempfile.mkdtemp(prefix="bench_faults_")
    cfg = _build_cfg(args, slo_e2e_ms=args.deadline_ms)
    engine, requests = serve_cli.build_engine_and_requests(cfg, log)
    squeeze = max(0, engine.num_pages - 2 * engine.table_width)
    spec = ",".join(
        [f"nan_logits@{r}" for r in FAULT_NAN_RIDS
         if r < args.num_requests]
        + ([f"pool_squeeze@{FAULT_SQUEEZE_T}:{squeeze}"]
           if squeeze else []))
    vclock = {"prefill": 0.004, "decode": 0.003, "classify": 0.002}

    arm_policies = {
        "control": dict(shed="off", kv_preempt="off"),
        "degrade": dict(shed="deadline", kv_preempt="on"),
    }
    arms: dict[str, dict] = {}
    for arm, policy in arm_policies.items():
        mdir = os.path.join(root, arm)
        log(f"--- faults arm: {arm} ({spec}) ---")
        writer = serve_cli.serve_writer(cfg, mdir)
        fleet = None
        try:
            summary = engine.run(
                requests, batching="continuous", writer=writer,
                clock=engine_mod.VirtualClock(vclock),
                faults=faults_mod.parse_serve_plan(spec),
                deadline_ms=args.deadline_ms, **policy)
        finally:
            writer.close()
        served_ok = 0
        counts = {"request": 0, "shed": 0, "quarantine": 0}
        with open(os.path.join(mdir, "metrics.jsonl")) as f:
            for line in f:
                rec = json.loads(line)
                kind = rec.get("kind")
                if kind in counts:
                    counts[kind] += 1
                if (kind == "request"
                        and rec["id"] not in FAULT_NAN_RIDS
                        and rec["e2e_ms"] <= args.deadline_ms):
                    served_ok += 1
        arms[arm] = {
            **policy,
            "served_within_slo": round(
                served_ok / max(1, args.num_requests), 4),
            "completed": summary["completed"],
            "shed": counts["shed"],
            "quarantined": counts["quarantine"],
            "degrade": summary.get("degrade"),
            "shed_frac": summary.get("shed_frac"),
            "p99_e2e_ms": summary.get("p99_e2e_ms"),
            "goodput": summary["goodput"],
            "slo": summary.get("slo"),
            "post_warmup_compiles": summary["post_warmup_compiles"],
            "metrics_dir": mdir,
        }

    ctl, deg = arms["control"], arms["degrade"]
    verdict = {
        # the acceptance property: under the SAME overload + faults,
        # degrading serves MORE of the trace correctly within SLO than
        # heroically serving everything late (and some of it poisoned)
        "degrade_beats_control_goodput": (
            deg["served_within_slo"] > ctl["served_within_slo"]),
        "served_within_slo_delta": round(
            deg["served_within_slo"] - ctl["served_within_slo"], 4),
        # every degraded exit carries a cause (folded by obs summarize)
        "sheds_caused": deg["degrade"]["shed"],
        "quarantined": deg["quarantined"],
        "preempts": deg["degrade"]["preempts"],
        "zero_post_warmup_compiles": (
            ctl["post_warmup_compiles"] == 0
            and deg["post_warmup_compiles"] == 0),
        "compile_record": engine.compile_record,
    }
    manifest = obs_metrics.manifest_subset(
        obs_metrics.run_manifest(cfg=cfg))
    return {
        "metric": f"{cfg.model}_serve_faults_goodput",
        "value": deg["served_within_slo"],
        "unit": "served_within_slo_frac",
        "vs_baseline": round(
            deg["served_within_slo"]
            / max(ctl["served_within_slo"], 1e-9), 3),
        "extra": {
            "workload": "serve",
            "mode": "faults",
            "model": cfg.model,
            "arrival_rate": cfg.arrival_rate,
            "num_requests": args.num_requests,
            "deadline_ms": args.deadline_ms,
            "fault_spec": spec,
            "decode_attention": cfg.decode_attention,
            "quant": cfg.quant,
            "goodput": deg["goodput"],
            # the regress gate's direction-aware degradation metric
            "shed_frac": deg["shed_frac"],
            "arms": arms,
            "verdict": verdict,
        },
        "manifest": manifest,
    }


def run_signals_ab(args) -> dict:
    """The round-24 sensing A/B: ONE warmed engine, TWO traces —

    - ``control``: the default offered load (``--arrival_rate``), no
      faults.  The health-signal engine must stay silent end to end:
      any fire here is a false positive and fails the verdict.
    - ``overload``: the same request shapes at ``--overload_rate``
      (far above service capacity) plus the round-23 sticky KV-pool
      squeeze landing at t=``FAULT_SQUEEZE_T``.  SUSTAINED_OVERLOAD
      and KV_PRESSURE must both fire, and KV_PRESSURE's first fire
      must land at or after the squeeze's injection instant.

    Degradation policy is pinned OFF on both arms — this A/B measures
    the autoscaler's SENSING half (does the engine see trouble, with
    hysteresis, without crying wolf), not the actuation the policies
    already cover in ``--mode faults``.  Both arms also check the
    merged-sketch p99 against the exact stored-sample tail read back
    from the full per-request stream: the sketch answer must land
    inside the order-statistic bracket widened by the sketch's own
    relative-error guarantee.  VirtualClock keeps the artifact a
    deterministic property of the traces."""
    import tempfile

    from tpu_hc_bench.obs import metrics as obs_metrics
    from tpu_hc_bench.obs import signals as signals_mod
    from tpu_hc_bench.obs import sketch as sketch_mod
    from tpu_hc_bench.serve import arrivals
    from tpu_hc_bench.serve import cli as serve_cli
    from tpu_hc_bench.serve import engine as engine_mod
    from tpu_hc_bench.serve import faults as faults_mod
    from tpu_hc_bench.serve import slo as slo_mod

    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    root = args.metrics_root or tempfile.mkdtemp(prefix="bench_signals_")
    cfg = _build_cfg(args, slo_e2e_ms=args.deadline_ms)
    engine, requests = serve_cli.build_engine_and_requests(cfg, log)
    vocab = engine.spec.vocab_size if engine.decode_mode else None
    ovl_cfg = _build_cfg(args, slo_e2e_ms=args.deadline_ms,
                         arrival_rate=args.overload_rate)
    ovl_requests = arrivals.build_requests(ovl_cfg, vocab)
    squeeze = max(0, engine.num_pages - 2 * engine.table_width)
    spec = (f"pool_squeeze@{FAULT_SQUEEZE_T}:{squeeze}"
            if squeeze else "")
    vclock = {"prefill": 0.004, "decode": 0.003, "classify": 0.002}

    arm_defs = {
        "control": (requests, None),
        "overload": (ovl_requests, spec or None),
    }
    arms: dict[str, dict] = {}
    for arm, (trace, fault_spec) in arm_defs.items():
        mdir = os.path.join(root, arm)
        log(f"--- signals arm: {arm}"
            + (f" ({fault_spec})" if fault_spec else "") + " ---")
        writer = serve_cli.serve_writer(cfg, mdir)
        try:
            summary = engine.run(
                trace, batching="continuous", writer=writer,
                clock=engine_mod.VirtualClock(vclock),
                faults=(faults_mod.parse_serve_plan(fault_spec)
                        if fault_spec else None),
                deadline_ms=args.deadline_ms, shed="off",
                kv_preempt="off")
        finally:
            writer.close()
        # exact stored-sample tail off the FULL per-request stream (the
        # summary's own fold rides the run-lifetime sketches; the raw
        # ring is bounded) — the sketch must land inside the exact
        # order-statistic bracket widened by its alpha guarantee
        e2e: list[float] = []
        with open(os.path.join(mdir, "metrics.jsonl")) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("kind") == "request":
                    e2e.append(float(rec["e2e_ms"]))
        e2e.sort()
        merged = summary.get("p99_merged_ms")
        alpha = sketch_mod.DEFAULT_ALPHA
        within = None
        exact_p99 = None
        if e2e:
            exact_p99 = slo_mod.percentile(e2e, 99)
            rank = 0.99 * (len(e2e) - 1)
            lo = e2e[int(rank)]
            hi = e2e[min(int(rank) + 1, len(e2e) - 1)]
            within = (merged is not None
                      and lo * (1.0 - alpha) - 1e-6 <= merged
                      <= hi * (1.0 + alpha) + 1e-6)
        events = signals_mod.read_signals(mdir)
        first_fire: dict[str, float] = {}
        for ev in events:
            if ev.get("state") == "fire":
                first_fire.setdefault(ev.get("signal"), ev.get("t"))
        arms[arm] = {
            "arrival_rate": (args.overload_rate if arm == "overload"
                             else cfg.arrival_rate),
            "fault_spec": fault_spec,
            "signals_fired": summary.get("signals_fired"),
            "signals_fired_total": summary.get("signals_fired_total"),
            "first_fire_t": first_fire,
            "signal_events": len(events),
            "p99_merged_ms": summary.get("p99_merged_ms"),
            "p99_exact_ms": (round(exact_p99, 3)
                             if exact_p99 is not None else None),
            "merged_vs_exact_pct": (
                round(100.0 * (merged - exact_p99) / max(exact_p99, 1e-9),
                      2)
                if merged is not None and exact_p99 else None),
            "merged_p99_within_bound": within,
            "sketch_windows": summary.get("sketch_windows"),
            "p99_e2e_ms": summary.get("p99_e2e_ms"),
            "goodput": summary["goodput"],
            "tokens_per_s": summary["tokens_per_s"],
            "completed": summary["completed"],
            "post_warmup_compiles": summary["post_warmup_compiles"],
            "metrics_dir": mdir,
        }

    ctl, ovl = arms["control"], arms["overload"]
    ovl_fired = ovl.get("signals_fired") or {}
    kv_onset = (ovl.get("first_fire_t") or {}).get("KV_PRESSURE")
    verdict = {
        # the sensing acceptance: the injected overload + pool squeeze
        # fire their signals, onset at/after injection, and the clean
        # arm never cries wolf
        "overload_fires_sustained_overload": (
            ovl_fired.get("SUSTAINED_OVERLOAD", 0) >= 1),
        "overload_fires_kv_pressure": (
            ovl_fired.get("KV_PRESSURE", 0) >= 1),
        "kv_onset_after_injection": (
            kv_onset is not None and kv_onset >= FAULT_SQUEEZE_T),
        "kv_pressure_onset_t": kv_onset,
        "control_zero_fires": ctl.get("signals_fired_total") == 0,
        "merged_p99_within_bound": bool(
            ctl.get("merged_p99_within_bound")
            and ovl.get("merged_p99_within_bound")),
        "zero_post_warmup_compiles": (
            ctl["post_warmup_compiles"] == 0
            and ovl["post_warmup_compiles"] == 0),
        "compile_record": engine.compile_record,
    }
    manifest = obs_metrics.manifest_subset(
        obs_metrics.run_manifest(cfg=cfg))
    return {
        "metric": f"{cfg.model}_serve_signal_sensing",
        "value": ovl.get("signals_fired_total"),
        "unit": "signals_fired",
        "vs_baseline": None,
        "extra": {
            "workload": "serve",
            "mode": "signals",
            "model": cfg.model,
            "arrival": cfg.arrival,
            "arrival_rate": cfg.arrival_rate,
            "overload_rate": args.overload_rate,
            "num_requests": args.num_requests,
            "deadline_ms": args.deadline_ms,
            "fault_spec": spec,
            "decode_attention": cfg.decode_attention,
            "quant": cfg.quant,
            # regress-gated: the HEALTHY arm's merged tail and fire
            # count — a drift in the clean config's p99 or ANY fire on
            # it flags (the abs floor is one fire)
            "p99_merged_ms": ctl.get("p99_merged_ms"),
            "latency_source": "sketch",
            "signals_fired": ctl.get("signals_fired"),
            "signals_fired_total": ctl.get("signals_fired_total"),
            "goodput": ctl["goodput"],
            "tokens_per_s": ctl["tokens_per_s"],
            "arms": arms,
            "verdict": verdict,
        },
        "manifest": manifest,
    }


def main() -> int:
    env = os.environ.get
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default=env("BENCH_MODEL", "moe_tiny"))
    ap.add_argument("--arrival", default=env("BENCH_ARRIVAL", "poisson"))
    ap.add_argument("--arrival_rate", type=float,
                    default=float(env("BENCH_ARRIVAL_RATE", "16")))
    ap.add_argument("--num_requests", type=int,
                    default=int(env("BENCH_REQUESTS", "48")))
    ap.add_argument("--serve_buckets",
                    default=env("BENCH_SERVE_BUCKETS", "auto"))
    ap.add_argument("--max_in_flight", type=int,
                    default=int(env("BENCH_MAX_IN_FLIGHT", "8")))
    ap.add_argument("--kv_page_size", type=int, default=16)
    ap.add_argument("--max_prompt_len", type=int, default=32)
    ap.add_argument("--max_output_len", type=int, default=16)
    ap.add_argument("--mode", choices=["batching", "decode", "kv",
                                       "faults", "signals"],
                    default=env("BENCH_MODE", "batching"),
                    help="batching: continuous-vs-static on one warmed "
                         "engine; decode: gather-vs-paged-vs-int8 "
                         "kernel arms, one engine each; kv: the "
                         "round-25 allocation A/B — worst-case "
                         "reservation vs lazy growth vs lazy+COW "
                         "prefix cache on one engine at one fixed "
                         "pool, headline = lazy+prefix kv_pool_util; "
                         "faults: "
                         "the round-23 overload-survival A/B — "
                         "shedding+preemption vs no degradation under "
                         "one fault schedule, headline = served-"
                         "within-SLO goodput; signals: the round-24 "
                         "sensing A/B — injected overload + pool "
                         "squeeze must fire SUSTAINED_OVERLOAD and "
                         "KV_PRESSURE, the clean control arm must "
                         "fire nothing")
    ap.add_argument("--deadline_ms", type=float,
                    default=float(env("BENCH_DEADLINE_MS", "150")),
                    help="faults/signals modes: the per-request e2e "
                         "SLO (shed target in faults; the overload "
                         "signal's violation threshold in signals)")
    ap.add_argument("--overload_rate", type=float,
                    default=float(env("BENCH_OVERLOAD_RATE", "120")),
                    help="signals mode: the overload arm's arrival "
                         "rate (req/s, far above service capacity)")
    ap.add_argument("--decode_attention",
                    choices=["gather", "paged"],
                    default=env("BENCH_DECODE_ATTENTION", "gather"),
                    help="batching mode: the decode program both "
                         "scheduler arms run on")
    ap.add_argument("--quant", choices=["off", "int8_w", "int8_kv"],
                    default=env("BENCH_QUANT", "off"))
    ap.add_argument("--decode_block_pages", type=int,
                    default=int(env("BENCH_DECODE_BLOCK_PAGES", "0")))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics_root", default=None,
                    help="write per-arm metrics dirs here; compare with "
                         "`python -m tpu_hc_bench.obs diff "
                         "<root>/static <root>/continuous`")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write the comparison JSON here")
    args = ap.parse_args()

    result = {"decode": run_decode_ab, "kv": run_kv_ab,
              "faults": run_faults_ab,
              "signals": run_signals_ab}.get(args.mode, run_ab)(args)
    print(json.dumps(result, indent=1))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)
        print(f"wrote {args.json}", file=sys.stderr)
    v = result["extra"]["verdict"]
    if args.mode == "decode":
        ok = (v["paged_temp_lt_gather"] and v["paged_token_parity"]
              and v["zero_post_warmup_compiles"] and v["all_completed"])
    elif args.mode == "kv":
        ok = (v["gap_measured"]
              and v["lazy_prefix_beats_control_req_per_s"]
              and v["same_pool_bytes_across_arms"]
              and v["util_above_waste_line"]
              and v["gap_below_r22_waste"]
              and v["lazy_token_parity"] and v["prefix_token_parity"]
              and v["zero_post_warmup_compiles"]
              and v["all_completed"])
    elif args.mode == "faults":
        ok = (v["degrade_beats_control_goodput"]
              and v["zero_post_warmup_compiles"])
    elif args.mode == "signals":
        ok = (v["overload_fires_sustained_overload"]
              and v["overload_fires_kv_pressure"]
              and v["kv_onset_after_injection"]
              and v["control_zero_fires"]
              and v["merged_p99_within_bound"]
              and v["zero_post_warmup_compiles"])
    else:
        ok = (v["continuous_beats_static_p99"]
              and v["continuous_beats_static_goodput"]
              and v["zero_post_warmup_compiles"])
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
