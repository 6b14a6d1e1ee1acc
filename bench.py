"""Headline benchmark: ResNet-50 synthetic images/sec/chip on real TPU.

Runs the reference measurement protocol (50 warmup + 100 timed batches,
``run-tf-sing-ucx-openmpi.sh:32-35``) on ResNet-50 with synthetic data —
the exact experiment of BASELINE.json config 1 — on every available chip,
and prints ONE JSON line.

``vs_baseline``: the reference publishes no numbers (BASELINE.md), so the
comparison point is the widely reported tf_cnn_benchmarks ResNet-50 fp32
MKL throughput of a 2-socket Xeon-Platinum HC-class node, ~85 images/sec
per node — i.e. vs_baseline is images/sec-per-chip over images/sec-per-
reference-node, worker-unit vs worker-unit.
"""

from __future__ import annotations

import json
import os
import sys

REFERENCE_NODE_IMAGES_PER_SEC = 85.0


def _maybe_regress(payload: dict) -> int:
    """``BENCH_REGRESS=1``: gate the exit code on the noise-aware
    regression check (``obs.regress``) after the JSON line is printed —
    the fresh record vs the median/MAD of the matching-fingerprint
    history (``BENCH_HISTORY`` sources, default ``BENCH_*.json`` +
    ``artifacts/`` in the cwd).  Opt-in: a plain bench run never reads
    history."""
    if os.environ.get("BENCH_REGRESS") != "1":
        return 0
    from tpu_hc_bench.obs import regress as regress_mod

    specs = None
    hist = os.environ.get("BENCH_HISTORY")
    if hist:
        specs = [s for s in hist.split(os.pathsep) if s]
    return regress_mod.run_regress(payload, specs, out=sys.stderr)


def _serve_main() -> int:
    """``BENCH_WORKLOAD=serve``: the serving-lane headline — one
    continuous-batching run of the round-16 engine at a fixed Poisson
    arrival rate, ONE JSON line (tokens/s + the p99/goodput SLO
    extras).  The continuous-vs-static A/B harness is
    ``scripts/bench_serve.py``; this entry keeps the serve headline in
    the same BENCH_*.json trajectory as the training one.  Shares the
    env grammar: BENCH_MODEL (a decoder/classify member),
    BENCH_ARRIVAL, BENCH_ARRIVAL_RATE, BENCH_REQUESTS, BENCH_SERVE_BUCKETS,
    BENCH_BATCHING, BENCH_DECODE_ATTENTION (gather|paged), BENCH_QUANT
    (off|int8_w|int8_kv), BENCH_DECODE_BLOCK_PAGES,
    BENCH_METRICS_DIR, BENCH_CONFIG=auto (resolves the <model>@serve
    registry row).  The extras carry decode_attention/quant and the
    worst decode bucket's AOT temp bytes so `obs regress`/`obs diff`
    track the decode-kernel win.
    """
    from tpu_hc_bench import flags
    from tpu_hc_bench.obs import metrics as obs_metrics
    from tpu_hc_bench.serve import cli as serve_cli

    cfg = flags.BenchmarkConfig(
        model=os.environ.get("BENCH_MODEL", "moe_tiny"),
        workload="serve",
        config=os.environ.get("BENCH_CONFIG", "manual"),
        arrival=os.environ.get("BENCH_ARRIVAL", "poisson"),
        arrival_rate=float(os.environ.get("BENCH_ARRIVAL_RATE", "16")),
        num_requests=int(os.environ.get("BENCH_REQUESTS", "48")),
        serve_buckets=os.environ.get("BENCH_SERVE_BUCKETS", "auto"),
        batching=os.environ.get("BENCH_BATCHING", "continuous"),
        decode_attention=os.environ.get("BENCH_DECODE_ATTENTION",
                                        "gather"),
        quant=os.environ.get("BENCH_QUANT", "off"),
        decode_block_pages=int(
            os.environ.get("BENCH_DECODE_BLOCK_PAGES", "0")),
        metrics_dir=os.environ.get("BENCH_METRICS_DIR") or None,
    ).resolve()
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    engine, requests = serve_cli.build_engine_and_requests(cfg, log)
    summary = serve_cli.run_serve(
        engine, requests, serve_cli.serve_writer(cfg, cfg.metrics_dir))
    manifest = obs_metrics.run_manifest(cfg=cfg)
    payload = {
        "metric": f"{cfg.model}_serve_tokens_per_s",
        "value": summary["tokens_per_s"],
        "unit": "tokens/sec",
        "vs_baseline": None,    # scripts/bench_serve.py carries the A/B
        "extra": {
            "workload": "serve",
            "batching": summary["batching"],
            "arrival": cfg.arrival,
            "arrival_rate": cfg.arrival_rate,
            "requests": summary["requests"],
            "completed": summary["completed"],
            "p99_ms": summary["p99_e2e_ms"],
            "p99_ttft_ms": summary["p99_ttft_ms"],
            "goodput": summary["goodput"],
            "tokens_per_s": summary["tokens_per_s"],
            "queue_depth_max": summary["queue_depth_max"],
            "buckets": summary["buckets"],
            "max_in_flight": summary["max_in_flight"],
            "kv_pages": summary["kv_pages"],
            "kv_page_size": summary["kv_page_size"],
            "decode_attention": summary.get("decode_attention"),
            "quant": summary.get("quant"),
            "aot_decode_temp_bytes": summary.get("aot_decode_temp_bytes"),
            "post_warmup_compiles": summary["post_warmup_compiles"],
            # round 20: the attribution-shift metrics obs regress gates
            # on (absent on pre-r20 history; the checks skip there)
            "tail_queue_wait_frac": summary.get("tail_queue_wait_frac"),
            "tail_decode_stall_frac": summary.get(
                "tail_decode_stall_frac"),
            # round 22: the allocation-honesty metrics obs regress
            # gates on (absent on pre-r22 history; the checks skip)
            "kv_pool_util": summary.get("kv_pool_util"),
            "kv_req_gap_frac": summary.get("kv_req_gap_frac"),
            # round 25: the lazy-reservation/prefix-sharing arms (part
            # of the regress fingerprint) and their gated metrics
            # (absent on pre-r25 history; the checks skip)
            "kv_reserve": summary.get("kv_reserve"),
            "prefix_cache": summary.get("prefix_cache"),
            "prefix_hit_frac": summary.get("prefix_hit_frac"),
            "pages_grown_total": summary.get("pages_grown_total"),
            # round 24: the merged-sketch tail + fired health signals
            # obs regress gates on (absent on pre-r24 history; skips)
            "p99_merged_ms": summary.get("p99_merged_ms"),
            "latency_source": summary.get("latency_source"),
            "signals_fired": summary.get("signals_fired"),
            "signals_fired_total": summary.get("signals_fired_total"),
            "config_source": cfg.config_source,
            "tuned_config": cfg.tuned_config,
        },
        "manifest": obs_metrics.manifest_subset(manifest),
    }
    print(json.dumps(payload))
    if summary["completed"] == 0:
        return 1
    return _maybe_regress(payload)


def main() -> int:
    # debug/CI escape hatch: BENCH_FORCE_CPU=1 runs the identical protocol
    # on a virtual 8-device CPU mesh (numbers meaningless, plumbing real)
    if os.environ.get("BENCH_FORCE_CPU") == "1":
        import jax

        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 8)

    # round 16: the serving lane's headline rides the same entry point
    # (after the FORCE_CPU escape hatch so both lanes share it)
    if os.environ.get("BENCH_WORKLOAD", "train") == "serve":
        return _serve_main()

    from tpu_hc_bench import flags
    from tpu_hc_bench.obs import metrics as obs_metrics
    from tpu_hc_bench.train import driver

    # round 14: BENCH_CONFIG=auto resolves the tuned registry row for
    # (BENCH_MODEL, live hardware) — tpu_hc_bench.tune.  The tuned
    # batch only wins when no explicit BENCH_BATCH_SIZE is set (auto
    # leaves the field at its dataclass default so resolve_auto's
    # explicit-flag-wins rule lets the row through); manual keeps the
    # headline protocol's batch 128.
    config_mode = os.environ.get("BENCH_CONFIG", "manual")
    batch_env = os.environ.get("BENCH_BATCH_SIZE")
    if batch_env is not None:
        batch_size = int(batch_env)
    elif config_mode == "auto":
        batch_size = flags.BenchmarkConfig.batch_size
    else:
        batch_size = 128

    cfg_kwargs = dict(
        # full obs artifact (metrics.jsonl + manifest.json) when asked;
        # the manifest fields below ride in the JSON line regardless
        metrics_dir=os.environ.get("BENCH_METRICS_DIR") or None,
        batch_size=batch_size,
        config=config_mode,
        model=os.environ.get("BENCH_MODEL", "resnet50"),
        use_fp16=True,          # bf16 compute: the TPU-native fast path
        num_warmup_batches=int(os.environ.get("BENCH_WARMUP", "50")),
        num_batches=int(os.environ.get("BENCH_BATCHES", "100")),
        display_every=10,
        # packed 4x4/s1 stem — same math as the 7x7/s2 conv (proven by
        # tests/test_models.py::test_space_to_depth_stem_equivalence).
        # Default OFF: the round-2 A/B measured s2d slower (BASELINE.md
        # "space_to_depth re-measured").  Models without an s2d stem are
        # rejected loudly by create_model.
        use_space_to_depth=os.environ.get("BENCH_S2D", "0") == "1",
        # round 3: Pallas fused bottleneck segment (BENCH_FUSED_CONV=1 to
        # enable; only the v1 bottleneck resnets accept it, so default off
        # keeps every BENCH_MODEL working)
        fused_conv=os.environ.get("BENCH_FUSED_CONV", "0") == "1",
        # round 6: gradient-arm A/B knobs — psum (default) | replicated |
        # zero1, the Horovod 128 MiB fusion threshold, and the
        # overlapped-vs-serialized collective schedule
        variable_update=os.environ.get("BENCH_VARIABLE_UPDATE", "psum"),
        fusion_threshold_bytes=int(os.environ.get(
            "BENCH_FUSION_THRESHOLD", "134217728")),
        overlap_grad_comm=os.environ.get("BENCH_OVERLAP", "on"),
        # round 12: elastic-resume knobs — BENCH_TRAIN_DIR checkpoints
        # the bench run (topology sidecar included), BENCH_RESUME=elastic
        # continues a prior bench run on a different world size; the
        # resume identity rides the JSON `extra` either way
        train_dir=os.environ.get("BENCH_TRAIN_DIR") or None,
        resume=os.environ.get("BENCH_RESUME", "auto"),
        # round 13: host-level shared input service A/B on real-data
        # bench runs (BENCH_DATA_DIR + BENCH_INPUT_SERVICE=on|off|auto);
        # synthetic runs resolve the flag to off with a translation note
        data_dir=os.environ.get("BENCH_DATA_DIR") or None,
        input_service=os.environ.get("BENCH_INPUT_SERVICE", "auto"),
        # round 15: pre-run AOT memory check (obs.memory) —
        # BENCH_HBM_BUDGET=16GB|auto warns loudly BEFORE the run pays
        # for the full compile when the step program cannot fit
        hbm_budget=os.environ.get("BENCH_HBM_BUDGET") or None,
    )
    cfg = flags.BenchmarkConfig(**cfg_kwargs).resolve()
    if (config_mode == "auto" and cfg.config_source == "baseline"
            and batch_env is None):
        # no tuned row for this hardware: fall back to the HEADLINE
        # protocol's batch 128, not the dataclass default 64 — a fresh
        # machine's BENCH history must stay comparable with the manual
        # runs.  Provenance stays 'baseline' and the loud note rides
        # the translation banner either way.
        note = cfg.translations.get("config")
        cfg_kwargs.update(batch_size=128, config="manual")
        cfg = flags.BenchmarkConfig(**cfg_kwargs).resolve()
        cfg.config_source = "baseline"
        if note:
            cfg.translations["config"] = note

    # human-readable progress to stderr; stdout carries only the JSON line
    result = driver.run_benchmark(
        cfg, fabric_name="ici",
        print_fn=lambda m: print(m, file=sys.stderr, flush=True),
    )
    # run-identity manifest (obs.metrics): the answer to "what exactly
    # produced this BENCH_*.json" — versions, git sha, device, world.
    # With BENCH_METRICS_DIR set the driver already wrote the manifest;
    # reuse it so the artifact and the JSON line agree on one record
    if cfg.metrics_dir:
        with open(os.path.join(cfg.metrics_dir,
                               obs_metrics.MANIFEST_NAME)) as f:
            manifest = json.load(f)
    else:
        manifest = obs_metrics.run_manifest(cfg=cfg)
    payload = {
        "metric": f"{cfg.model}_synthetic_images_per_sec_per_chip",
        "value": round(result.images_per_sec_per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(
            result.images_per_sec_per_chip / REFERENCE_NODE_IMAGES_PER_SEC, 3
        ),
        "extra": {
            "total_images_per_sec": round(result.total_images_per_sec, 2),
            "mfu": round(result.mfu, 4),
            "chips": result.total_workers,
            "global_batch": result.global_batch,
            "mean_step_ms": round(result.mean_step_ms, 3),
            "p50_step_ms": round(result.p50_step_ms, 3),
            "p50_step_granularity": result.p50_step_granularity,
            "dtype": cfg.compute_dtype,
            # gradient-arm identity: A/B runs over these knobs must
            # render as config drift, not as unexplained perf deltas
            # (obs diff reads the same fields from the manifest config)
            "variable_update": cfg.variable_update,
            "fusion_threshold_bytes": cfg.fusion_threshold_bytes,
            "overlap_grad_comm": cfg.overlap_grad_comm,
            # goodput ledger: the perf trajectory captures overlap wins
            # (compile/checkpoint blocking shrinking), not just the
            # images/sec headline (NaN-goodput runs carry null)
            "goodput": (round(result.goodput, 4)
                        if result.goodput == result.goodput else None),
            "goodput_phases": result.goodput_phases,
            # input plane: which arm ACTUALLY fed the run (the driver
            # resolves --input_service=auto, so the flag string alone
            # can't distinguish arms; true/false/null-resolved) + the
            # ledger's data_wait fraction — the input-service success
            # metric (~0 as workers-per-host scale)
            "input_service": result.input_service,
            "input_service_flag": cfg.input_service,
            "data_wait_frac": (round(result.data_wait_frac, 4)
                               if result.data_wait_frac
                               == result.data_wait_frac else None),
            # resume topology (saved world -> live world, arm): a
            # post-resume throughput shift with a world-size change is
            # a different experiment — obs diff and the BENCH history
            # must both see it as config drift, not a regression
            "resume": result.resume,
            # measured device memory (round 15, obs.memory): the run's
            # HBM high water (mem_source says allocator peak vs the
            # live-arrays fallback) and the step program's AOT
            # argument/temp/output byte account — the BENCH history
            # shows a lever change moving memory BEFORE it OOMs
            "peak_hbm_bytes": result.peak_hbm_bytes,
            "hbm_bytes_limit": result.hbm_bytes_limit,
            "mem_source": result.mem_source,
            "memory_analysis": result.memory_analysis,
            # config provenance (round 14): manual = hand-set flags,
            # auto = a tuned registry row was applied (the row rides
            # along), baseline = --config=auto found no row and fell
            # back to BASELINE defaults — the perf trajectory must
            # distinguish tuned from hand-set runs
            "config_source": cfg.config_source,
            "tuned_config": cfg.tuned_config,
        },
        "manifest": obs_metrics.manifest_subset(manifest),
    }
    print(json.dumps(payload))
    return _maybe_regress(payload)


if __name__ == "__main__":
    raise SystemExit(main())
