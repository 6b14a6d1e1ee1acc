"""``python -m tpu_hc_bench serve`` — the serving-lane entry point.

Same flag grammar as the training launcher (everything parses through
``flags.build_parser``; resolve() runs the serving validity matrix and
rejects training-only knobs loudly), same observability contract
(``--metrics_dir`` leaves manifest.json + metrics.jsonl and the banner
prints the summarize command), same exit codes where they apply:

- ``0``  clean (every request completed, shed, or quarantined)
- ``1``  run completed but zero requests finished
- ``70`` scheduler-iteration watchdog fired (``--serve_step_timeout_s``)
- ``75`` SIGTERM/Ctrl-C honored: the engine drained, journaled every
  unfinished request, and ``--serve_resume=<journal>`` replays them
  exactly once

On every exit path — including Ctrl-C — the metrics stream and the
FleetWriter are flushed and closed, so the tail of an interrupted run
is still on disk for ``obs summarize``.

Example::

    JAX_PLATFORMS=cpu python -m tpu_hc_bench serve --model moe_tiny \
        --arrival_rate 8 --num_requests 64 --max_prompt_len 32 \
        --max_output_len 16 --metrics_dir /tmp/serve_run
"""

from __future__ import annotations

import sys
from typing import Callable

from tpu_hc_bench import flags as flags_mod


def build_engine_and_requests(cfg, print_fn):
    """The one engine/trace handshake every serve entry point shares
    (CLI, ``BENCH_WORKLOAD=serve``, scripts/bench_serve.py): construct
    the warmed engine, then the arrival trace — classify members carry
    no vocabulary, so the sampler runs promptless for them."""
    from tpu_hc_bench.serve import arrivals
    from tpu_hc_bench.serve.engine import ServeEngine

    engine = ServeEngine(cfg, print_fn=print_fn)
    vocab = engine.spec.vocab_size if engine.decode_mode else None
    return engine, arrivals.build_requests(cfg, vocab)


def serve_writer(cfg, metrics_dir):
    """A MetricsWriter stamped with the serve-lane manifest, or a
    disabled writer when ``metrics_dir`` is falsy."""
    from tpu_hc_bench.obs import metrics as obs_metrics

    return obs_metrics.MetricsWriter(
        metrics_dir,
        obs_metrics.run_manifest(cfg=cfg, extra={"workload": "serve"})
        if metrics_dir else None)


def run_serve(engine, requests, writer, *, batching=None, clock=None):
    """One closed loop with the writer(s) closed on every exit path.

    A metrics-enabled run also gets a FleetWriter beside the metrics
    stream (round 22): the engine heartbeats at serve-record cadence
    carrying ``kv_peak_pages``, so ``obs watch``'s fleet view shows
    per-host KV pressure.  process_index is pinned to 0 — the serve
    lane is single-process today and the FleetWriter default would
    touch ``jax.process_index()`` (a device round-trip) from the hot
    path's setup."""
    fleet = None
    out_dir = getattr(writer, "out_dir", None)
    if out_dir:
        from tpu_hc_bench.obs import fleet as fleet_mod

        fleet = fleet_mod.FleetWriter(out_dir, process_index=0)
    try:
        return engine.run(requests, batching=batching, writer=writer,
                          clock=clock, fleet=fleet)
    finally:
        writer.close()
        if fleet is not None:
            fleet.close()


def main(argv: list[str] | None = None,
         print_fn: Callable[[str], None] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    print_fn = print_fn or (lambda m: print(m, flush=True))
    cfg = flags_mod.parse_flags(argv, workload="serve")

    if cfg.virtual_devices:
        import jax

        jax.config.update("jax_num_cpu_devices", cfg.virtual_devices)

    from tpu_hc_bench.obs import metrics as obs_metrics
    from tpu_hc_bench.serve import slo as slo_mod

    print_fn(f"command: python -m tpu_hc_bench serve {' '.join(argv)}")
    for line in cfg.summary_lines():
        print_fn(line)

    engine, requests = build_engine_and_requests(cfg, print_fn)
    if cfg.serve_resume:
        # drain-journal replay: serve every unfinished request of the
        # SIGTERM'd run exactly once (the journal is the trace)
        from tpu_hc_bench.serve import faults as faults_mod

        payload = faults_mod.read_journal(cfg.serve_resume)
        requests = faults_mod.journal_requests(payload)
        print_fn(f"resume: {len(requests)} unfinished request(s) from "
                 f"{cfg.serve_resume} (reason={payload.get('reason')})")
    writer = serve_writer(cfg, cfg.metrics_dir)
    if writer.enabled:
        print_fn(f"metrics: {cfg.metrics_dir}/{obs_metrics.METRICS_NAME} "
                 f"(+ {obs_metrics.MANIFEST_NAME}); live view: "
                 f"python -m tpu_hc_bench.obs watch {cfg.metrics_dir}")
    try:
        summary = run_serve(engine, requests, writer)
    except KeyboardInterrupt:
        # the engine's own handler converts SIGINT into a drain while
        # run() is live; this catches a Ctrl-C outside that window —
        # run_serve's finally already flushed and closed the streams
        print_fn("interrupted — metrics stream closed")
        return 130
    for line in slo_mod.slo_lines(summary):
        print_fn(line)
    if cfg.metrics_dir:
        print_fn("summarize: python -m tpu_hc_bench.obs summarize "
                 + cfg.metrics_dir)
    if summary.get("drained"):
        from tpu_hc_bench import resilience

        return resilience.EXIT_PREEMPTED
    return 0 if summary["completed"] > 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
