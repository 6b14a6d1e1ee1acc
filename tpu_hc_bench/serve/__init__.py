"""Request-driven serving benchmark — the inference workload lane.

The reference harness (and every round of this repo before 16) is a
*training* workload driver; the north star — "serve heavy traffic from
millions of users" — names the scenario it could not exercise at all:
inference under load.  This package closes that gap with a miniature of
the two techniques the related work canonized:

- **Continuous batching** (Orca): requests are admitted into and
  retired from the running decode batch *per decode step*, instead of
  batches running to completion while arrivals queue
  (``serve.loop``, over ``serve.engine``'s programs;
  ``--batching=static`` keeps the classic arm as the A/B control).
- **Paged KV cache** (vLLM): decode members allocate KV cache in fixed
  pages from a shared pool, so memory scales with tokens actually held
  rather than worst-case sequence slabs (``serve.decode``'s programs,
  ``serve.cache``'s allocation).

Everything runs over a small ladder of AOT-compiled ``(batch, seqlen)``
bucket shapes, warmed at startup through the shared persistent compile
cache and the ``obs.efficiency`` lowering path — after
warmup the engine only ever calls AOT executables, so a mid-traffic
recompile is structurally impossible (an off-ladder shape raises).
SLO reporting (p50/p95/p99 TTFT + end-to-end, queue depth, tokens/s,
goodput-under-load) rides the existing ``obs.metrics`` stream as
``request``/``serve`` records, so ``obs summarize|diff|watch`` render
serving runs with no new artifact format (``serve.slo``).

Entry point: ``python -m tpu_hc_bench serve --model moe_tiny
--arrival_rate 8 --num_requests 64 --metrics_dir /runs/serve``.

This module is import-light on purpose: ``serve.slo`` is pure record
processing (the obs CLI must keep working without a jax backend), and
the engine/decoder only import jax when constructed.
"""
