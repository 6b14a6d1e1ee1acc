"""Runtime observability: traces, metrics, goodput, fleet, efficiency.

The runtime counterpart of the static ``tpu_hc_bench.analysis`` package.
Where ``analysis`` inspects the *compiled program* (HLO, jaxpr),
``obs`` inspects *runs*:

- ``obs.trace`` — reusable perfetto-trace analysis: leaf-op extraction
  with the same-tid containment rule, op classification, per-step timeline
  reconstruction, and compute/collective/host-transfer/idle-bubble
  bucket attribution.
- ``obs.metrics`` — the per-run artifact: a ``metrics.jsonl`` stream of
  windowed measurements plus a ``manifest.json`` (resolved flags, mesh
  shape, world size, versions, git sha) written next to it, so every
  benchmark run leaves something machine-readable behind.
- ``obs.goodput`` — the wall-clock ledger: driver phase transitions
  (init/compile/step/data_wait/checkpoint/rewind_replay/...) folded,
  with resilience events counted as wasted work, into a goodput
  fraction and per-category breakdown.
- ``obs.fleet`` — per-host heartbeat files (``metrics.<k>.jsonl``,
  every process writes its own) and clock-free straggler skew from a
  sync-window progress allgather.
- ``obs.efficiency`` — measured MFU (``compiled.cost_analysis()`` of
  the actual step program, source-labeled against the analytic table)
  and achieved-collective-bandwidth attribution against a measured
  fabric ceiling (``microbench.osu --json`` sweeps).
- ``obs.memory`` — measured device memory: the AOT
  ``compiled.memory_analysis()`` report cross-checked against an
  analytic params+opt+batch table, a per-sync-window HBM ledger whose
  high-water mark is attributed to the goodput phase that set it, OOM/
  emergency forensics (``memory_dump.json``), and the ``--hbm_budget``
  pre-run check.
- ``obs.timeline`` — the always-on host flight recorder: a bounded
  preallocated span ring every lane records into (train driver, data
  service, serve engine, checkpoint), persisted per rank as
  ``spans.<k>.jsonl``, merged cross-rank (heartbeat clock alignment)
  into Chrome-trace JSON, and dumped as ``timeline_dump.json`` by the
  watchdog/OOM/preemption paths — the time forensics twin of
  ``memory_dump.json``.
- ``obs.regress`` — the noise-aware regression gate: a fresh BENCH
  record vs the median/MAD of the matching-config-fingerprint history,
  direction-aware per metric (throughput down, p99/HBM up).
- ``obs.requests`` — the per-request lifecycle ledger (serving lane):
  every request's e2e decomposed into conserved components
  (queue_wait / prefill / decode_active / decode_stall /
  retire_overhead) stamped by the engine, the slowest-decile tail
  attribution (``summarize`` names where the p99 lives, ``diff``
  renders component deltas, ``regress`` gates on attribution shift),
  per-bucket occupancy folds, and per-request Chrome-trace lanes
  merged into the ``timeline`` view.
- ``obs.kv`` — the KV-pool utilization ledger (serving lane):
  ``kv_pool_util`` (written-page-seconds / reserved-page-seconds) from
  the engine's periodic pool snapshots, the per-request reservation
  honesty gap (``pages_reserved`` vs ``pages_final`` at retirement),
  the r20 ``queue_wait`` component's cause split (``pool_starved`` vs
  ``batch_full`` — WHICH resource gated the tail), and the pool
  occupancy counter track merged into the ``timeline`` view.
- ``python -m tpu_hc_bench.obs`` — ``summarize`` renders either
  artifact kind (a metrics run or a raw trace directory); ``diff``
  compares two runs at bucket/metric granularity, so a regression
  reads "collective +40%, compute flat" instead of a single throughput
  delta; ``watch`` tails a live run in place and exits when it
  completes; ``timeline`` writes the merged cross-rank Chrome trace;
  ``regress`` runs the history gate (exit 1 on a real regression).
"""

from tpu_hc_bench.obs import metrics, trace  # noqa: F401
