#!/usr/bin/env python3
"""Runs one cell of ``BENCHMARK.json`` once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that refuses to run without a TPU (exit 2, no result line),
keeps JAX's compile cache inside the checkout, builds weights and inputs
from the seed, warms only the cell's own shapes (set-up), measures for
``--seconds``, holds what the timed path produced against the plain
reference, prints each number compared beside its limit and, as the last
line of standard output, one JSON object.

``--rehearse`` is the CPU rehearsal: every step of the run at a tiny
size with ``JAX_PLATFORMS=cpu``; it prints counts (requests, tokens,
steps, compiles) and nothing under the name of a device metric.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, ROOT]

from harness import device  # noqa: E402

T_PROC = device.process_start_monotonic()
WORKDIR = os.path.join(ROOT, ".bench_work")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "tpu_hc_bench")):
        print("benchmark: the system under test (tpu_hc_bench/) is not in "
              "this checkout", file=sys.stderr)
        return 3

    from harness import checks, rehearse, spec, traffic

    bench = spec.load_benchmark()
    cell = spec.cell_of(bench, args.workload)
    cfg = spec.config_of(bench, cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    if args.rehearse:
        return rehearse.run(cell, cfg, mix, args, WORKDIR)

    dev = device.require_tpu(cell["chips"])
    from tpu_hc_bench.utils import compile_cache

    cache = compile_cache.resolve(None)
    print(f"[bench] {cell['name']} seed {args.seed} seconds {args.seconds} "
          f"trace {args.trace}; device {dev}; compile cache {cache}",
          flush=True)
    os.makedirs(WORKDIR, exist_ok=True)
    lane = importlib.import_module("harness." + mix["lane"] + "_lane")
    out = lane.run_cell(cell, cfg, mix, args, T_PROC, dev, WORKDIR)

    ctx = out["ctx"]
    group = "per_layer" if args.trace else "end_to_end"
    if not args.trace:
        # every run also prints the metrics a later issue may promote
        for name, value in sorted(out.get("also", {}).items()):
            print(f"[bench] also {name} = {value!r}", flush=True)
    metrics = spec.read_metrics(
        spec.metrics_for(bench, group, cell["name"]), ctx)
    correct = checks.verdict(out["numbers"])
    dev_rec = dict(dev, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": dev_rec}
    if args.trace:
        tr = ctx.get("trace")
        if tr is None:
            print("benchmark: the traced run produced no trace",
                  file=sys.stderr)
            return 4
        from harness import xplane

        dev_rec["busy_s"] = tr["busy_s"]
        dev_rec["window_s"] = tr["window_s"]
        result["breakdown"] = {
            "device_ops": xplane.top_ops(tr["ops"]),
            "idle_gaps": tr["idle_gaps"]}
        print(f"[bench] trace of {len(tr['gaps'])} idle gaps: closing the "
              f"profiler took {tr['stop_s']:.1f} s, reading and reducing "
              f"the trace {tr['reduce_s']:.1f} s", flush=True)
    result["also"] = out.get("also", {})
    if args.trace:
        # what the trace cost this run: both, since the stop is the larger
        result["also"]["trace_stop_s"] = tr["stop_s"]
        result["also"]["trace_reduce_s"] = tr["reduce_s"]
    result["wall_s"] = time.monotonic() - T_PROC
    result["compared"] = checks.as_json(out["numbers"])
    sys.stdout.flush()
    for line in checks.report_lines(out["numbers"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
