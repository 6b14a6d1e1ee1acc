#!/usr/bin/env python3
"""One seed's readings of a serve cell whose engine and reference do not
fit the chip together (``tools/readings.py`` keeps one warmed engine
beside the reference for all its seeds): the cell's own run — set-up,
window, the engine freed, then the reference — with the arm's ``control``
read on the same rows and tokens, each side through ``checks.verdict``
and the cell's own limits.  One process a seed.

    python3 benchmarks/tools/readings_run.py --workload <cell> --seed 11 --seconds 20
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    args.trace = 0

    from harness import checks, device, serve_lane, spec, traffic
    from tpu_hc_bench.utils import compile_cache

    bench = spec.load_benchmark()
    cell = spec.cell_of(bench, args.workload)
    cfg = spec.config_of(bench, cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    dev = device.require_tpu(cell["chips"])
    compile_cache.resolve(None)
    work = os.path.join(spec.ROOT, ".bench_work")
    os.makedirs(work, exist_ok=True)
    arm = cfg["serve_arm"]
    row = {"reading": cell["name"], "seed": args.seed}

    def with_control(cfg, seed, sample, max_ctx, max_out):
        """In the place of ``checks.serve_numbers``: the same numbers,
        and the control's beside them."""
        st = checks.serve_stats(cfg, seed, sample, max_ctx, max_out,
                                arm["reference_precision"],
                                controls=[arm["control"]])
        for side, key in (("program", "program"),
                          ("control_" + arm["control"], arm["control"])):
            numbers = checks.serve_numbers_from(cfg, st[key])
            row[side] = {"correct": checks.verdict(numbers),
                         "compared": checks.as_json(numbers)}
        row["stats"] = st
        return checks.serve_numbers_from(cfg, st["program"])

    checks.serve_numbers = with_control
    out = serve_lane.run_cell(cell, cfg, mix, args, time.monotonic(), dev,
                              work)
    row["finished"] = len(out["ctx"]["records"])
    print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
