#!/usr/bin/env python3
"""The readings a limit is set from, and the control held to that limit,
on the chip at the cell's own size, one process for all seeds.  For each
seed: the numbers the program reads against the reference (the lower
readings) and, through the same ``checks.verdict`` and the cell's own
limits as a run of the benchmark, ``correct``; then what the control
reads — the reference put in the program's place in the arm's
``control`` precision — and, for a train cell, each planted fault of the
arm's ``faults`` (the upper readings), each with its own ``correct``,
which has to come out false.

    python3 benchmarks/tools/readings.py --workload <cell> \
        --seeds 11,12,13 --seconds 51 [--also fp8]
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]


def held(numbers: dict) -> dict:
    """One side's numbers beside their limits, and the verdict."""
    from harness import checks

    return {"correct": checks.verdict(numbers),
            "compared": checks.as_json(numbers)}


ALSO_SEEDS = 3      # how many of the seeds read the further controls


def serve(cell, cfg, mix, seeds, seconds, work, also) -> None:
    from harness import adapters, checks, serve_lane, traffic

    arm = cfg["serve_arm"]
    gen = traffic.generator_of(mix)
    vocab = adapters.family_of(cfg).vocab_size(cfg)
    journal = os.path.join(work, "journal.json")
    engine = serve_lane.build_engine(cfg, mix, seeds[0], journal, print)
    close = seconds if mix.get("close_window_at_seconds") else None
    for i, seed in enumerate(seeds):
        serve_lane.load_weights(engine, cfg, seed)
        if i == 0:
            serve_lane.warm_up(engine, cfg, mix, seed)
        reqs = gen.requests(mix, seconds, seed, vocab)
        tap = serve_lane.LogitTap(engine, seed, seconds)
        records, others, _, wall = serve_lane.run_window(
            engine, reqs, seconds, close, tap=tap)
        sample = checks.sample_with_rows(
            records, {r["rid"]: r for r in reqs}, tap.fetch())
        controls = [arm["control"]] + (also if i < ALSO_SEEDS else [])
        t0 = time.monotonic()
        st = checks.serve_stats(
            cfg, seed, sample, engine.max_ctx, mix["output_len"]["max"],
            arm["reference_precision"], controls=controls)
        row = {"reading": cell["name"], "seed": seed,
               "finished": len(records), "sampled": len(sample),
               "reference": arm["reference_precision"],
               "reference_s": time.monotonic() - t0,
               "program": held(checks.serve_numbers_from(
                   cfg, st["program"]))}
        for c in controls:
            row["control_" + c] = held(checks.serve_numbers_from(cfg, st[c]))
        row["stats"] = st
        print(json.dumps(row), flush=True)
        del tap, sample
        gc.collect()


def train(cell, cfg, mix, seeds, seconds, work, control_seeds: int) -> None:
    from harness import checks, device, train_lane

    arm = cfg["train_arm"]
    dev = device.require_tpu(cell["chips"])
    for i, seed in enumerate(seeds):
        args = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
        out = train_lane.run_cell(cell, cfg, mix, args, time.monotonic(),
                                  dev, work)
        row = {"reading": cell["name"], "seed": seed,
               "program": held(out["numbers"])}
        if i < control_seeds:
            sides = [("control_" + arm["control"],
                      {"precision": arm["control"]})] + [
                ("fault_" + f, {"fault": f}) for f in arm["faults"]]
            for name, kw in sides:
                got = checks.reference_train(cfg, seed, out["batch"],
                                             out["lr"], **kw)
                row[name] = held(checks.train_numbers_from(
                    cfg, got, out["reference"]))
        print(json.dumps(row), flush=True)
        del out
        gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--control_seeds", type=int, default=3,
                    help="train: how many of the seeds also read the "
                         "control and the faults")
    ap.add_argument("--also", default="",
                    help="serve: further controls the first seeds read, e.g. fp8")
    args = ap.parse_args()

    from harness import device, spec, traffic
    from tpu_hc_bench.utils import compile_cache

    bench = spec.load_benchmark()
    cell = spec.cell_of(bench, args.workload)
    cfg = spec.config_of(bench, cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    device.require_tpu(cell["chips"])
    compile_cache.resolve(None)
    work = os.path.join(spec.ROOT, ".bench_work")
    os.makedirs(work, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    if mix["lane"] == "serve":
        serve(cell, cfg, mix, seeds, args.seconds, work,
              [c for c in args.also.split(",") if c])
    else:
        train(cell, cfg, mix, seeds, args.seconds, work, args.control_seeds)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
