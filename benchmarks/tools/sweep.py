#!/usr/bin/env python3
"""Finds the knee of a serve mix once, on the chip: one warmed engine,
one window per rate, and for each rate whether a backlog grew through the
window.  The rate chosen (0.8 of the knee) then becomes a number in the
traffic file; the benchmark never searches.  A queue GROWS at a rate
(``grows``) where the last third's median queue wait is over 1.5x the
first third's and above one step of the lane's largest decode bucket, or
where a request queued for over a second; the knee is the lowest rate at
which any seed's does.

    python3 benchmarks/tools/sweep.py --workload <serve cell> \
        --rates 8,10,12,14,16,18,20,24 --seconds 30 --seed 7
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()

    from harness import (adapters, device, readers, serve_lane, spec, stats,
                         traffic)

    bench = spec.load_benchmark()
    cell = spec.cell_of(bench, args.workload)
    cfg = spec.config_of(bench, cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    device.require_tpu(cell["chips"])
    work = os.path.join(spec.ROOT, ".bench_work")
    os.makedirs(work, exist_ok=True)
    engine = serve_lane.build_engine(cfg, mix, args.seed,
                                     os.path.join(work, "journal.json"), print)
    serve_lane.load_weights(engine, cfg, args.seed)
    serve_lane.warm_up(engine, cfg, mix, args.seed)
    gen = traffic.generator_of(mix)
    vocab = adapters.family_of(cfg).vocab_size(cfg)
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, requests_per_s=rate, close_window_at_seconds=False)
        reqs = gen.requests(m, args.seconds, args.seed, vocab)
        records, others, summary, wall = serve_lane.run_window(
            engine, reqs, args.seconds, None)
        recs = records
        first, last = readers.queue_p50_by_thirds(recs)
        util = summary["bucket_util"]
        top = util[max((k for k in util if k.startswith("decode@")),
                       key=lambda k: int(k.split("@")[1]))]
        top_step_ms = 1e3 * top["wall_s"] / top["steps"]
        queue_max = max(r["queue_ms"] for r in recs)
        print(json.dumps({
            "rate": rate, "offered": len(reqs), "finished": len(records),
            "wall_s": wall, "drain_s": wall - reqs[-1]["arrival_s"],
            "queue_p50_ms_first_third": first,
            "queue_p50_ms_last_third": last,
            "queue_max_ms": queue_max,
            "top_bucket_step_ms": top_step_ms,
            "grows": bool((last > 1.5 * first and last > top_step_ms)
                          or queue_max > 1e3),
            "ttft_p90_ms": stats.percentile([r["ttft_ms"] for r in recs], 90),
            "tpot_p90_ms": stats.percentile(readers.tpot_values(recs), 90),
            "tokens_per_s": sum(r["output_len"] for r in recs) / wall,
            "occupancy": readers.batch_occupancy({"summary": summary}),
            "decode_step_ms": readers.decode_step_wall_ms(
                {"summary": summary}),
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
