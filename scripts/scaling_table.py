#!/usr/bin/env python
"""The multi-node scaling-efficiency table, through the literal CLI.

The reference's headline deliverable is the 1/2/4-node sweep with the
fabric flip — `./run-tf-sing-ucx-openmpi.sh N 1 64 ib|sock` for N in
{1,2,4} (`/root/reference/README.md:68-73`, launch at
`run-tf-sing-ucx-openmpi.sh:85-95,99-109`).  This harness produces its
analog on the virtual CPU mesh: for each world size it spawns WORLD real
OS processes, each running the literal 4-positional CLI

    python -m tpu_hc_bench WORLD 0 BATCH FABRIC --model=... \
        --virtual_devices=(TOTAL_DEVICES/WORLD)

joined through the nodeips.txt hostfile contract + jax.distributed (the
proven tests/test_multiprocess.py launch pattern), full 50+100 protocol,
and parses each rank-0 "total images/sec" line into one table.

Design note — why the TOTAL device count stays fixed while the world
grows: on real hardware the reference grows the fleet (more nodes = more
compute) and efficiency is total(N)/(N*total(1)).  On this one-box CPU
mesh, growing the device count would just oversubscribe the same vCPUs
and measure host contention.  Holding total devices at 8 and splitting
them over 1/2/4 processes keeps the device work constant so the measured
ratio total(world=N)/total(world=1) isolates exactly what the reference's
fabric flip probes: the cost of gradient reduction crossing process
boundaries (ici-analog = compiled XLA collectives over the distributed
backend; host = the sock-analog bounce through host memory + a
process_allgather hop).  Numbers are RELATIVE, clearly CPU-mesh, and
recorded as such in BASELINE.md.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import socket
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_config(world: int, fabric: str, model: str, batch: int,
               total_devices: int, warmup: int, batches: int,
               workdir: Path, timeout: int = 2400,
               metrics_dir: Path | None = None) -> dict:
    """One table cell: WORLD processes through the literal CLI."""
    devices_per = total_devices // world
    assert devices_per * world == total_devices
    cmd = [sys.executable, "-m", "tpu_hc_bench",
           str(world), "0", str(batch), fabric,
           f"--model={model}", f"--num_warmup_batches={warmup}",
           f"--num_batches={batches}", f"--virtual_devices={devices_per}"]
    if metrics_dir is not None:
        # per-cell obs artifact: rank 0 writes metrics.jsonl + manifest
        # there, so each world size leaves a diffable record
        # (python -m tpu_hc_bench.obs diff <cell_a> <cell_b>)
        cmd.append(f"--metrics_dir={metrics_dir}")
    hostfile = workdir / f"nodeips_{world}.txt"
    hostfile.write_text("127.0.0.1\n" * world)
    port = free_port()
    procs = []
    for pid in range(world):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": f"{REPO}:{env.get('PYTHONPATH', '')}",
        })
        # share the suite's warm XLA executable cache, placed the way a
        # user would place it (and out of the checkout)
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       "/tmp/tpu_hc_bench_jax_cache")
        if world > 1:
            env.update({
                "TPU_HC_BENCH_HOSTFILE": str(hostfile),
                "TPU_HC_BENCH_PROCESS_ID": str(pid),
                "TPU_HC_BENCH_COORDINATOR_PORT": str(port),
            })
        procs.append(subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs[len(outs):]:
            out, _ = p.communicate()
            outs.append(out)
        raise RuntimeError(
            f"config world={world} {fabric} {model} timed out:\n"
            + "\n---\n".join(outs))
    for i, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            raise RuntimeError(
                f"rank {i} failed (world={world} {fabric} {model}):\n{out}")
    rank0 = outs[0]
    m = re.search(r"total (?:images|examples)/sec: ([\d.]+)", rank0)
    s = re.search(r"step: ([\d.]+)ms", rank0)
    if not m:
        raise RuntimeError(f"no throughput line in rank-0 output:\n{rank0}")
    return {
        "world": world, "fabric": fabric, "model": model,
        "batch_per_worker": batch, "total_devices": total_devices,
        "warmup": warmup, "batches": batches,
        "total_ex_per_sec": float(m.group(1)),
        "mean_step_ms": float(s.group(1)) if s else None,
        "metrics_dir": str(metrics_dir) if metrics_dir else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worlds", default="1,2,4")
    ap.add_argument("--fabrics", default="ici,host")
    ap.add_argument("--models", default="resnet20_cifar,bert_tiny")
    ap.add_argument("--batch", type=int, default=2,
                    help="per-worker batch (reference semantics)")
    ap.add_argument("--total_devices", type=int, default=8)
    ap.add_argument("--warmup", type=int, default=50)
    ap.add_argument("--batches", type=int, default=100)
    ap.add_argument("--out", default="artifacts/scaling_r04")
    ap.add_argument("--timeout", type=int, default=2400)
    ap.add_argument("--no-metrics", dest="metrics", action="store_false",
                    default=True,
                    help="skip the per-cell obs.metrics artifacts")
    args = ap.parse_args(argv)

    worlds = [int(w) for w in args.worlds.split(",")]
    fabrics = args.fabrics.split(",")
    models = args.models.split(",")
    out_dir = REPO / args.out
    out_dir.mkdir(parents=True, exist_ok=True)
    jsonl = out_dir / "scaling.jsonl"

    rows = []
    with jsonl.open("a") as f:
        for model in models:
            for fabric in fabrics:
                for world in worlds:
                    t0 = time.time()
                    cell_metrics = (
                        out_dir / "obs" / f"w{world}_{fabric}_{model}"
                        if args.metrics else None)
                    row = run_config(world, fabric, model, args.batch,
                                     args.total_devices, args.warmup,
                                     args.batches, out_dir,
                                     timeout=args.timeout,
                                     metrics_dir=cell_metrics)
                    row["wall_s"] = round(time.time() - t0, 1)
                    rows.append(row)
                    f.write(json.dumps(row) + "\n")
                    f.flush()
                    print(f"done: world={world} {fabric} {model}: "
                          f"{row['total_ex_per_sec']:.1f} ex/s "
                          f"({row['wall_s']}s wall)", flush=True)

    # markdown table with efficiency vs the world-1 row of the same
    # (model, fabric) — the reference's scaling-efficiency metric reshaped
    # for the fixed-total-device design (see module docstring)
    lines = [
        "| model | fabric | world | total ex/s | step ms | eff vs world-1 |",
        "|---|---|---|---|---|---|",
    ]
    base = {(r["model"], r["fabric"]): r["total_ex_per_sec"]
            for r in rows if r["world"] == 1}
    for r in rows:
        b = base.get((r["model"], r["fabric"]))
        eff = f"{r['total_ex_per_sec'] / b:.3f}" if b else "—"
        lines.append(
            f"| {r['model']} | {r['fabric']} | {r['world']} "
            f"| {r['total_ex_per_sec']:.1f} | {r['mean_step_ms']:.1f} "
            f"| {eff} |")
    table = "\n".join(lines)
    (out_dir / "scaling.md").write_text(table + "\n")
    print(table)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
