"""Cross-process (DCN-analog) allreduce microbench: fused vs unfused.

Round-3 (VERDICT #6): the reference's second transport stack is a real
alternative fabric (IntelMPI/libfabric, run-tf-sing-libfabric-intelmpi.sh
:86-105); the TPU counterpart is the multislice layout where the gradient
allreduce's outer phase crosses slices over DCN.  No multi-slice pod is
reachable from this box, so the honest measurable form is the same one
the multi-process tests use: 2 OS processes x N CPU devices with the
``dcn`` mesh axis ON the process boundary, sweeping message sizes through
``allreduce_gradients(fuse=True/False)`` over ``(dcn, data)``.

Numbers are host-loopback (no real NIC) — RELATIVE shape is the signal
(fusion amortizes per-collective latency on small tensors, converges on
large ones), matching the ICI microbench's table convention.

Spawns its own workers: ``python scripts/microbench_dcn.py``.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

WORKER = textwrap.dedent("""
    import sys, time
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from tpu_hc_bench.parallel import distributed
    from tpu_hc_bench.parallel.collectives import allreduce_gradients
    from tpu_hc_bench import topology

    distributed.initialize(coordinator_port=int(sys.argv[1]))
    layout = topology.discover_layout(workers_per_host=0)
    mesh = topology.build_mesh(layout, num_slices=2)
    axes = (topology.DCN_AXIS, topology.DATA_AXIS)
    ITERS = 30

    def bench(nbytes, fuse):
        n = nbytes // 4
        # 64 leaves when small enough: the fusion buffer's target case
        leaves = max(1, min(64, n // 64))
        per = n // leaves
        tree = {f"g{i}": jnp.arange(per, dtype=jnp.float32) + i
                for i in range(leaves)}

        def step(t):
            def body(_, tt):
                r = allreduce_gradients(tt, axis_name=axes, fuse=fuse)
                return jax.tree.map(lambda x: x * 0.5, r)
            return jax.lax.fori_loop(0, ITERS, body, t)

        f = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=P(),
                                  out_specs=P(), check_vma=False))
        r = f(tree)
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        r = f(tree)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / ITERS * 1e6   # us/allreduce

    if jax.process_index() == 0:
        print("# cross-process (dcn x data) allreduce, 2 procs x 2 devs, "
              "fused vs per-leaf", flush=True)
        print(f"{'bytes':>10} {'fused_us':>10} {'unfused_us':>12} "
              f"{'speedup':>8}", flush=True)
    for nbytes in (4096, 65536, 1 << 20, 8 << 20, 64 << 20):
        tf = bench(nbytes, True)
        tu = bench(nbytes, False)
        if jax.process_index() == 0:
            print(f"{nbytes:>10} {tf:>10.1f} {tu:>12.1f} {tu / tf:>8.2f}",
                  flush=True)
    print(f"DCN_BENCH_OK process={jax.process_index()}", flush=True)
""")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main() -> int:
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        hostfile = Path(td) / "nodeips.txt"
        hostfile.write_text("127.0.0.1\n127.0.0.1\n")
        script = Path(td) / "worker.py"
        script.write_text(WORKER)
        port = free_port()
        procs = []
        for pid in range(2):
            env = dict(os.environ)
            env.update({
                "TPU_HC_BENCH_HOSTFILE": str(hostfile),
                "TPU_HC_BENCH_PROCESS_ID": str(pid),
                "PYTHONPATH": f"{REPO}:{env.get('PYTHONPATH', '')}",
                "JAX_PLATFORMS": "cpu",
            })
            procs.append(subprocess.Popen(
                [sys.executable, str(script), str(port)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
        ok = True
        for i, p in enumerate(procs):
            out, _ = p.communicate(timeout=600)
            if i == 0:
                sys.stdout.write(out)
            ok = ok and p.returncode == 0 and "DCN_BENCH_OK" in out
        return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
