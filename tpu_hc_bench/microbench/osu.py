"""OSU-equivalent collective latency/bandwidth sweeps over the device mesh.

Protocol follows OSU's shape: for each message size (powers of two over a
configurable range), run ``warmup`` untimed iterations then ``iters`` timed
iterations, report mean time per op and derived bandwidth.  Iterations are
chained *inside* one compiled computation (``lax.fori_loop`` with a data
dependency between steps) so Python dispatch overhead is excluded — the TPU
counterpart of OSU's tight C loop around ``MPI_Allreduce``.

Bandwidth columns:
- ``algbw``  = message_bytes / time — what the caller observes.
- ``busbw``  = algbw * 2*(n-1)/n for allreduce (ring traffic factor),
  algbw * (n-1)/n for all_gather / reduce_scatter, algbw for ppermute —
  the fabric-utilization number comparable across world sizes (same
  convention as nccl-tests / OSU derived metrics).

Usage (the reference runs OSU via ``mpirun … singularity exec`` by hand,
SURVEY.md §3.5; here it is a first-class CLI)::

    python -m tpu_hc_bench.microbench.osu --op allreduce --max_bytes 16777216
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_hc_bench.topology import DATA_AXIS, discover_layout, build_mesh
from tpu_hc_bench.utils.sync import drain


@dataclasses.dataclass(frozen=True)
class SweepResult:
    op: str
    world_size: int
    message_bytes: int
    mean_us: float
    algbw_gbps: float   # GB/s (1e9 bytes)
    busbw_gbps: float


def _busbw_factor(op: str, n: int) -> float:
    if n <= 1:
        return 1.0
    if op == "allreduce":
        return 2.0 * (n - 1) / n
    if op in ("all_gather", "reduce_scatter", "all_to_all"):
        return (n - 1) / n
    return 1.0  # ppermute: each link carries the full message once


def _collective(op: str, axis: str) -> Callable[[jax.Array], jax.Array]:
    if op == "allreduce":
        # divide by world size so chained iterations stay finite; pcast
        # re-marks the (now replicated) result as axis-varying so it can
        # feed the next loop iteration's carry under shard_map
        return lambda x: jax.lax.pcast(
            jax.lax.psum(x, axis) / jax.lax.axis_size(axis), axis, to="varying"
        )
    if op == "all_gather":
        # gather then take own shard back so shape is loop-invariant
        def f(x):
            g = jax.lax.all_gather(x, axis, axis=0, tiled=True)
            n = jax.lax.axis_size(axis)
            i = jax.lax.axis_index(axis)
            return jax.lax.dynamic_slice_in_dim(g, i * x.shape[0], x.shape[0], 0)
        return f
    if op == "reduce_scatter":
        def f(x):
            s = jax.lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
            return jnp.tile(s / jax.lax.axis_size(axis), jax.lax.axis_size(axis))
        return f
    if op == "ppermute":
        def f(x):
            n = jax.lax.axis_size(axis)
            perm = [(i, (i + 1) % n) for i in range(n)]
            return jax.lax.ppermute(x, axis, perm)
        return f
    if op == "all_to_all":
        # osu_alltoall analog — the building block of expert/sequence
        # parallelism layouts; shape-preserving tiled exchange
        return lambda x: jax.lax.all_to_all(
            x, axis, split_axis=0, concat_axis=0, tiled=True
        )
    raise ValueError(f"unknown op {op!r}")


OSU_OPS = ("allreduce", "all_gather", "reduce_scatter", "ppermute",
           "all_to_all")


def _build_timed_fn(mesh: Mesh, op: str, iters: int):
    """Jitted fn running `iters` chained collectives on a per-device shard."""
    coll = _collective(op, DATA_AXIS)

    def body(x):
        # each iteration consumes the previous result, so the chain of
        # collectives cannot be CSE'd or reordered by XLA
        return jax.lax.fori_loop(0, iters, lambda _, c: coll(c), x)

    shard = jax.shard_map(
        body, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS)
    )
    return jax.jit(shard)


def run_sweep(
    op: str = "allreduce",
    min_bytes: int = 1024,
    max_bytes: int = 64 * 1024 * 1024,
    warmup: int = 5,
    iters: int = 20,
    mesh: Mesh | None = None,
    dtype=jnp.float32,
) -> list[SweepResult]:
    """Sweep one collective over message sizes; returns per-size results.

    ``message_bytes`` is the per-device payload handed to the collective
    (matching OSU, where -m sets the per-rank message size).
    """
    if mesh is None:
        mesh = build_mesh(discover_layout())
    n = mesh.devices.size
    itemsize = jnp.dtype(dtype).itemsize
    results = []
    size = min_bytes
    while size <= max_bytes:
        elems_per_dev = max(1, size // itemsize)
        fn = _build_timed_fn(mesh, op, iters)
        sharding = NamedSharding(mesh, P(DATA_AXIS))
        x = jax.device_put(
            jnp.ones((elems_per_dev * n,), dtype), sharding
        )
        # warmup (includes compile)
        w = _build_timed_fn(mesh, op, warmup)
        drain(w(x))
        drain(fn(x))  # compile the timed fn
        t0 = time.perf_counter()
        drain(fn(x))
        dt = time.perf_counter() - t0
        per_op = dt / iters
        msg_bytes = elems_per_dev * itemsize
        algbw = msg_bytes / per_op / 1e9 if per_op > 0 else float("inf")
        results.append(
            SweepResult(
                op=op,
                world_size=n,
                message_bytes=msg_bytes,
                mean_us=per_op * 1e6,
                algbw_gbps=algbw,
                busbw_gbps=algbw * _busbw_factor(op, n),
            )
        )
        size *= 2
    return results


def format_table(results: list[SweepResult]) -> str:
    """OSU-style output table."""
    if not results:
        return "(no results)"
    r0 = results[0]
    lines = [
        f"# TPU ICI micro-benchmark: {r0.op} "
        f"(world={r0.world_size}, OSU-equivalent)",
        f"# {'bytes':>12} {'latency_us':>12} {'algbw_GB/s':>12} {'busbw_GB/s':>12}",
    ]
    for r in results:
        lines.append(
            f"  {r.message_bytes:>12} {r.mean_us:>12.2f} "
            f"{r.algbw_gbps:>12.3f} {r.busbw_gbps:>12.3f}"
        )
    return "\n".join(lines)


def sweep_json(results_by_op: dict[str, list[SweepResult]]) -> dict:
    """The JSON export schema ``obs.efficiency.load_fabric_ceiling``
    consumes: one sweep-row list per op plus the fabric identity the
    ceiling is only valid for (world size, device kind)."""
    from tpu_hc_bench.utils import hw

    world = next(
        (rs[0].world_size for rs in results_by_op.values() if rs), 0)
    try:
        kind = hw.device_kind()
    except Exception:
        kind = "unknown"
    return {
        "schema": 1,
        "created_unix": time.time(),
        "world_size": world,
        "device_kind": kind,
        "sweeps": {
            op: [dataclasses.asdict(r) for r in rows]
            for op, rows in results_by_op.items()
        },
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--op", choices=list(OSU_OPS) + ["all"], default="allreduce")
    p.add_argument("--min_bytes", type=int, default=1024)
    p.add_argument("--max_bytes", type=int, default=64 * 1024 * 1024)
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--json", metavar="PATH", default=None,
                   help="save the sweep as a fabric-ceiling file for "
                        "--fabric_ceiling / obs summarize")
    args = p.parse_args(argv)
    ops = OSU_OPS if args.op == "all" else [args.op]
    by_op: dict[str, list[SweepResult]] = {}
    for op in ops:
        res = run_sweep(
            op=op, min_bytes=args.min_bytes, max_bytes=args.max_bytes,
            warmup=args.warmup, iters=args.iters,
        )
        by_op[op] = res
        print(format_table(res))
    if args.json:
        import json

        with open(args.json, "w") as f:
            json.dump(sweep_json(by_op), f, indent=2)
            f.write("\n")
        print(f"# sweep saved: {args.json} (pass as --fabric_ceiling)")


if __name__ == "__main__":
    main()
