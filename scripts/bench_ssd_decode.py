#!/usr/bin/env python3
"""The Mamba-2 decode step alone, at the Granite serve cell's shape: the
kernel (``ops.ssd_decode_step``) against the XLA step it replaced (the
rows' inputs scattered to slot order, ``models/granite4h.ssd_step`` over
every slot of the layer, the read-out gathered back), each jitted with
the state leaf donated.

A sample is ten calls back to back on the layers 0..9 and one wait,
host-timed; the median of ``--samples`` samples / 10 is printed as ms a
layer, with the bytes a call must move (every active row's state read
and written once) over that time.  One JSON line on stdout; refuses to
time anything but a TPU unless ``--tiny`` (a CPU rehearsal of the flow:
its times mean nothing).

    python scripts/bench_ssd_decode.py [--rows 64] [--samples 20] [--tiny]

(from the repository's root).
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

sys.path.insert(0, ".")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from tpu_hc_bench.models import granite4h as gh  # noqa: E402
from tpu_hc_bench.ops import ssd_decode  # noqa: E402

# layers, slots (64 rows + the trash slot), heads, P, N of the cell
CELL = (36, 65, 64, 64, 128)
TINY = (4, 9, 4, 32, 16)
CALLS = 10


def xla_step(h, layer, slots, decay, dtx, B, C, A, dt, x):
    """The XLA decode step the kernel replaced, one Mamba-2 layer."""
    del decay, dtx

    def at(rows):
        return jnp.zeros((h.shape[1],) + rows.shape[1:],
                         rows.dtype).at[slots].set(rows)

    h_l, y = gh.ssd_step(jax.lax.dynamic_index_in_dim(h, layer, 0, False),
                         at(x), at(B), at(C), at(dt), A)
    return jax.lax.dynamic_update_index_in_dim(h, h_l, layer, 0), y[slots]


def kernel_step(h, layer, slots, decay, dtx, B, C, A, dt, x):
    del A, dt, x
    return ssd_decode.ssd_decode_step(h, layer, slots, decay, dtx, B, C)


def calls_back_to_back(step, h, slots, rest):
    """``CALLS`` calls on the layers 0..CALLS-1, the leaf handed on from
    one to the next, and one wait."""
    for li in range(CALLS):
        h, y = step(h, li, slots, *rest)
    y.block_until_ready()
    return h


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--samples", type=int, default=20)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        print(f"no TPU (backend {dev.platform}): nothing timed",
              file=sys.stderr)
        return 2
    L, S, H, P, N = TINY if args.tiny else CELL
    b = min(args.rows, S - 1)
    rng = np.random.default_rng(0)
    slots = jnp.asarray(rng.permutation(np.arange(1, S))[:b], jnp.int32)
    f32 = lambda a: jnp.asarray(a, jnp.float32)             # noqa: E731
    dt = f32(rng.uniform(1e-3, 0.1, (b, H)))
    A = -f32(rng.uniform(1.0, 16.0, H))
    x = f32(rng.standard_normal((b, H, P)))
    rest = (jnp.exp(dt * A), dt[..., None] * x,
            f32(rng.standard_normal((b, N))),
            f32(rng.standard_normal((b, N))), A, dt, x)
    need = 2 * b * H * P * N * 4
    arms = {"xla": xla_step, "kernel": kernel_step}
    out = {"device": dev.device_kind, "shape": [L, S, H, P, N], "rows": b}
    h = jnp.zeros((L, S, H, P, N), jnp.float32)
    for name, fn in arms.items():
        step = jax.jit(fn, donate_argnums=(0,))
        h = calls_back_to_back(step, h, slots, rest)    # compile, warm
        times = []
        for _ in range(args.samples):
            t0 = time.perf_counter()
            h = calls_back_to_back(step, h, slots, rest)
            times.append((time.perf_counter() - t0) / CALLS)
        ms = 1e3 * statistics.median(times)
        out[name] = {"ms_a_layer": round(ms, 4),
                     "gb_per_s": round(need / ms / 1e6, 1),
                     "min_ms": round(1e3 * min(times), 4)}
        print(f"{name}: {out[name]}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
