#!/usr/bin/env python3
"""The gated delta-rule ("KDA") decode step alone, at the Solar serve
cell's shape: the kernel (``ops.kda_decode_step``) against the XLA step
it replaced (the rows' inputs scattered to slot order,
``models/solar_open2.kda_step`` over every slot of the layer, the
read-out gathered back), each jitted with the state leaf donated.

Both arms first step the same leaf once and their results are compared
(over the active rows, the largest difference of ``o`` over the largest
``|o|``, and of the state); then a sample is ``CALLS`` calls back to
back on the layers in turn and one wait, host-timed; the median of ``--samples`` samples /
``CALLS`` is printed as ms a layer, with the bytes a call must move
(every active row's state read and written once) over that time.  One
JSON line on stdout; refuses to time anything but a TPU unless
``--tiny`` (a CPU rehearsal of the flow: its times mean nothing).

    python scripts/bench_kda_decode.py [--rows 128] [--samples 20] [--tiny]

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

from tpu_hc_bench.models import solar_open2 as so  # noqa: E402
from tpu_hc_bench.ops import kda_decode  # noqa: E402

# layers, slots (128 rows + the trash slot), heads, d_k, d_v of the cell
CELL = (3, 129, 64, 128, 128)
TINY = (3, 9, 4, 16, 16)
CALLS = 12


def xla_step(S, layer, slots, eg, k, q, v, beta, g):
    """The XLA decode step the kernel replaced, one KDA layer."""
    del eg

    def at(rows):
        return jnp.zeros((S.shape[1],) + rows.shape[1:],
                         rows.dtype).at[slots].set(rows)

    s_l, o = so.kda_step(jax.lax.dynamic_index_in_dim(S, layer, 0, False),
                         at(q), at(k), at(v), at(g), at(beta))
    return jax.lax.dynamic_update_index_in_dim(S, s_l, layer, 0), o[slots]


def kernel_step(S, layer, slots, eg, k, q, v, beta, g):
    del g
    return kda_decode.kda_decode_step(S, layer, slots, eg, k, q, v, beta)


def calls_back_to_back(step, S, slots, rest):
    """``CALLS`` calls on the layers in turn, the leaf handed on from one
    to the next, and one wait."""
    for c in range(CALLS):
        S, o = step(S, c % S.shape[0], slots, *rest)
    o.block_until_ready()
    return S


def inputs(shape, rows, seed=0):
    """The rows' slots (one row in eight inactive, on the trash slot)
    and their inputs, drawn as ``kda_inputs`` makes them."""
    _, n_slots, H, d, _ = shape
    rng = np.random.default_rng(seed)
    slots = rng.permutation(np.arange(1, n_slots))[:rows]
    slots[::8] = 0
    f32 = lambda a: jnp.asarray(a, jnp.float32)             # noqa: E731
    active = jnp.asarray(slots > 0)[:, None]
    g = jnp.where(active[..., None],
                  -f32(rng.uniform(0.0, 0.05, (rows, H, d))), 0.0)
    beta = jnp.where(active, f32(rng.uniform(0.0, 2.0, (rows, H))), 0.0)
    k = so._l2norm(f32(rng.standard_normal((rows, H, d))))
    q = so._l2norm(f32(rng.standard_normal((rows, H, d)))) / d ** 0.5
    v = f32(rng.standard_normal((rows, H, d)))
    return (jnp.asarray(slots, jnp.int32),
            (jnp.exp(g), k, q, v, beta, g), int((slots > 0).sum()))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=128)
    ap.add_argument("--samples", type=int, default=20)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        print(f"no TPU (backend {dev.platform}): nothing timed",
              file=sys.stderr)
        return 2
    shape = TINY if args.tiny else CELL
    L, n_slots, H, d, _ = shape
    b = min(args.rows, n_slots - 1)
    slots, rest, active = inputs(shape, b)
    need = 2 * active * H * d * d * 4
    arms = {"xla": xla_step, "kernel": kernel_step}
    out = {"device": dev.device_kind, "shape": list(shape), "rows": b,
           "active_rows": active}
    S0 = jax.random.normal(jax.random.key(1), shape, jnp.float32)
    first = {}
    for name, fn in arms.items():
        step = jax.jit(fn, donate_argnums=(0,))
        first[name] = jax.device_get(step(S0.copy(), 1, slots, *rest))
        S = jnp.zeros(shape, jnp.float32)
        S = calls_back_to_back(step, S, slots, rest)    # compile, warm
        times = []
        for _ in range(args.samples):
            t0 = time.perf_counter()
            S = calls_back_to_back(step, S, slots, rest)
            times.append((time.perf_counter() - t0) / CALLS)
        ms = 1e3 * statistics.median(times)
        out[name] = {"ms_a_layer": round(ms, 4),
                     "gb_per_s": round(need / ms / 1e6, 1),
                     "min_ms": round(1e3 * min(times), 4)}
        print(f"{name}: {out[name]}", file=sys.stderr)
    (s_x, o_x), (s_k, o_k) = first["xla"], first["kernel"]
    # rows on the trash slot are compared by neither: the XLA step's
    # scatter keeps one of their inputs for the slot, the kernel each
    # row's own
    active = np.asarray(slots) > 0
    named = np.asarray(slots)[active]
    o_k, o_x = o_k[active], o_x[active]
    out["o_rel_err"] = float(np.max(np.abs(o_k - o_x)) / np.max(np.abs(o_x)))
    out["state_rel_err"] = float(
        np.max(np.abs(s_k[1, named] - s_x[1, named]))
        / np.max(np.abs(s_x[1, named])))
    out["others_equal"] = bool(np.array_equal(s_k[0], s_x[0])
                               and np.array_equal(s_k[2:], s_x[2:]))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
