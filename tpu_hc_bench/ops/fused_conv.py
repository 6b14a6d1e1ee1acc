"""Fused BN-apply + relu + 3x3 conv + BN-stats Pallas kernel (round 3).

The ResNet bottleneck's hot pattern is ``conv -> BN -> relu -> conv``:
in training, the producer conv's raw output must be materialized (its BN
statistics aren't ready until the whole tensor exists), but the
*normalize + relu + next conv* consumption can run in one pass.  The
round-3 measurement (closing VERDICT item #1's conv question left open
by the round-2 matmul proxy) showed XLA
fuses this well at stage-1 shapes (56x56x64: fused/xla = 1.07, no win)
but NOT at wider channels:

    [128, 28, 28, 128]: fused/xla = 0.65   (35% faster)
    [128, 14, 14, 256]: fused/xla = 0.64
    [128,  7,  7, 512]: see BASELINE.md round-3 table

This kernel is the production form of that experiment:

    y2, s1, s2 = fused_bn_relu_conv(y1_raw, a, b, w)

      prologue   xn = relu(y1_raw * a + b)   (BN folded to scale/shift,
                 computed into a padded VMEM halo buffer — y1_raw is read
                 from HBM exactly once)
      body       9 shifted [rows, Cin] x [Cin, Cout] MXU taps, f32 acc
      epilogue   y2 streamed out in the model dtype; per-channel
                 sum / sum-of-squares accumulated across the grid so the
                 NEXT BatchNorm needs no pass over y2

Backward (custom_vjp) runs on XLA: the cotangent folds the stats terms
into g_y2, the conv transposes come from ``jax.linear_transpose`` (no
forward re-execution), and the BN-apply/relu backward is elementwise.

Grid: ``G`` images per program (G chosen so each program's matmul has
>=~784 rows even at 7x7), one pass over the batch; the running-stat
scratch accumulates across sequential grid steps ("arbitrary" dimension
semantics) exactly like `ops/xent.py`.

Reference provenance: the reference's compute engine delegates conv+BN
fusion to MKL-DNN (SURVEY.md §2b #21); this is the TPU counterpart,
Pallas-where-XLA-underperforms per the same survey row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_hc_bench.ops._pallas import interpret as _interpret


def _pick_group(batch: int, rows: int, target: int = 784) -> int:
    """Largest divisor of ``batch`` keeping ~``target`` matmul rows per
    program (small feature maps pack several images per grid step)."""
    want = max(1, target // max(rows, 1))
    g = 1
    for d in range(1, min(batch, want) + 1):
        if batch % d == 0:
            g = d
    return g


def _kernel(x_ref, w_ref, a_ref, b_ref, y_ref, s1_ref, s2_ref,
            xn_ref, sacc1, sacc2, *, gh, hw, cin, cout, out_dtype):
    i = pl.program_id(0)
    g, h, w = gh, hw, hw

    @pl.when(i == 0)
    def _init():
        sacc1[...] = jnp.zeros_like(sacc1)
        sacc2[...] = jnp.zeros_like(sacc2)

    x = x_ref[...].astype(jnp.float32)                    # [G, H, W, Ci]
    xn = jnp.maximum(x * a_ref[...] + b_ref[...], 0.0)
    xn_ref[...] = jnp.zeros_like(xn_ref)
    xn_ref[:, 1:h + 1, 1:w + 1, :] = xn.astype(xn_ref.dtype)

    acc = jnp.zeros((g * h * w, cout), jnp.float32)
    for dh in range(3):
        for dw in range(3):
            patch = xn_ref[:, dh:dh + h, dw:dw + w, :].reshape(
                g * h * w, cin)
            acc += jnp.dot(patch, w_ref[dh, dw],
                           preferred_element_type=jnp.float32)

    y_ref[...] = acc.reshape(g, h, w, cout).astype(out_dtype)
    sacc1[...] += acc.sum(axis=0, keepdims=True)
    sacc2[...] += (acc * acc).sum(axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        s1_ref[...] = sacc1[...]
        s2_ref[...] = sacc2[...]


def _fused_fwd_impl(y1, a, b, w):
    """Raw forward: (y2, s1, s2) with s1/s2 the per-channel sum/sumsq."""
    batch, h, width, cin = y1.shape
    assert h == width, "square feature maps only (ResNet pattern)"
    cout = w.shape[-1]
    g = _pick_group(batch, h * h)
    out_dtype = y1.dtype
    kern = functools.partial(
        _kernel, gh=g, hw=h, cin=cin, cout=cout, out_dtype=out_dtype)
    y, s1, s2 = pl.pallas_call(
        kern,
        grid=(batch // g,),
        in_specs=[
            pl.BlockSpec((g, h, h, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((3, 3, cin, cout), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((1, cin), lambda i: (0, 0)),
            pl.BlockSpec((1, cin), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((g, h, h, cout), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, h, h, cout), out_dtype),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, h + 2, h + 2, cin), out_dtype),
            pltpu.VMEM((1, cout), jnp.float32),
            pltpu.VMEM((1, cout), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(y1, w, a, b)
    return y, s1[0], s2[0]


def _conv(xn, w):
    return jax.lax.conv_general_dilated(
        xn, w, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32,
    )


@jax.custom_vjp
def fused_bn_relu_conv(y1, a, b, w):
    """``relu(y1 * a + b)`` convolved with ``w`` (3x3, SAME, stride 1).

    ``a``/``b`` are the folded BN scale/shift (f32, shape ``[Cin]``);
    returns ``(y2, s1, s2)`` where ``s1``/``s2`` are y2's per-channel
    sum / sum-of-squares (f32, ``[Cout]``) for the next BatchNorm.
    """
    return _fused_fwd_impl(y1, a[None], b[None], w)


def _fwd(y1, a, b, w):
    y2, s1, s2 = _fused_fwd_impl(y1, a[None], b[None], w)
    return (y2, s1, s2), (y1, a, b, w, y2)


def _bwd(res, cts):
    y1, a, b, w, y2 = res
    g_y, g_s1, g_s2 = cts
    # fold the stats cotangents into the output cotangent:
    #   s1 = sum(y2), s2 = sum(y2^2)  =>  dy2 += g_s1 + 2*y2*g_s2
    geff = (g_y.astype(jnp.float32)
            + g_s1[None, None, None, :]
            + 2.0 * y2.astype(jnp.float32) * g_s2[None, None, None, :])
    xn_f = jnp.maximum(y1.astype(jnp.float32) * a + b, 0.0)
    xn = xn_f.astype(y1.dtype)
    geff_c = geff.astype(y1.dtype)

    # linear_transpose: the conv's transposes without re-running a forward.
    # The transposed primitive requires operand dtypes to MATCH, so the
    # function transposed here is the same-dtype conv (bf16 in -> bf16
    # out; the MXU still accumulates in f32 internally), with the
    # cotangent cast to that dtype.
    def conv_same(x, k):
        return jax.lax.conv_general_dilated(
            x, k, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    dxn, = jax.linear_transpose(lambda t: conv_same(t, w), xn)(geff_c)
    dw, = jax.linear_transpose(lambda t: conv_same(xn, t), w)(geff_c)
    t = dxn.astype(jnp.float32) * (xn_f > 0)
    dy1 = (t * a).astype(y1.dtype)
    da = jnp.sum(t * y1.astype(jnp.float32), axis=(0, 1, 2))
    db = jnp.sum(t, axis=(0, 1, 2))
    return dy1, da, db, dw.astype(w.dtype)


fused_bn_relu_conv.defvjp(_fwd, _bwd)


def eligible(shape: tuple, kernel: tuple, strides, cin: int) -> bool:
    """Where the kernel beats XLA — the measured win region (round-3
    A/B at bs=128):

        56x56x 64: 1.07x (XLA already fuses; stays on XLA)
        28x28x128: 0.65x  WIN
        14x14x256: 0.64x  WIN
         7x7x512: 1.06x (tiny maps; stays on XLA)

    => 3x3 stride-1 square maps, >=128 input channels, >=14 spatial."""
    if tuple(kernel) != (3, 3):
        return False
    s = strides if isinstance(strides, int) else max(strides)
    if s != 1:
        return False
    if len(shape) != 4 or shape[1] != shape[2]:
        return False
    return cin >= 128 and shape[1] >= 14
