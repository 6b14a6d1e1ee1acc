"""Fused residual-add + layer normalization as a Pallas TPU kernel.

Every pre-LN decoder layer does ``x = x + branch; h = norm(x)`` twice
(attention and FFN).  Unfused, that is three HBM round-trips of the
``[rows, hidden]`` activation (write the sum, read it for the stats,
read it again for the normalize); fused, the sum is computed once in
VMEM and both the new residual stream *and* its normalized view leave
the kernel together — one read of each input, one write of each
output.  Both serving decode families consume it (``serve.decode``):
GPT's ``LayerNorm`` (mean/variance, scale+bias) and Llama's ``RMSNorm``
(root-mean-square, scale only).

Numerics match the Flax modules they replace (``nn.LayerNorm`` fast
variance ``E[x^2] - E[x]^2`` clamped at 0; ``models.llama.RMSNorm``'s
f32 stats) — pinned by ``tests/test_zz_decode_kernels.py``.  Stats always
accumulate in float32.  The CPU backend runs the Pallas interpreter
(``ops._pallas.interpret``), same as every kernel in this package.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tpu_hc_bench.ops._pallas import interpret as _interpret
from tpu_hc_bench.ops._pallas import pad_up as _pad_up

_BLOCK_ROWS = 256


def _kernel(res_ref, x_ref, gamma_ref, beta_ref, y_ref, o_ref, *,
            eps, kind):
    y = res_ref[...] + x_ref[...]
    y_ref[...] = y
    f = y.astype(jnp.float32)
    gamma = gamma_ref[0].astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(f, axis=-1, keepdims=True)
        # flax fast variance: E[x^2] - E[x]^2, clamped at 0
        var = jnp.maximum(
            jnp.mean(f * f, axis=-1, keepdims=True) - mu * mu, 0.0)
        o = (f - mu) * jax.lax.rsqrt(var + eps) * gamma
        o = o + beta_ref[0].astype(jnp.float32)
    else:                                   # rmsnorm
        var = jnp.mean(f * f, axis=-1, keepdims=True)
        o = f * jax.lax.rsqrt(var + eps) * gamma
    o_ref[...] = o.astype(o_ref.dtype)


def fused_residual_norm(res, x, gamma, beta=None, *,
                        kind: str = "layernorm",
                        eps: float | None = None,
                        block_rows: int = _BLOCK_ROWS):
    """``y = res + x``; ``out = norm(y)`` — one fused kernel.

    Args:
      res: the residual stream, ``[..., hidden]``.
      x: the branch output to add, same shape.
      gamma: ``[hidden]`` norm scale.
      beta: ``[hidden]`` bias (layernorm only; None for rmsnorm).
      kind: ``"layernorm"`` (flax ``nn.LayerNorm`` numerics, eps 1e-6)
        or ``"rmsnorm"`` (``models.llama.RMSNorm`` numerics, eps 1e-5).
      eps: override the kind's default epsilon.
      block_rows: rows per grid step (clipped to the padded row count).
    Returns:
      ``(y, out)`` — the new residual stream and its normalized view,
      both in ``res``'s dtype and shape.
    """
    if kind not in ("layernorm", "rmsnorm"):
        raise ValueError(f"kind must be layernorm|rmsnorm: {kind!r}")
    if kind == "layernorm" and beta is None:
        raise ValueError("layernorm needs beta (bias); rmsnorm is the "
                         "scale-only form")
    eps = (1e-6 if kind == "layernorm" else 1e-5) if eps is None else eps
    shape = res.shape
    h = shape[-1]
    rf = res.reshape(-1, h)
    xf = x.reshape(-1, h)
    n = rf.shape[0]
    block_rows = min(block_rows, _pad_up(n, 8))
    n_pad = _pad_up(n, block_rows)
    if n_pad != n:
        rf = jnp.pad(rf, ((0, n_pad - n), (0, 0)))
        xf = jnp.pad(xf, ((0, n_pad - n), (0, 0)))
    if beta is None:
        beta = jnp.zeros((h,), gamma.dtype)     # never read (rmsnorm)

    row_spec = pl.BlockSpec((block_rows, h), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, h), lambda i: (0, 0))
    y, o = pl.pallas_call(
        functools.partial(_kernel, eps=eps, kind=kind),
        grid=(n_pad // block_rows,),
        in_specs=[row_spec, row_spec, vec_spec, vec_spec],
        out_specs=[row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, h), res.dtype),
            jax.ShapeDtypeStruct((n_pad, h), res.dtype),
        ],
        interpret=_interpret(),
    )(rf, xf, gamma.reshape(1, h), beta.reshape(1, h))
    return y[:n].reshape(shape), o[:n].reshape(shape)
