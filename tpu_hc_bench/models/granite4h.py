"""Hybrid Mamba-2 / softmax-attention dense decoder (the
``granitemoehybrid`` model type with no experts: ibm-granite/
granite-4.0-h-micro ``config.json``).

Forty layers: Mamba-2 state-space mixers ("SSD", the ``mamba_*`` keys)
with a softmax GQA layer at ``layer_types``' ``"attention"`` entries (5,
15, 25 and 35: one in ten), no position encoding at all
(``position_embedding_type`` ``nope``).  Every layer's FFN is one dense
SwiGLU (``shared_intermediate_size``; ``num_local_experts`` 0).  Pre-norm
residuals with muP multipliers, RMSNorm, a tied head.

The mixers' mathematics are plain functions over their parameter
subtrees (``ssd_inputs`` / ``ssd_chunked`` / ``ssd_step`` /
``ssd_output``, ``attn_inputs`` / ``attn_output``, ``mlp``): the flax
module below declares the parameters and calls them, and
``serve/decode.py`` calls the same functions over the same subtrees
against its cache tree — one implementation for the full forward, the
chunked prefill and the one-token decode step.

Mamba-2, ``u`` the normed input (``P`` = ``mamba_d_head``, ``N`` =
``mamba_d_state``, one group: ``B`` and ``C`` shared by every head)::

    [z | xBC | dt] = u W_in
    xBC = SiLU(conv4(xBC) + b_conv);  [x | B | C] = xBC       # x: heads x P
    D_t = softplus(dt + dt_bias);  A = -exp(A_log)            # per head
    h_t = exp(D_t A) h_{t-1} + D_t x_t B_t^T                  # [P, N] a head
    y_t = h_t C_t + D x_t
    out = W_out RMSNorm(y * SiLU(z))                          # over d_inner

Attention: ``softmax(q k^T * attention_multiplier + causal) v``, then
``Wo``.  Block: ``x += 0.22 * mixer(norm1(x))``, ``x += 0.22 *
MLP(norm2(x))``; ends: ``x_0 = 12 * E[tok]``, ``logits = norm(x_L) E^T /
8``.

Stated arithmetic: parameters and the activations between operations in
``dtype`` (bfloat16 when served so), float32 accumulation; float32 for
norm statistics, softmax, ``D_t``, ``exp(D_t A)``, the chunk's decay sums
and the state ``h`` (its products at ``HIGHEST``).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_hc_bench.models.llama import RMSNorm
from tpu_hc_bench.models.solar_open2 import _Params, _proj, causal_conv

_HI = jax.lax.Precision.HIGHEST
SSD_CHUNK = 256
LAYER_TYPES = tuple(
    "attention" if l % 10 == 5 else "mamba" for l in range(40))


def residual(x, y, mult: float):
    """``x + mult * y`` in float32, stored in ``x``'s dtype."""
    return (x.astype(jnp.float32)
            + mult * y.astype(jnp.float32)).astype(x.dtype)


def ssd_inputs(p, u, tail, heads: int, d_state: int):
    """The recurrence's inputs for new positions ``u`` [b, s, H].

    ``tail`` [b, K-1, conv] holds the pre-convolution ``xBC`` of the
    positions just before (zeros at a sequence's start).  Returns ``(x,
    B, C, dt, z, padded)``: ``x`` float32 [b, s, heads, P], ``B``, ``C``
    float32 [b, s, N], ``dt`` = softplus(dt + dt_bias) float32 [b, s,
    heads], ``z`` [b, s, d_inner] in ``u``'s dtype, ``padded`` as
    ``causal_conv``'s."""
    b, s, _ = u.shape
    d_inner = p["norm"].shape[0]
    zxbcdt = _proj(u, p["in_proj"])
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:-heads]
    dt = zxbcdt[..., -heads:]
    y, padded = causal_conv(xbc, tail, p["conv_w"], p["conv_b"])
    x = y[..., :d_inner].reshape(b, s, heads, -1)
    B = y[..., d_inner:d_inner + d_state]
    C = y[..., d_inner + d_state:]
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    return x, B, C, dt, z, padded


def ssd_decay(p):
    """``A = -exp(A_log)`` a head, float32."""
    return -jnp.exp(p["A_log"].astype(jnp.float32))


def ssd_output(p, y, x, z, eps: float):
    """``W_out RMSNorm((y + D x) * SiLU(z))``; ``y``, ``x`` float32 [b, s,
    heads, P], ``z`` [b, s, d_inner].  The gate comes before the norm
    (one group: the norm is over all of ``d_inner``)."""
    b, s, heads, hp = y.shape
    y = y + p["D"].astype(jnp.float32)[:, None] * x
    g = y.reshape(b, s, heads * hp) * nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    g = g * jax.lax.rsqrt(var + eps) * p["norm"].astype(jnp.float32)
    return _proj(g.astype(z.dtype), p["out_proj"])


def ssd_step(h, x, B, C, dt, A):
    """One step of the recurrence for every leading index at once.

    ``h`` [..., heads, P, N] float32; ``x`` [..., heads, P]; ``B``, ``C``
    [..., N]; ``dt`` [..., heads]; ``A`` [heads].  Returns ``(h_new, y
    [..., heads, P])``.  Broadcast multiplies and a reduction (float32 on
    the vector unit): no matmul rounds an operand.  ``dt`` = 0 leaves
    ``h`` as it was."""
    decay = jnp.exp(dt * A)
    h = (h * decay[..., None, None]
         + (dt[..., None] * x)[..., None] * B[..., None, None, :])
    return h, jnp.sum(h * C[..., None, None, :], axis=-1)


def ssd_chunked(x, B, C, dt, A, h0, chunk: int = SSD_CHUNK):
    """The recurrence over a whole sequence, a chunk at a time (the SSD
    form).

    ``x`` [s, heads, P], ``B``, ``C`` [s, N], ``dt`` [s, heads] float32,
    ``A`` [heads], ``h0`` [heads, P, N].  Inside a chunk, with ``a_t``
    the running sum of ``dt A`` from the chunk's start and ``h_0`` the
    entering state::

        y_t = e^(a_t) h_0 C_t + sum_{j<=t} e^(a_t - a_j) (C_t . B_j) dt_j x_j
        h_C = e^(a_C) h_0 + sum_j e^(a_C - a_j) dt_j x_j B_j^T

    Every exponent is a difference <= 0.  A position with ``dt`` = 0 is
    inert: it leaves the state as it was.  Returns ``(y [s, heads, P],
    h_end)``."""
    s, heads, hp = x.shape
    c = min(chunk, s)
    if s % c:
        raise ValueError(f"sequence {s} is no multiple of the chunk {c}")
    n = s // c
    tri = jnp.tril(jnp.ones((c, c), bool))

    def body(h, xs):
        xc, bc, cc, dtc = xs            # [c, heads, P], [c, N] x2, [c, heads]
        a = jnp.cumsum(dtc * A, axis=0).T                   # [heads, c]
        seg = jnp.exp(jnp.where(tri[None], a[:, :, None] - a[:, None, :],
                                -jnp.inf))                  # [heads, t, j]
        cb = jnp.einsum("tn,jn->tj", cc, bc, precision=_HI)
        m = seg * cb[None] * dtc.T[:, None, :]
        y = (jnp.einsum("htj,jhp->thp", m, xc, precision=_HI)
             + jnp.exp(a).T[:, :, None] * jnp.einsum(
                 "tn,hpn->thp", cc, h, precision=_HI))
        w = (jnp.exp(a[:, -1:] - a) * dtc.T).T              # e^(a_C-a_j) dt_j
        h_new = (h * jnp.exp(a[:, -1])[:, None, None]
                 + jnp.einsum("jhp,jn->hpn", w[:, :, None] * xc, bc,
                              precision=_HI))
        return h_new, y

    h_end, y = jax.lax.scan(
        body, h0, tuple(t.reshape(n, c, *t.shape[1:])
                        for t in (x, B, C, dt)))
    return y.reshape(s, heads, hp), h_end


def ssd_sequence(x, B, C, dt, A, h0, chunk: int = SSD_CHUNK):
    """``ssd_chunked`` over any length: right-padded to a whole number of
    chunks with inert positions (``dt`` = 0)."""
    s = x.shape[0]
    pad = (-s) % min(chunk, s)
    if pad:
        x, B, C, dt = (jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                       for t in (x, B, C, dt))
    y, h_end = ssd_chunked(x, B, C, dt, A, h0, chunk)
    return y[:s], h_end


def attn_inputs(p, u, heads: int, kv_heads: int):
    """``q`` [b, s, heads, d], ``k``, ``v`` [b, s, kv_heads, d]; no
    position encoding."""
    b, s, _ = u.shape
    return (_proj(u, p["wq"]).reshape(b, s, heads, -1),
            _proj(u, p["wk"]).reshape(b, s, kv_heads, -1),
            _proj(u, p["wv"]).reshape(b, s, kv_heads, -1))


def attn_output(p, ctx):
    """``Wo ctx``; ``ctx`` [b, s, heads, d] (no output gate)."""
    b, s, heads, d = ctx.shape
    return _proj(ctx.reshape(b, s, heads * d), p["wo"])


def mlp(p, h):
    """``(SiLU(h W_a) * h W_b) W_o`` with ``[W_a | W_b]`` one matrix."""
    a, b = jnp.split(_proj(h, p["w_in"]), 2, axis=-1)
    act = nn.silu(a.astype(jnp.float32)) * b.astype(jnp.float32)
    return _proj(act.astype(h.dtype), p["w_out"])


def mamba_shapes(hidden: int, heads: int, d_head: int, d_state: int,
                 conv: int):
    d_inner = heads * d_head
    channels = d_inner + 2 * d_state
    return (("in_proj", (hidden, d_inner + channels + heads), "matrix"),
            ("conv_w", (conv, channels), "conv"),
            ("conv_b", (channels,), "small"),
            ("dt_bias", (heads,), "small"), ("A_log", (heads,), "small"),
            ("D", (heads,), "ones"), ("norm", (d_inner,), "ones"),
            ("out_proj", (d_inner, hidden), "matrix"))


def attn_shapes(hidden: int, heads: int, kv_heads: int, d: int):
    return (("wq", (hidden, heads * d), "matrix"),
            ("wk", (hidden, kv_heads * d), "matrix"),
            ("wv", (hidden, kv_heads * d), "matrix"),
            ("wo", (heads * d, hidden), "matrix"))


def mlp_shapes(hidden: int, ffn: int):
    return (("w_in", (hidden, 2 * ffn), "matrix"),
            ("w_out", (ffn, hidden), "matrix"))


class GraniteHybridLM(nn.Module):
    """The decoder at every published width and its full depth."""

    vocab_size: int = 100352
    hidden: int = 2048
    layer_types: tuple = LAYER_TYPES
    heads: int = 32
    kv_heads: int = 8
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    d_state: int = 128
    conv_kernel: int = 4
    chunk: int = SSD_CHUNK
    ffn: int = 8192
    embedding_mult: float = 12.0
    residual_mult: float = 0.22
    attn_scale: float = 0.015625
    logits_scaling: float = 8.0
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def conv_channels(self) -> int:
        return self.mamba_heads * self.mamba_head_dim + 2 * self.d_state

    def mixer_kind(self, l: int) -> str:
        return "attn" if self.layer_types[l] == "attention" else "mamba2"

    @nn.nowrap
    def mixer_params(self, l: int, name=None) -> _Params:
        if self.mixer_kind(l) == "attn":
            shapes = attn_shapes(self.hidden, self.heads, self.kv_heads,
                                 self.head_dim)
        else:
            shapes = mamba_shapes(self.hidden, self.mamba_heads,
                                  self.mamba_head_dim, self.d_state,
                                  self.conv_kernel)
        return _Params(shapes, dtype=self.dtype, name=name)

    @nn.compact
    def __call__(self, token_ids, train: bool = True):
        del train                       # no dropout in the family
        from tpu_hc_bench.parallel.sequence import dense_attention

        embed = nn.Embed(self.vocab_size, self.hidden, dtype=self.dtype,
                         param_dtype=self.dtype, name="tok_embed")
        x = embed(token_ids)
        x = (x.astype(jnp.float32) * self.embedding_mult).astype(self.dtype)
        for l in range(self.num_layers):
            u = RMSNorm(eps=self.eps, dtype=self.dtype,
                        name=f"layer_{l}_norm1")(x)
            p = self.mixer_params(l, name=f"layer_{l}_mixer")()
            if self.mixer_kind(l) == "attn":
                q, k, v = attn_inputs(p, u, self.heads, self.kv_heads)
                group = self.heads // self.kv_heads
                ctx = dense_attention(q, jnp.repeat(k, group, axis=2),
                                      jnp.repeat(v, group, axis=2),
                                      causal=True, scale=self.attn_scale)
                y = attn_output(p, ctx)
            else:
                y = self.ssd_forward(p, u)
            x = residual(x, y, self.residual_mult)
            h = RMSNorm(eps=self.eps, dtype=self.dtype,
                        name=f"layer_{l}_norm2")(x)
            pm = _Params(mlp_shapes(self.hidden, self.ffn), dtype=self.dtype,
                         name=f"layer_{l}_mlp")()
            x = residual(x, mlp(pm, h), self.residual_mult)
        x = RMSNorm(eps=self.eps, dtype=self.dtype, name="final_norm")(x)
        return self.tied_head(embed.variables["params"], x)

    # --- the functional seams serve/decode.py re-walks the tree through

    @nn.nowrap
    def tied_head(self, embed: dict, x):
        """``x E^T / logits_scaling``, float32 logits."""
        return jnp.einsum("bsh,vh->bsv", x.astype(self.dtype),
                          embed["embedding"].astype(self.dtype),
                          preferred_element_type=jnp.float32
                          ) / self.logits_scaling

    @nn.nowrap
    def pp_embed(self, params: dict, token_ids, rng):
        x = params["tok_embed"]["embedding"][token_ids].astype(jnp.float32)
        return (x * self.embedding_mult).astype(self.dtype), rng

    @nn.nowrap
    def pp_head(self, params: dict, x):
        x = RMSNorm(eps=self.eps, dtype=self.dtype).apply(
            {"params": params["final_norm"]}, x)
        return self.tied_head(params["tok_embed"], x)

    @nn.nowrap
    def ssd_forward(self, p, u):
        """A Mamba-2 mixer over whole sequences from a zero state (the
        training-shaped forward)."""
        b = u.shape[0]
        tail = jnp.zeros((b, self.conv_kernel - 1, self.conv_channels),
                         u.dtype)
        x, B, C, dt, z, _ = ssd_inputs(p, u, tail, self.mamba_heads,
                                       self.d_state)
        h0 = jnp.zeros((self.mamba_heads, self.mamba_head_dim,
                        self.d_state), jnp.float32)
        A = ssd_decay(p)
        y = jax.vmap(lambda *a: ssd_sequence(*a, A, h0, self.chunk)[0])(
            x, B, C, dt)
        return ssd_output(p, y, x, z, self.eps)


def _factory(**sizes):
    def create(num_classes: int = 0, dtype=jnp.float32,
               attention_impl: str = "dense", max_len: int | None = None,
               remat: bool = False, seq_axis: str | None = None):
        del num_classes, max_len        # no position table: any length
        if attention_impl != "dense" or remat or seq_axis is not None:
            raise ValueError(
                "the granite4h members run dense attention on one "
                "device without recomputation (serve lane first)")
        return GraniteHybridLM(dtype=dtype, **sizes)

    return create


# the published model whole: 40 layers, every width, 100,352 rows
granite_4_0_h_micro = _factory()

# the CPU tests' size: both kinds of mixer, the attention layer between
# recurrent ones, several SSD chunks in a short prompt
TINY = dict(vocab_size=256, hidden=64,
            layer_types=("mamba", "attention", "mamba", "mamba"),
            heads=4, kv_heads=2, mamba_heads=4, mamba_head_dim=32,
            d_state=16, chunk=8, ffn=96)
granite4h_tiny = _factory(**TINY)
