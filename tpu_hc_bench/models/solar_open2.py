"""Hybrid linear-attention / softmax-attention MoE decoder (the
``solar_open2`` model type: upstage/Solar-Open2-250B ``config.json``).

One period of the layer pattern is four layers: a softmax GQA layer with
an output gate and no position encoding at all (``use_rope`` false), then
three gated delta-rule linear-attention layers ("KDA": Kimi Delta
Attention, the ``kda_*`` keys).  Every layer's FFN is a routed mixture
(``models/moe.MoEFFN`` with ``score="sigmoid"``: sigmoid top-k with a
selection bias, a shared expert, and ``experts_held``: the chip's share
of an expert-parallel deployment).  Pre-norm residuals, RMSNorm, untied
head.

The mixers' mathematics are plain functions over their parameter
subtrees (``kda_inputs`` / ``kda_chunked`` / ``kda_step`` /
``kda_output``, ``gqa_inputs`` / ``gqa_output``): the flax modules below
declare the parameters and call them, and ``serve/decode.py`` calls the
same functions over the same subtrees against its cache tree — one
implementation for the full forward, the chunked prefill and the
one-token decode step.

KDA, per head (``d_k = d_v = head_dim``), ``u`` the normed input::

    q~, k~, v = SiLU(conv4(u Wq)), SiLU(conv4(u Wk)), SiLU(conv4(u Wv))
    q = l2norm(q~) / sqrt(d_k);  k = l2norm(k~)
    g = -exp(A_log) * softplus((u Wf_down) Wf_up + dt_bias)   # per channel
    b = 2 * sigmoid(u Wb)                                     # per head
    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t
    out = Wo [ rmsnorm_head(o_t) * sigmoid((u Wg_down) Wg_up) ]

Stated arithmetic: parameters and the activations between operations in
``dtype`` (bfloat16 when served so), float32 accumulation; float32 for
the router scores, norm statistics, softmax, ``g``, ``b``, the chunk's
triangular solve and the state ``S`` (its products at ``HIGHEST``).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_hc_bench.models.llama import RMSNorm
from tpu_hc_bench.models.moe import MoEFFN

_HI = jax.lax.Precision.HIGHEST
KDA_CHUNK = 64


def _proj(x, w):
    """``x @ w`` with float32 accumulation, stored in ``x``'s dtype."""
    return jnp.einsum("...h,hf->...f", x, w.astype(x.dtype),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def causal_conv(x, tail, w, bias=None):
    """Depthwise causal convolution over time (plus ``bias`` [c] where
    given), then SiLU, in float32.

    ``x`` [b, s, c] the new inputs, ``tail`` [b, K-1, c] the inputs just
    before them (zeros at a sequence's start), ``w`` [K, c].  Returns
    ``(y [b, s, c] float32, padded [b, K-1+s, c])``: ``padded[:, n:n+K-1]``
    is the tail after ``n`` of the new inputs."""
    k = w.shape[0]
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    y = sum(padded[:, i:i + s].astype(jnp.float32)
            * w[i].astype(jnp.float32) for i in range(k))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return nn.silu(y), padded


def kda_inputs(p, u, tail, heads: int, neg_eigval: bool = True):
    """The recurrence's inputs for new positions ``u`` [b, s, H].

    ``tail`` [b, K-1, 3 * heads * d] holds the conv inputs (q | k | v
    projections, pre-conv) of the positions just before.  Returns
    ``(q, k, v, g, beta, padded)``: q, k, v, g float32 [b, s, heads, d],
    beta float32 [b, s, heads], ``padded`` as ``causal_conv``'s."""
    b, s, _ = u.shape
    x = jnp.concatenate([_proj(u, p["wq"]), _proj(u, p["wk"]),
                         _proj(u, p["wv"])], axis=-1)
    conv_w = jnp.concatenate([p["conv_q"], p["conv_k"], p["conv_v"]], -1)
    y, padded = causal_conv(x, tail, conv_w)
    q, k, v = (t.reshape(b, s, heads, -1) for t in jnp.split(y, 3, axis=-1))
    d = q.shape[-1]
    q = _l2norm(q) * (1.0 / d ** 0.5)
    k = _l2norm(k)
    f = _proj(_proj(u, p["wf_down"]), p["wf_up"]).astype(jnp.float32)
    g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        (f + p["dt_bias"].astype(jnp.float32)).reshape(b, s, heads, d))
    beta = jax.nn.sigmoid(jnp.einsum(
        "bsh,hn->bsn", u.astype(jnp.float32), p["wb"].astype(jnp.float32),
        precision=_HI))
    if neg_eigval:
        beta = 2.0 * beta
    return q, k, v, g, beta, padded


def kda_output(p, o, u, eps: float):
    """``Wo [rmsnorm_head(o) * sigmoid((u Wg_down) Wg_up)]``; ``o``
    float32 [b, s, heads, d_v]."""
    b, s, heads, d = o.shape
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    o = o * jax.lax.rsqrt(var + eps) * p["o_norm"].astype(jnp.float32)
    gate = jax.nn.sigmoid(_proj(_proj(u, p["wg_down"]), p["wg_up"]).astype(
        jnp.float32)).reshape(b, s, heads, d)
    return _proj((o * gate).astype(u.dtype).reshape(b, s, heads * d),
                 p["wo"])


def kda_step(S, q, k, v, g, beta):
    """One step of the recurrence for every leading index at once.

    ``S`` [..., d_k, d_v] float32; ``q``, ``k``, ``g`` [..., d_k]; ``v``
    [..., d_v]; ``beta`` [...].  Returns ``(S_new, o [..., d_v])``.
    Written as broadcast multiplies and reductions (float32 on the
    vector unit): no matmul rounds an operand."""
    sd = S * jnp.exp(g)[..., None]
    w = beta[..., None] * (v - jnp.sum(sd * k[..., None], axis=-2))
    s_new = sd + k[..., None] * w[..., None, :]
    return s_new, jnp.sum(s_new * q[..., None], axis=-2)


def kda_chunked(q, k, v, g, beta, S0, chunk: int = KDA_CHUNK):
    """The recurrence over a whole sequence, a chunk at a time.

    ``q``, ``k``, ``v``, ``g`` [s, heads, d] float32, ``beta`` [s, heads],
    ``S0`` [heads, d_k, d_v].  Inside a chunk of ``C`` positions, with
    ``G_t`` the running sum of ``g`` and ``S_0`` the entering state::

        (I + L) W = B * (V - (K * e^G) S_0),
            L[t, j] = b_t sum_c k[t,c] k[j,c] e^(G[t,c] - G[j,c]),  j < t
        o_t = S_0^T (e^G_t * q_t) + sum_{j<=t} ((e^(G_t-G_j) * k_j) . q_t) w_j
        S_C = Diag(e^G_C) S_0 + sum_j (e^(G_C-G_j) * k_j) w_j^T

    Every exponent is a difference <= 0 (never a quotient of
    exponentials).  A position with ``beta`` = 0 and ``g`` = 0 is inert:
    it leaves the state as it was.  Returns ``(o [s, heads, d_v],
    S_end)``."""
    s, heads, d = q.shape
    c = min(chunk, s)
    if s % c:
        raise ValueError(f"sequence {s} is no multiple of the chunk {c}")
    n = s // c
    tri = jnp.tril(jnp.ones((c, c), bool))

    def split(x):                       # [s, heads, ...] -> [n, heads, c, ...]
        return jnp.moveaxis(x.reshape(n, c, *x.shape[1:]), 1, 2)

    def body(S, xs):
        qc, kc, vc, gc, bc = xs         # [heads, c, d] x4, [heads, c]
        G = jnp.cumsum(gc, axis=1)
        # decay[h, t, j, :] = e^(G_t - G_j) for j <= t, else 0
        decay = jnp.exp(jnp.where(
            tri[None, :, :, None], G[:, :, None] - G[:, None, :], -jnp.inf))
        kd = kc[:, None, :, :] * decay                      # [h, t, j, d]
        A = jnp.sum(kc[:, :, None, :] * kd, axis=-1)        # k_t . k_j e^..
        Aq = jnp.sum(qc[:, :, None, :] * kd, axis=-1)
        L = bc[:, :, None] * jnp.where(tri & ~jnp.eye(c, dtype=bool), A, 0.0)
        eG = jnp.exp(G)
        rhs = bc[..., None] * (vc - jnp.einsum(
            "hcd,hdv->hcv", kc * eG, S, precision=_HI))
        W = jax.lax.linalg.triangular_solve(
            L + jnp.eye(c, dtype=L.dtype), rhs, left_side=True, lower=True,
            unit_diagonal=True)
        o = (jnp.einsum("hcd,hdv->hcv", qc * eG, S, precision=_HI)
             + jnp.einsum("htj,hjv->htv", Aq, W, precision=_HI))
        tail = jnp.exp(G[:, -1:, :] - G)                    # e^(G_C - G_j)
        S_new = (S * eG[:, -1, :, None]
                 + jnp.einsum("hjd,hjv->hdv", kc * tail, W, precision=_HI))
        return S_new, o

    S_end, o = jax.lax.scan(
        body, S0, tuple(split(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 1, 2).reshape(s, heads, -1), S_end


def kda_sequence(q, k, v, g, beta, S0, chunk: int = KDA_CHUNK):
    """``kda_chunked`` over any length: right-padded to a whole number of
    chunks with inert positions (``beta`` = 0, ``g`` = 0)."""
    s = q.shape[0]
    pad = (-s) % min(chunk, s)
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
            for t in (q, k, v, g, beta))
    o, s_end = kda_chunked(q, k, v, g, beta, S0, chunk)
    return o[:s], s_end


def gqa_inputs(p, u, heads: int, kv_heads: int):
    """``q`` [b, s, heads, d], ``k``, ``v`` [b, s, kv_heads, d]; no
    position encoding."""
    b, s, _ = u.shape
    return (_proj(u, p["wq"]).reshape(b, s, heads, -1),
            _proj(u, p["wk"]).reshape(b, s, kv_heads, -1),
            _proj(u, p["wv"]).reshape(b, s, kv_heads, -1))


def gqa_output(p, ctx, u):
    """``Wo [ctx * sigmoid(u Wgate)]``; ``ctx`` [b, s, heads, d]."""
    b, s, heads, d = ctx.shape
    gate = jax.nn.sigmoid(_proj(u, p["wgate"]).astype(jnp.float32))
    mixed = ctx.reshape(b, s, heads * d).astype(jnp.float32) * gate
    return _proj(mixed.astype(u.dtype), p["wo"])


class _Params(nn.Module):
    """Declares a mixer's parameters and hands them over as a dict:
    matrices in ``dtype``, vectors and the convolution's taps float32."""

    shapes: tuple               # ((name, shape, kind), ...)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self):
        kinds = {
            "matrix": (nn.initializers.lecun_normal(), self.dtype),
            "ones": (nn.initializers.ones, jnp.float32),
            "small": (nn.initializers.zeros, jnp.float32),
        }
        out = {}
        for name, shape, kind in self.shapes:
            init, dtype = kinds.get(kind) or (
                nn.initializers.normal(shape[0] ** -0.5), jnp.float32)
            out[name] = self.param(name, init, shape, dtype)
        return out


def kda_shapes(hidden: int, heads: int, d: int, rank: int, conv: int):
    n = heads * d
    return (("wq", (hidden, n), "matrix"), ("wk", (hidden, n), "matrix"),
            ("wv", (hidden, n), "matrix"),
            ("conv_q", (conv, n), "conv"), ("conv_k", (conv, n), "conv"),
            ("conv_v", (conv, n), "conv"),
            ("wf_down", (hidden, rank), "matrix"),
            ("wf_up", (rank, n), "matrix"),
            ("dt_bias", (n,), "small"), ("A_log", (heads,), "small"),
            ("wb", (hidden, heads), "matrix"),
            ("wg_down", (hidden, rank), "matrix"),
            ("wg_up", (rank, n), "matrix"),
            ("o_norm", (d,), "ones"), ("wo", (n, hidden), "matrix"))


def gqa_shapes(hidden: int, heads: int, kv_heads: int, d: int):
    return (("wq", (hidden, heads * d), "matrix"),
            ("wk", (hidden, kv_heads * d), "matrix"),
            ("wv", (hidden, kv_heads * d), "matrix"),
            ("wgate", (hidden, heads * d), "matrix"),
            ("wo", (heads * d, hidden), "matrix"))


class SolarOpen2LM(nn.Module):
    """The decoder, parametrised by the share a chip holds: ``num_layers``
    (whole periods), ``experts_held`` and ``vocab_size`` (rows held)."""

    vocab_size: int = 24576
    hidden: int = 4096
    num_layers: int = 4
    heads: int = 64
    kv_heads: int = 8
    head_dim: int = 128
    gqa_layers: tuple = (0,)            # the rest are KDA
    kda_heads: int = 64
    kda_head_dim: int = 128
    conv_kernel: int = 4
    gate_rank: int = 128
    neg_eigval: bool = True
    n_routed: int = 320
    experts_held: tuple = (0, 40)
    top_k: int = 8
    expert_ffn: int = 1280
    shared_ffn: int = 1280
    routed_scale: float = 1.0
    eps: float = 1e-5
    dtype: Any = jnp.float32

    def mixer_kind(self, l: int) -> str:
        return "gqa" if l in self.gqa_layers else "kda"

    @nn.nowrap
    def moe_module(self, name=None) -> MoEFFN:
        return MoEFFN(
            self.hidden, self.expert_ffn, self.n_routed, top_k=self.top_k,
            dtype=self.dtype, impl="ragged", score="sigmoid", gated=True,
            shared_ffn=self.shared_ffn, experts_held=tuple(self.experts_held),
            routed_scale=self.routed_scale, param_dtype=self.dtype, name=name)

    @nn.nowrap
    def mixer_params(self, l: int, name=None) -> _Params:
        if self.mixer_kind(l) == "gqa":
            shapes = gqa_shapes(self.hidden, self.heads, self.kv_heads,
                                self.head_dim)
        else:
            shapes = kda_shapes(self.hidden, self.kda_heads,
                                self.kda_head_dim, self.gate_rank,
                                self.conv_kernel)
        return _Params(shapes, dtype=self.dtype, name=name)

    @nn.compact
    def __call__(self, token_ids, train: bool = True):
        del train                       # no dropout in the family
        from tpu_hc_bench.parallel.sequence import dense_attention

        b, s = token_ids.shape
        x = nn.Embed(self.vocab_size, self.hidden, dtype=self.dtype,
                     param_dtype=self.dtype, name="tok_embed")(token_ids)
        for l in range(self.num_layers):
            u = RMSNorm(eps=self.eps, dtype=self.dtype,
                        name=f"layer_{l}_norm1")(x)
            p = self.mixer_params(l, name=f"layer_{l}_mixer")()
            if self.mixer_kind(l) == "gqa":
                q, k, v = gqa_inputs(p, u, self.heads, self.kv_heads)
                group = self.heads // self.kv_heads
                ctx = dense_attention(q, jnp.repeat(k, group, axis=2),
                                      jnp.repeat(v, group, axis=2),
                                      causal=True)
                x = x + gqa_output(p, ctx, u)
            else:
                x = x + self.kda_forward(p, u)
            h = RMSNorm(eps=self.eps, dtype=self.dtype,
                        name=f"layer_{l}_norm2")(x)
            x = x + self.moe_module(name=f"layer_{l}_moe")(h)
        x = RMSNorm(eps=self.eps, dtype=self.dtype, name="final_norm")(x)
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (self.hidden, self.vocab_size), self.dtype)
        return jnp.einsum("bsh,hv->bsv", x.astype(self.dtype),
                          head.astype(self.dtype),
                          preferred_element_type=jnp.float32)

    # --- the functional seams serve/decode.py re-walks the tree through

    @nn.nowrap
    def pp_embed(self, params: dict, token_ids, rng):
        return params["tok_embed"]["embedding"].astype(
            self.dtype)[token_ids], rng

    @nn.nowrap
    def pp_head(self, params: dict, x):
        x = RMSNorm(eps=self.eps, dtype=self.dtype).apply(
            {"params": params["final_norm"]}, x)
        return jnp.einsum("bsh,hv->bsv", x.astype(self.dtype),
                          params["lm_head"].astype(self.dtype),
                          preferred_element_type=jnp.float32)

    @nn.nowrap
    def kda_forward(self, p, u):
        """A KDA mixer over whole sequences from a zero state (the
        training-shaped forward)."""
        b, s, _ = u.shape
        n = self.kda_heads * self.kda_head_dim
        tail = jnp.zeros((b, self.conv_kernel - 1, 3 * n), u.dtype)
        q, k, v, g, beta, _ = kda_inputs(p, u, tail, self.kda_heads,
                                         self.neg_eigval)
        S0 = jnp.zeros((self.kda_heads, self.kda_head_dim,
                        self.kda_head_dim), jnp.float32)
        o = jax.vmap(lambda *a: kda_sequence(*a, S0)[0])(q, k, v, g, beta)
        return kda_output(p, o, u, self.eps)


def _factory(**sizes):
    def create(num_classes: int = 0, dtype=jnp.float32,
               attention_impl: str = "dense", max_len: int | None = None,
               remat: bool = False, seq_axis: str | None = None):
        del num_classes, max_len        # no position table: any length
        if attention_impl != "dense" or remat or seq_axis is not None:
            raise ValueError(
                "the solar_open2 members run dense attention on one "
                "device without recomputation (serve lane first)")
        return SolarOpen2LM(dtype=dtype, **sizes)

    return create


# one chip's share of an EP8 deployment at the published widths: experts
# 0-39 of 320, rows 0-24,575 of the vocabulary, one period of the depth
solar_open2_250b_ep8 = _factory()

# the CPU tests' size: the same pattern, every kind of part present
TINY = dict(vocab_size=256, hidden=64, heads=4, kv_heads=2, head_dim=16,
            kda_heads=4, kda_head_dim=16, gate_rank=8, n_routed=16,
            experts_held=(0, 2), top_k=4, expert_ffn=32, shared_ffn=32)
solar_open2_tiny = _factory(**TINY)
