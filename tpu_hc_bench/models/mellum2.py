"""Sliding-window / full-attention decoder with softmax-routed SwiGLU
experts (the ``mellum`` model type: JetBrains/Mellum2-12B-A2.5B-Instruct
``config.json``).

One period of the layer pattern is four layers: three that attend over a
sliding window of ``window`` positions and one over the whole sequence
(``layer_types``).  Every layer's FFN is a routed mixture
(``models/moe.MoEFFN``'s share path with ``score="softmax"``: the top-k of
a softmax over all experts, renormalised to sum to 1, SwiGLU experts, no
shared expert).  Pre-norm residuals, RMSNorm, untied head.

Layer ``l``, ``u = RMSNorm(x)``::

    q, k, v = u Wq [heads, d], u Wk [kv_heads, d], u Wv [kv_heads, d]
    q, k = RoPE_l(q), RoPE_l(k)          # split-half, float32 trig
    ctx = softmax(q k^T / sqrt(d) + mask_l) v     # GQA
    x = x + ctx Wo
    h = RMSNorm(x);  p = softmax(h W_router)      # float32, all experts
    x = x + sum over the top-k e of p_e / sum(p_top) Down_e(SiLU(Gate_e h) * Up_e h)

``mask_l`` is causal; on a window layer a query at ``i`` sees keys ``i -
window < j <= i`` (``window`` keys, itself included).  ``RoPE_l`` is the
default rotary on window layers and YaRN on full layers
(``yarn_inv_freq``: frequencies past the original context interpolated
by ``factor``, the ramp between ``yarn_bounds``, cos and sin times the
attention factor).

The layers' mathematics are plain functions over their parameter
subtrees (``attn_inputs``, ``attn_output``, the experts' ``MoEFFN``):
the flax module below declares the parameters and calls them, and
``serve/decode.py`` calls the same functions over the same subtrees
against its cache tree.

Stated arithmetic: parameters and the activations between operations in
``dtype`` (bfloat16 when served so), float32 accumulation; float32 for
norm statistics, the rotary's trig and rotation, the router's scores and
softmax, the attention softmax.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tpu_hc_bench.models.llama import RMSNorm, apply_rope
from tpu_hc_bench.models.moe import MoEFFN
from tpu_hc_bench.models.solar_open2 import _Params, _proj

LAYER_TYPES = tuple("full_attention" if l % 4 == 3 else "sliding_attention"
                    for l in range(28))
# rope_parameters.full_attention of the published config
YARN = dict(factor=16.0, original=8192, beta_fast=32.0, beta_slow=1.0,
            attention_factor=1.2772588722239782)


def rope_inv_freq(d: int, theta: float) -> np.ndarray:
    """The default rotary's frequencies ``theta^(-2i/d)``, i < d/2."""
    return theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)


def yarn_bounds(d: int, theta: float, original: int, beta_fast: float,
                beta_slow: float) -> tuple:
    """YaRN's ramp: the frequency indices below ``low`` keep their
    frequency (they turn more than ``beta_fast`` times over the original
    context), those from ``high`` on are interpolated by the factor
    (fewer than ``beta_slow`` turns)."""
    def index(turns):
        return d * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    return (max(math.floor(index(beta_fast)), 0),
            min(math.ceil(index(beta_slow)), d - 1))


def yarn_inv_freq(d: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """``f_i / factor * ramp_i + f_i * (1 - ramp_i)``, ``ramp_i =
    clip((i - low) / (high - low), 0, 1)``."""
    f = rope_inv_freq(d, theta)
    low, high = yarn_bounds(d, theta, original, beta_fast, beta_slow)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return f / factor * ramp + f * (1 - ramp)


def attn_inputs(p, u, heads: int, kv_heads: int, positions, rope):
    """``q`` [b, s, heads, d], ``k``, ``v`` [b, s, kv_heads, d]; ``q`` and
    ``k`` rotated at ``positions`` ([b, s]) by ``rope = (inv_freq,
    scale)``."""
    b, s, _ = u.shape
    inv_freq, scale = rope
    q = _proj(u, p["wq"]).reshape(b, s, heads, -1)
    k = _proj(u, p["wk"]).reshape(b, s, kv_heads, -1)
    v = _proj(u, p["wv"]).reshape(b, s, kv_heads, -1)
    return (apply_rope(q, positions, inv_freq=inv_freq, scale=scale),
            apply_rope(k, positions, inv_freq=inv_freq, scale=scale), v)


def attn_output(p, ctx):
    """``ctx Wo``; ``ctx`` [b, s, heads, d]."""
    b, s, heads, d = ctx.shape
    return _proj(ctx.reshape(b, s, heads * d), p["wo"])


def attn_shapes(hidden: int, heads: int, kv_heads: int, d: int):
    return (("wq", (hidden, heads * d), "matrix"),
            ("wk", (hidden, kv_heads * d), "matrix"),
            ("wv", (hidden, kv_heads * d), "matrix"),
            ("wo", (heads * d, hidden), "matrix"))


class Mellum2LM(nn.Module):
    """The decoder at every published width; ``layer_types`` sets the
    depth (whole periods)."""

    vocab_size: int = 98304
    hidden: int = 2304
    layer_types: tuple = LAYER_TYPES
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    n_experts: int = 64
    top_k: int = 8
    expert_ffn: int = 896
    window: int = 1024
    rope_theta: float = 500000.0
    yarn: tuple = tuple(YARN.items())
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def windowed(self, l: int) -> bool:
        return self.layer_types[l] == "sliding_attention"

    @nn.nowrap
    def rope(self, l: int) -> tuple:
        """``(inv_freq, scale)`` of layer ``l``'s rotary."""
        if self.windowed(l):
            return rope_inv_freq(self.head_dim, self.rope_theta), 1.0
        y = dict(self.yarn)
        return (yarn_inv_freq(self.head_dim, self.rope_theta, y["factor"],
                              y["original"], y["beta_fast"], y["beta_slow"]),
                y["attention_factor"])

    @nn.nowrap
    def moe_module(self, name=None) -> MoEFFN:
        return MoEFFN(
            self.hidden, self.expert_ffn, self.n_experts, top_k=self.top_k,
            dtype=self.dtype, impl="ragged", score="softmax", gated=True,
            param_dtype=self.dtype, name=name)

    @nn.compact
    def __call__(self, token_ids, train: bool = True):
        del train                       # no dropout in the family
        from tpu_hc_bench.parallel.sequence import dense_attention

        b, s = token_ids.shape
        group = self.heads // self.kv_heads
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        x = nn.Embed(self.vocab_size, self.hidden, dtype=self.dtype,
                     param_dtype=self.dtype, name="tok_embed")(token_ids)
        for l in range(self.num_layers):
            u = RMSNorm(eps=self.eps, dtype=self.dtype,
                        name=f"layer_{l}_norm1")(x)
            p = _Params(attn_shapes(self.hidden, self.heads, self.kv_heads,
                                    self.head_dim), dtype=self.dtype,
                        name=f"layer_{l}_mixer")()
            q, k, v = attn_inputs(p, u, self.heads, self.kv_heads, positions,
                                  self.rope(l))
            ctx = dense_attention(
                q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
                causal=True, window=self.window if self.windowed(l) else None)
            x = x + attn_output(p, ctx)
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


def _factory(**sizes):
    def create(num_classes: int = 0, dtype=jnp.float32,
               attention_impl: str = "dense", max_len: int | None = None,
               remat: bool = False, seq_axis: str | None = None):
        del num_classes, max_len        # rotary positions: any length
        if attention_impl != "dense" or remat or seq_axis is not None:
            raise ValueError(
                "the mellum2 members run on one device without "
                "recomputation (serve lane first)")
        return Mellum2LM(dtype=dtype, **sizes)

    return create


# a serving stage of two whole periods at every published width: 8 of
# the 28 layers, every expert, every row of the vocabulary
mellum2_12b_a2_5b_8l = _factory(layer_types=LAYER_TYPES[:8])

# the CPU tests' size: both kinds of layer, a window a page-sized ring
# wraps within a few decode steps
TINY = dict(vocab_size=256, hidden=64, layer_types=LAYER_TYPES[:4], heads=4,
            kv_heads=2, head_dim=16, n_experts=8, top_k=2, expert_ffn=32,
            window=8)
mellum2_tiny = _factory(**TINY)
