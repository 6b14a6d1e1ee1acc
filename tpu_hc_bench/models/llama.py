"""Llama-style decoder (RMSNorm + RoPE + SwiGLU + GQA) in Flax.

Beyond-reference member (the reference's only text config is BERT MLM —
SURVEY.md §2c): the modern decoder architecture family, so a user of this
framework finds current-generation LM building blocks alongside the
GPT-2/BERT classics.  TPU-first choices:

- **RoPE** is applied after the QK projections with positions from
  ``global_position_ids``, so it is sequence-parallel-aware for free
  (each seq shard rotates by its global offset).
- **GQA**: ``num_kv_heads < heads`` shrinks the KV projection params; the
  attention dispatch broadcasts KV heads to the query-head count
  (``kv_repeat``) — up front for the single-device impls, but *after or
  inside the collective* for ring/ulysses, so sequence parallelism moves
  only the un-repeated KV bytes over the fabric.  MXU work equals MHA;
  params and SP wire traffic shrink.
- **SwiGLU** gate/up/down projections are three MXU-shaped matmuls;
  RMSNorm statistics accumulate in f32 (bf16-safe).
- Untied LM head (Llama convention), computed with compute-dtype operands
  and f32 accumulation like the other families.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_hc_bench.models.bert import global_position_ids


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + self.eps)
        return (y * scale.astype(jnp.float32)).astype(self.dtype)


def apply_rope(x, positions, theta: float = 10000.0, inv_freq=None,
               scale: float = 1.0):
    """Rotary position embedding over the trailing head_dim.

    ``x``: [batch, seq, heads, head_dim]; ``positions``: [seq] global
    token positions shared across the batch (sequence-parallel shards
    pass their offset range), or [batch, seq] per-row positions (the
    serving lane's decode step, where every in-flight request sits at
    its own cache depth).  Split-half convention (rotate_half), f32
    trig, output in x's dtype.  ``inv_freq`` [head_dim / 2] replaces
    ``theta``'s frequencies (a scaled rotary such as YaRN), and ``scale``
    multiplies cos and sin (YaRN's attention factor).
    """
    d = x.shape[-1]
    half = d // 2
    if inv_freq is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    if angles.ndim == 2:                             # [S, half]
        cos = jnp.cos(angles)[None, :, None, :]      # [1, S, 1, half]
        sin = jnp.sin(angles)[None, :, None, :]
    else:                                            # [B, S, half]
        cos = jnp.cos(angles)[:, :, None, :]         # [B, S, 1, half]
        sin = jnp.sin(angles)[:, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


class LlamaAttention(nn.Module):
    """Causal self-attention with RoPE and grouped-query KV heads."""

    hidden: int
    heads: int
    num_kv_heads: int
    max_len: int
    dtype: Any = jnp.float32
    attention_impl: str = "dense"
    seq_axis: str | None = None

    @nn.compact
    def __call__(self, x):
        if self.heads % self.num_kv_heads:
            raise ValueError(
                f"heads={self.heads} not divisible by "
                f"num_kv_heads={self.num_kv_heads}")
        d = self.hidden // self.heads
        group = self.heads // self.num_kv_heads
        q = nn.DenseGeneral((self.heads, d), use_bias=False,
                            dtype=self.dtype, name="wq")(x)
        k = nn.DenseGeneral((self.num_kv_heads, d), use_bias=False,
                            dtype=self.dtype, name="wk")(x)
        v = nn.DenseGeneral((self.num_kv_heads, d), use_bias=False,
                            dtype=self.dtype, name="wv")(x)
        pos = global_position_ids(x.shape[1], self.seq_axis, self.max_len)
        q = apply_rope(q, pos)
        k = apply_rope(k, pos)
        # GQA: the dispatch broadcasts KV heads to the query-head count —
        # up front for single-device impls, after/inside the collective
        # for sequence-parallel ones (un-repeated KV bytes on the wire)
        from tpu_hc_bench.parallel.sequence import local_attention

        out = local_attention(q, k, v, impl=self.attention_impl,
                              axis_name=self.seq_axis, causal=True,
                              kv_repeat=group)
        return nn.DenseGeneral(self.hidden, axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, name="wo")(out)


class LlamaBlock(nn.Module):
    hidden: int
    heads: int
    num_kv_heads: int
    ffn: int
    max_len: int
    dtype: Any = jnp.float32
    attention_impl: str = "dense"
    seq_axis: str | None = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        del train  # Llama uses no dropout
        h = RMSNorm(dtype=self.dtype, name="attn_norm")(x)
        x = x + LlamaAttention(
            self.hidden, self.heads, self.num_kv_heads, self.max_len,
            dtype=self.dtype, attention_impl=self.attention_impl,
            seq_axis=self.seq_axis, name="attn")(h)
        h = RMSNorm(dtype=self.dtype, name="mlp_norm")(x)
        gate = nn.Dense(self.ffn, use_bias=False, dtype=self.dtype,
                        name="gate")(h)
        up = nn.Dense(self.ffn, use_bias=False, dtype=self.dtype,
                      name="up")(h)
        down = nn.Dense(self.hidden, use_bias=False, dtype=self.dtype,
                        name="down")(nn.silu(gate) * up)
        return x + down


class LlamaLM(nn.Module):
    vocab_size: int = 32000
    hidden: int = 2048
    num_layers: int = 16
    heads: int = 32
    num_kv_heads: int = 8
    ffn: int = 8192
    max_len: int = 2048
    dtype: Any = jnp.float32
    attention_impl: str = "dense"
    seq_axis: str | None = None
    remat: bool = False
    scan_layers: bool = False          # lax.scan over stacked layers: ONE
                                       # compiled layer body regardless of
                                       # depth — same program-size lever as
                                       # GPTLM.scan_layers (round 5: built
                                       # because llama_1b's UNROLLED 16-layer
                                       # 1.1B program is what the remote
                                       # compile helper 500s on; round-4
                                       # bisect: <=6 unrolled layers compile,
                                       # >=9 crash).  Param tree: layers/<..>
                                       # stacked [L, ...] instead of
                                       # layer_i/<..> — not interchangeable
                                       # with unrolled checkpoints, guarded
                                       # off TP/EP/PP by the driver.

    @nn.compact
    def __call__(self, token_ids, train: bool = True):
        x = nn.Embed(self.vocab_size, self.hidden, dtype=self.dtype,
                     name="tok_embed")(token_ids)
        block_cls = (nn.remat(LlamaBlock, static_argnums=(2,))
                     if self.remat else LlamaBlock)
        block_kw = dict(
            hidden=self.hidden, heads=self.heads,
            num_kv_heads=self.num_kv_heads, ffn=self.ffn,
            max_len=self.max_len, dtype=self.dtype,
            attention_impl=self.attention_impl, seq_axis=self.seq_axis)
        if self.scan_layers:
            # scan-over-layers: stacked params [L, ...], one compiled body
            # (no dropout in the family, but params rngs still split per
            # layer so each stacked slice initializes independently)
            scan = nn.scan(
                lambda module, carry, _: (module(carry, train), None),
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                length=self.num_layers)
            x, _ = scan(block_cls(**block_kw, name="layers"), x, None)
        else:
            for i in range(self.num_layers):
                x = block_cls(**block_kw, name=f"layer_{i}")(x, train)
        x = RMSNorm(dtype=self.dtype, name="final_norm")(x)
        head = self.param(
            "lm_head", nn.initializers.normal(0.02),
            (self.hidden, self.vocab_size))
        return jnp.einsum("bsh,hv->bsv", x.astype(self.dtype),
                          head.astype(self.dtype),
                          preferred_element_type=jnp.float32)

    # --- pipeline-parallel interface (parallel/pipeline.py) -------------
    # Same contract as GPTLM's: the PP step builder derives the stage
    # forward from these instead of hardcoding any family's wiring.

    @nn.nowrap
    def pp_layer_module(self) -> nn.Module:
        return LlamaBlock(
            self.hidden, self.heads, self.num_kv_heads, self.ffn,
            self.max_len, dtype=self.dtype,
            attention_impl=self.attention_impl)

    @nn.nowrap
    def pp_embed(self, params: dict, token_ids, rng):
        """Token embedding only (no positions here — RoPE rotates inside
        attention; no embed dropout in the Llama family)."""
        emb = params["tok_embed"]["embedding"]
        return emb.astype(self.dtype)[token_ids], rng

    @nn.nowrap
    def pp_head(self, params: dict, x):
        x = RMSNorm(dtype=self.dtype).apply(
            {"params": params["final_norm"]}, x)
        return jnp.einsum("bsh,hv->bsv", x.astype(self.dtype),
                          params["lm_head"].astype(self.dtype),
                          preferred_element_type=jnp.float32)


def llama_1b(num_classes: int = 0, dtype=jnp.float32,
             attention_impl: str = "dense", max_len: int | None = None,
             remat: bool = False, seq_axis: str | None = None,
             scan_layers: bool = False):
    """Llama-3.2-1B-shaped decoder (16L/2048H, 32q/8kv heads, SwiGLU
    8192, 32k vocab here to keep the head sane on one chip; ~1.1B
    params)."""
    del num_classes
    return LlamaLM(dtype=dtype, attention_impl=attention_impl,
                   max_len=max(2048, max_len or 0), remat=remat,
                   seq_axis=seq_axis, scan_layers=scan_layers)


def llama_tiny(num_classes: int = 0, dtype=jnp.float32,
               attention_impl: str = "dense", max_len: int | None = None,
               remat: bool = False, seq_axis: str | None = None,
               scan_layers: bool = False):
    """4-layer/128-hidden 8q/2kv variant for tests and CPU smoke runs."""
    del num_classes
    return LlamaLM(vocab_size=1024, hidden=128, num_layers=4, heads=8,
                   num_kv_heads=2, ffn=256, max_len=max(128, max_len or 0),
                   dtype=dtype, attention_impl=attention_impl, remat=remat,
                   seq_axis=seq_axis, scan_layers=scan_layers)
