"""Plain reference for the ``mellum`` model type (JetBrains/
Mellum2-12B-A2.5B-Instruct ``config.json``): the forward pass in
straightforward ``jax.numpy`` — attention as scores over every key with
the mask written out (a block of queries at a time, so that it fits at
the contexts served), every expert over every token weighed by its gate
(0 where it was not picked), no cache, no batching tricks, no kernels.
It imports nothing of the program under test and takes nothing the
program has made: weights come from ``make_params`` (the seed), inputs
from the benchmark's traffic generator.  The model is a serving stage of
the published model: its first ``num_hidden_layers`` layers (whole
periods of ``layer_types``) with the embedding and the head, every width,
every expert and every row of the vocabulary.

Layers (every norm RMSNorm; ``h = x + Attn(norm1(x))``, ``y = h +
MoE(norm2(h))``; final norm, untied head):

- attention: ``q = u Wq`` (heads x d), ``k, v = u Wk, u Wv`` (kv heads x
  d), no biases, no q/k norm; ``q`` and ``k`` rotated (split-half
  convention, float32 trig) by the layer type's ``rope_parameters``:
  ``sliding_attention`` the default rotary on ``rope_theta``,
  ``full_attention`` YaRN (frequencies ``f_i / factor * ramp_i + f_i *
  (1 - ramp_i)`` with ``ramp_i = clip((i - low) / (high - low), 0, 1)``,
  ``low = floor(d ln(original / (beta_fast 2 pi)) / (2 ln theta))``,
  ``high = ceil(d ln(original / (beta_slow 2 pi)) / (2 ln theta))``; cos
  and sin times ``attention_factor``); ``softmax(q k^T / sqrt(d)) v``,
  causal, a sliding layer's query at ``i`` seeing keys ``i -
  sliding_window < j <= i``; each kv head serves heads / kv heads query
  heads; then ``Wo``;
- MoE: ``p = softmax(u Wr)`` in float32 over all experts, the
  ``num_experts_per_tok`` largest picked, their weights ``p_e / sum over
  the picked`` (``norm_topk_prob``), ``y = sum over the picked of w_e
  Wdown_e(SiLU(Wgate_e u) * Wup_e u)``; no shared expert.

Every leaf is drawn from the seed in float32 and ROUNDED TO BFLOAT16
ONCE, so that the program (which holds its matrices in bfloat16) and this
reference hold equal numbers; the matrices stay in bfloat16 storage and
are widened where they are used.

``precision``: ``"f32"`` is float32 at ``highest`` throughout (the
reference proper).  ``"bf16"`` is the arithmetic the serve arm states:
matmul operands in bfloat16 with float32 accumulation and every tensor
between operations stored in bfloat16, but float32 for the router's
scores and softmax, norm statistics, the rotary's trig and rotation and
the attention softmax.  ``"fp8"`` is the lower-precision control: as
``"bf16"`` with both matmul operands rounded to e4m3 first.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512       # queries a block of the attention's scores


def _normal(mean):
    """``mean + initializer_range x normal``."""
    return lambda key, shape, std: mean + std * jax.random.normal(
        key, shape, jnp.float32)


# name -> (shape from sizes, draw, held in float32 by both sides)
LAYER_LEAVES = (
    ("norm1", lambda z: (z["H"],), _normal(1.0), True),
    ("wq", lambda z: (z["H"], z["heads"] * z["d"]), _normal(0), False),
    ("wk", lambda z: (z["H"], z["kvh"] * z["d"]), _normal(0), False),
    ("wv", lambda z: (z["H"], z["kvh"] * z["d"]), _normal(0), False),
    ("wo", lambda z: (z["heads"] * z["d"], z["H"]), _normal(0), False),
    ("norm2", lambda z: (z["H"],), _normal(1.0), True),
    ("router", lambda z: (z["H"], z["E"]), _normal(0), True),
    ("exp_gate", lambda z: (z["E"], z["H"], z["F"]), _normal(0), False),
    ("exp_up", lambda z: (z["E"], z["H"], z["F"]), _normal(0), False),
    ("exp_down", lambda z: (z["E"], z["F"], z["H"]), _normal(0), False),
)
TOP_LEAVES = (
    ("embed", lambda z: (z["V"], z["H"]), _normal(0), False),
    ("head", lambda z: (z["H"], z["V"]), _normal(0), False),
    ("final_norm", lambda z: (z["H"],), _normal(1.0), True),
)


def sizes(cfg: dict) -> dict:
    if not all(t == "sparse" for t in cfg["mlp_layer_types"]):
        raise ValueError("every layer's FFN routed (mlp_layer_types sparse)")
    rope = cfg["rope_parameters"]
    return {
        "H": cfg["hidden_size"], "L": cfg["num_hidden_layers"],
        "heads": cfg["num_attention_heads"], "d": cfg["head_dim"],
        "kvh": cfg["num_key_value_heads"], "V": cfg["vocab_size"],
        "E": cfg["num_experts"], "k": cfg["num_experts_per_tok"],
        "F": cfg["moe_intermediate_size"], "eps": cfg["rms_norm_eps"],
        "W": cfg["sliding_window"], "norm_topk": cfg["norm_topk_prob"],
        "types": tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
        "rope": rope, "std": cfg["assumed"]["initializer_range"],
    }


def is_window(z: dict, l: int) -> bool:
    return z["types"][l] == "sliding_attention"


def seed_key(seed: int):
    """Any whole seed up to 2**62 folds into one key (a benchmark's seeds
    pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf(key, shape, draw, f32: bool, std: float):
    x = draw(key, shape, std).astype(jnp.bfloat16)  # rounded ONCE
    return x.astype(jnp.float32) if f32 else x


def leaf_values(cfg: dict, seed):
    """Every leaf from the seed, keyed ``(name, layer | None)``: the one
    place values are drawn, whatever layout they are handed out in.
    Traceable (``seed`` may be a key from ``seed_key``)."""
    z = sizes(cfg)
    key = seed if not isinstance(seed, int) else seed_key(seed)
    out = {}
    for i, (name, shape, draw, f32) in enumerate(TOP_LEAVES):
        out[(name, None)] = _leaf(jax.random.fold_in(key, i), shape(z),
                                  draw, f32, z["std"])
    for l in range(z["L"]):
        kl = jax.random.fold_in(key, 1000 + l)
        for i, (name, shape, draw, f32) in enumerate(LAYER_LEAVES):
            out[(name, l)] = _leaf(jax.random.fold_in(kl, i), shape(z),
                                   draw, f32, z["std"])
    return out


def make_params(cfg: dict, seed: int) -> dict:
    """The reference's own weights, on the device in one jitted call."""
    z = sizes(cfg)

    @jax.jit
    def build(key):
        leaves = leaf_values(cfg, key)
        p = {name: leaves[(name, None)] for name, _, _, _ in TOP_LEAVES}
        p["layers"] = [{name: leaves[(name, l)]
                        for name, _, _, _ in LAYER_LEAVES}
                       for l in range(z["L"])]
        return p

    return build(seed_key(seed))


# ---------------------------------------------------------------------
# positions


def inv_freq(z: dict, l: int) -> tuple:
    """``(frequencies [d / 2], cos/sin factor)`` of layer ``l``'s rotary,
    as ``rope_parameters`` gives them for its type."""
    rp = z["rope"][z["types"][l]]
    d, theta = z["d"], rp["rope_theta"]
    f = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if rp["rope_type"] == "default":
        return f, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"no rotary {rp['rope_type']!r} here")
    orig = rp["original_max_position_embeddings"]
    low = max(math.floor(d * math.log(orig / (rp["beta_fast"] * 2 * math.pi))
                         / (2 * math.log(theta))), 0)
    high = min(math.ceil(d * math.log(orig / (rp["beta_slow"] * 2 * math.pi))
                         / (2 * math.log(theta))), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (f / rp["factor"] * ramp + f * (1 - ramp),
            rp["attention_factor"])


def rotate(x, positions, freq, factor):
    """Split-half rotary over the last axis of ``x`` [B, S, n, d], float32
    trig and rotation."""
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        freq, jnp.float32)                                  # [S, d/2]
    cos = (jnp.cos(angles) * factor)[None, :, None, :]
    sin = (jnp.sin(angles) * factor)[None, :, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------------
# arithmetic


def _round_to(x, precision: str):
    if precision == "f32":
        return x.astype(jnp.float32)
    if precision == "bf16":
        return x.astype(jnp.bfloat16)
    if precision == "fp8":
        # e4m3 operands, carried in bf16 so the dot is defined everywhere
        return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    raise ValueError(f"unknown precision {precision!r}")


def _store(x, precision: str):
    """Below float32, every tensor between operations is held in
    bfloat16: rounded by ``reduce_precision``, which the compiler keeps
    (a convert to bfloat16 and straight back can be fused away on the
    TPU)."""
    if precision == "f32":
        return x
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _mm(eq: str, a, w, precision: str):
    return _store(jnp.einsum(eq, _round_to(a, precision),
                             _round_to(w, precision), precision=_HI,
                             preferred_element_type=jnp.float32), precision)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def silu(x):
    return x * jax.nn.sigmoid(x)


def attention(u, lp, z, l: int, precision):
    B, S, _ = u.shape
    heads, kvh, d = z["heads"], z["kvh"], z["d"]
    pos = jnp.arange(S)
    freq, factor = inv_freq(z, l)
    q = _mm("bsh,hn->bsn", u, lp["wq"], precision).reshape(B, S, heads, d)
    k = _mm("bsh,hn->bsn", u, lp["wk"], precision).reshape(B, S, kvh, d)
    v = _mm("bsh,hn->bsn", u, lp["wv"], precision).reshape(B, S, kvh, d)
    q = _store(rotate(q, pos, freq, factor), precision)
    k = _store(rotate(k, pos, freq, factor), precision)
    window = z["W"] if is_window(z, l) else S
    nb = -(-S // QUERY_BLOCK)
    qb = jnp.pad(q, ((0, 0), (0, nb * QUERY_BLOCK - S), (0, 0), (0, 0)))
    qb = qb.reshape(B, nb, QUERY_BLOCK, heads, d)

    def one_block(i):
        """Every query of block ``i`` over every key, the mask written
        out: ``[B, QUERY_BLOCK, heads, d]``."""
        qi = jax.lax.dynamic_index_in_dim(qb, i, axis=1, keepdims=False)
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        seen = ((pos[None, :] <= qpos[:, None])
                & (pos[None, :] > qpos[:, None] - window))

        def one_kv_head(j):
            """The heads/kvh query heads that read kv head ``j``."""
            qg = jax.lax.dynamic_slice_in_dim(
                qi, j * (heads // kvh), heads // kvh, axis=2)
            kj = jax.lax.dynamic_index_in_dim(k, j, axis=2, keepdims=False)
            vj = jax.lax.dynamic_index_in_dim(v, j, axis=2, keepdims=False)
            scores = _mm("bqgd,bkd->bgqk", qg, kj, precision) / math.sqrt(d)
            scores = jnp.where(seen[None, None], scores, -jnp.inf)
            probs = _store(jax.nn.softmax(scores, axis=-1), precision)
            return _mm("bgqk,bkd->bqgd", probs, vj, precision)

        ctx = jax.lax.map(one_kv_head, jnp.arange(kvh))  # [kvh, B, q, g, d]
        return jnp.moveaxis(ctx, 0, 2).reshape(B, QUERY_BLOCK, heads, d)

    ctx = jax.lax.map(one_block, jnp.arange(nb))        # [nb, B, q, h, d]
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(B, nb * QUERY_BLOCK, heads * d)
    return _mm("bsn,nh->bsh", ctx[:, :S], lp["wo"], precision)


def moe(u, lp, z, precision):
    """The routed sum over the picked experts, every expert computed over
    every token and weighed by its gate (0 where not picked)."""
    p = jax.nn.softmax(jnp.einsum(
        "bsh,he->bse", u.astype(jnp.float32), lp["router"].astype(
            jnp.float32), precision=_HI), axis=-1)
    top, picked = jax.lax.top_k(p, z["k"])
    if z["norm_topk"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(picked, z["E"], dtype=jnp.float32)
                     * top[..., None], axis=-2)             # [B, S, E]

    def add_expert(y, e):
        act = _store(silu(_mm("bsh,hf->bsf", u, lp["exp_gate"][e],
                              precision))
                     * _mm("bsh,hf->bsf", u, lp["exp_up"][e], precision),
                     precision)
        out = _mm("bsf,fh->bsh", act, lp["exp_down"][e], precision)
        w_e = jax.lax.dynamic_index_in_dim(weight, e, axis=2)
        return y + _store(w_e * out, precision), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), jnp.arange(z["E"]))
    return y


def hidden_states(params, tokens, cfg: dict, precision: str = "f32"):
    """Final-norm hidden states ``[B, S, H]`` for token ids ``[B, S]``."""
    z = sizes(cfg)
    x = params["embed"][tokens].astype(jnp.float32)
    for l, lp in enumerate(params["layers"]):
        u = _store(rms_norm(x, lp["norm1"], z["eps"]), precision)
        x = _store(x + attention(u, lp, z, l, precision), precision)
        h = _store(rms_norm(x, lp["norm2"], z["eps"]), precision)
        x = _store(x + moe(h, lp, z, precision), precision)
    return _store(rms_norm(x, params["final_norm"], z["eps"]), precision)


def logits_of(params, hidden, precision: str = "f32"):
    """Untied output projection over the whole vocabulary."""
    return jnp.einsum("...h,hv->...v", _round_to(hidden, precision),
                      _round_to(params["head"], precision), precision=_HI,
                      preferred_element_type=jnp.float32)
