"""Plain reference for GPT-2 (Radford et al. 2019; the sizes of
openai-community/gpt2-medium's ``config.json``): the forward pass, the
next-token loss and, through ``jax.grad``, its gradients, in
straightforward ``jax.numpy`` — no kernels, no cache, no batching tricks.
It imports nothing of the program under test and takes nothing the
program has made: weights come from ``make_params`` (the seed), inputs
from the benchmark's traffic generator.

Parameter layout is the published checkpoint's (``c_attn`` fused q|k|v
along the last axis, heads contiguous), with the layers stacked on a
leading axis so one ``lax.scan`` body compiles for any depth.

Departures from the published description, each noted:
- no dropout, and the norms' epsilon the program runs (the
  configuration file states both under ``assumed``, beside the
  ``published`` values);
- ``precision`` names how matmul operands are rounded: ``"f32"`` is
  float32 at ``highest`` (the reference proper), ``"bf16"`` and
  ``"fp8"`` are lower-precision controls with matmul operands rounded to
  that type, float32 accumulation and everything between operations in
  float32.  ``"bf16"`` is what a TPU's float32 matmul at default
  precision is (one bfloat16 pass, float32 accumulation): the precision
  the serve arm states, and the mode its served tokens are held
  against; ``"bf16_all"`` also holds every tensor between operations in
  bfloat16, the way a model served in bfloat16 does: that arm's
  lower-precision control.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LAYER_LEAVES = (
    # name, shape as a function of (H, F), kind
    ("ln_1_g", lambda H, F: (H,), "ones"),
    ("ln_1_b", lambda H, F: (H,), "normal"),
    ("c_attn_w", lambda H, F: (H, 3 * H), "normal"),
    ("c_attn_b", lambda H, F: (3 * H,), "normal"),
    ("c_proj_w", lambda H, F: (H, H), "normal"),
    ("c_proj_b", lambda H, F: (H,), "normal"),
    ("ln_2_g", lambda H, F: (H,), "ones"),
    ("ln_2_b", lambda H, F: (H,), "normal"),
    ("fc_w", lambda H, F: (H, F), "normal"),
    ("fc_b", lambda H, F: (F,), "normal"),
    ("proj_w", lambda H, F: (F, H), "normal"),
    ("proj_b", lambda H, F: (H,), "normal"),
)
TOP_LEAVES = (
    ("wte", lambda V, P, H: (V, H), "normal"),
    ("wpe", lambda V, P, H: (P, H), "normal"),
    ("ln_f_g", lambda V, P, H: (H,), "ones"),
    ("ln_f_b", lambda V, P, H: (H,), "normal"),
)

def sizes(cfg: dict) -> dict:
    H = cfg["n_embd"]
    a = cfg["assumed"]
    return {"V": cfg["vocab_size"], "P": cfg["n_positions"], "H": H,
            "F": a.get("n_inner") or 4 * H, "L": cfg["n_layer"],
            "heads": cfg["n_head"], "eps": a["layer_norm_epsilon"]}


def seed_key(seed: int):
    """Any whole seed up to 2**62 folds into one key (the driver's seeds
    pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf(key, shape, kind, std):
    x = std * jax.random.normal(key, shape, jnp.float32)
    return 1.0 + x if kind == "ones" else x


def leaf_values(cfg: dict, seed):
    """Every leaf from the seed, keyed ``(name, layer | None)``: the one
    place values are drawn, whatever layout they are handed out in.
    Traceable (``seed`` may be a traced int32 pair via ``seed_key``)."""
    z = sizes(cfg)
    key = seed if not isinstance(seed, int) else seed_key(seed)
    std = cfg["initializer_range"]
    out = {}
    for i, (name, shape, kind) in enumerate(TOP_LEAVES):
        out[(name, None)] = _leaf(jax.random.fold_in(key, i),
                                  shape(z["V"], z["P"], z["H"]), kind,
                                  std)
    for l in range(z["L"]):
        kl = jax.random.fold_in(key, 1000 + l)
        for i, (name, shape, kind) in enumerate(LAYER_LEAVES):
            out[(name, l)] = _leaf(jax.random.fold_in(kl, i),
                                   shape(z["H"], z["F"]), kind, std)
    return out


def make_params(cfg: dict, seed: int) -> dict:
    """The reference's own weights, on the device in one jitted call."""
    L = sizes(cfg)["L"]

    @jax.jit
    def build(key):
        leaves = leaf_values(cfg, key)
        p = {name: leaves[(name, None)] for name, _, _ in TOP_LEAVES}
        p["h"] = {name: jnp.stack([leaves[(name, l)] for l in range(L)])
                  for name, _, _ in LAYER_LEAVES}
        return p

    return build(seed_key(seed))


# ---------------------------------------------------------------------
# arithmetic


def _round_to(x, precision: str):
    if precision == "f32":
        return x
    if precision in ("bf16", "bf16_all"):
        return x.astype(jnp.bfloat16)
    if precision == "fp8":
        # e4m3 operands, carried in bf16 so the dot is defined everywhere
        return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    raise ValueError(f"unknown precision {precision!r}")


def _dot(eq: str, a, b, precision: str):
    return _store(jnp.einsum(eq, _round_to(a, precision),
                             _round_to(b, precision),
                             preferred_element_type=jnp.float32), precision)


def _store(x, precision: str):
    """``bf16_all`` keeps every tensor between operations in bfloat16 as
    well (weights, residual stream, norms, probabilities), the way a
    model served in bfloat16 holds them; reductions stay float32 inside
    an operation, as the hardware's do."""
    if precision == "bf16_all":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _ln(x, g, b, eps, precision):
    return _store(layer_norm(x, _store(g, precision), _store(b, precision),
                             eps), precision)


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, lp, heads: int, eps: float, precision: str):
    B, S, H = x.shape
    d = H // heads
    h = _ln(x, lp["ln_1_g"], lp["ln_1_b"], eps, precision)
    qkv = _store(_dot("bsh,hk->bsk", h, lp["c_attn_w"], precision)
                 + lp["c_attn_b"], precision)
    q, k, v = (t.reshape(B, S, heads, d) for t in jnp.split(qkv, 3, axis=-1))
    scores = _dot("bqnd,bknd->bnqk", q, k, precision) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = _store(jax.nn.softmax(scores, axis=-1), precision)
    ctx = _dot("bnqk,bknd->bqnd", probs, v, precision).reshape(B, S, H)
    x = _store(x + _dot("bsh,hk->bsk", ctx, lp["c_proj_w"], precision)
               + lp["c_proj_b"], precision)
    h = _ln(x, lp["ln_2_g"], lp["ln_2_b"], eps, precision)
    h = _store(gelu_new(_dot("bsh,hf->bsf", h, lp["fc_w"], precision)
                        + lp["fc_b"]), precision)
    return _store(x + _dot("bsf,fh->bsh", h, lp["proj_w"], precision)
                  + lp["proj_b"], precision)


def hidden_states(params, tokens, cfg: dict, precision: str = "f32",
                  remat: bool = False):
    """Final-norm hidden states ``[B, S, H]`` for token ids ``[B, S]``.
    ``remat`` keeps only each layer's input for the backward pass (the
    same arithmetic, run twice), so a float32 backward fits the chip."""
    z = sizes(cfg)
    S = tokens.shape[1]
    x = _store(params["wte"][tokens] + params["wpe"][:S][None], precision)

    def body(x, lp):
        return block(x, lp, z["heads"], z["eps"], precision), None

    x, _ = jax.lax.scan(jax.checkpoint(body) if remat else body, x,
                        params["h"])
    return _ln(x, params["ln_f_g"], params["ln_f_b"], z["eps"], precision)


def logits_of(params, hidden, precision: str = "f32"):
    """Tied output projection: ``hidden @ wte.T``."""
    return _dot("...h,vh->...v", hidden, params["wte"], precision)


def loss_fn(params, batch, cfg: dict, precision: str = "f32"):
    """Weighted mean next-token cross-entropy, as the train lane states
    it: ``batch = (tokens, targets, weights)``."""
    tokens, targets, weights = batch
    logits = logits_of(
        params, hidden_states(params, tokens, cfg, precision, remat=True),
        precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * weights) / jnp.maximum(jnp.sum(weights), 1.0)
