"""Plain reference for the ``granitemoehybrid`` model type with no
experts (ibm-granite/granite-4.0-h-micro ``config.json``; the Mamba-2
layer as the ``mamba_*`` keys and Mamba-2's released SSD layer give it):
the forward pass in straightforward ``jax.numpy`` — the state-space
recurrence token by token as it is written, no chunking, no cache, no
batching tricks, no kernels.  It imports nothing of the program under
test and takes nothing the program has made: weights come from
``make_params`` (the seed), inputs from the benchmark's traffic
generator.  The model is the whole published model: every layer, every
width and every row of the vocabulary.

Layers (every norm RMSNorm; ``h = x + 0.22 Mixer(norm1(x))``, ``y = h +
0.22 MLP(norm2(h))``; ``x_0 = 12 E[tok]``; final norm; the head is the
embedding's transpose, the logits divided by ``logits_scaling``):

- ``layer_types`` "attention": ``q = u Wq`` (heads x d), ``k, v = u Wk,
  u Wv`` (kv heads x d), no position encoding, causal ``softmax(q k^T x
  attention_multiplier) v`` with each kv head serving heads/kv_heads
  query heads, then ``Wo``;
- "mamba" (one group): ``[z | xBC | dt] = u W_in``, ``xBC = SiLU(conv4(
  xBC) + b)`` split into ``x`` (heads x P), ``B``, ``C`` (N each),
  ``D_t = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, per head
  ``h_t = exp(D_t A) h_{t-1} + D_t x_t B_t^T``, ``y_t = h_t C_t + D x_t``,
  output ``W_out RMSNorm(y * SiLU(z))`` (the gate before the norm, over
  all of d_inner);
- MLP: ``(SiLU(u W_a) * u W_b) W_o`` with ``[W_a | W_b]`` one matrix.

Departures from the published model, each a choice of weights and none a
width: the weights are random from the seed (below), not trained; the
published ``time_step_limit`` is (0, inf), so ``D_t`` is not clamped here
either.

Weights: drawn from the seed in float32 and ROUNDED TO BFLOAT16 ONCE, so
that the program (which holds its matrices in bfloat16) and this
reference hold equal numbers; the matrices stay in bfloat16 storage and
are widened where they are used (6.4 GB at the published size, which fits
the chip once the engine is freed).  ``A_log``, ``dt_bias`` and ``D``
follow Mamba-2's published initialisation (``A`` uniform in [1, 16],
``dt_bias`` the inverse softplus of a step drawn log-uniform in [1e-3,
1e-1]; ``D`` is 1 there, and 1 + normal(0, 0.1) here, so that a fault in
how it is applied a head is not hidden by a constant).

``precision``: ``"f32"`` is float32 at ``highest`` throughout (the
reference proper).  ``"bf16"`` is the arithmetic the serve arm states:
matmul operands in bfloat16 with float32 accumulation and every tensor
between operations stored in bfloat16, but float32 for norm statistics,
softmax, ``D_t``, ``exp(D_t A)`` and the state ``h``.  ``"fp8"`` is the
lower-precision control: as ``"bf16"`` with both matmul operands rounded
to e4m3 first.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _normal(mean, sd):
    """``mean + sd x normal``; ``sd`` "w" is the initializer range."""
    return lambda key, shape, std: mean + (std if sd == "w" else sd) * (
        jax.random.normal(key, shape, jnp.float32))


def _a_log(key, shape, std):
    """Mamba-2: ``A`` uniform in [1, 16], stored as its log."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _dt_bias(key, shape, std):
    """Mamba-2: a step drawn log-uniform in [1e-3, 1e-1], stored as its
    inverse softplus (``softplus(dt_bias)`` is the step)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


# name -> (shape from sizes, draw, held in float32 by both sides)
MAMBA_LEAVES = (
    ("in_proj", lambda z: (z["H"], z["di"] + z["conv"] + z["mh"]),
     _normal(0, "w"), False),
    ("conv_w", lambda z: (z["K"], z["conv"]), _normal(0, 0.5), True),
    ("conv_b", lambda z: (z["conv"],), _normal(0, 0.1), True),
    ("dt_bias", lambda z: (z["mh"],), _dt_bias, True),
    ("A_log", lambda z: (z["mh"],), _a_log, True),
    ("D", lambda z: (z["mh"],), _normal(1.0, 0.1), True),
    ("norm", lambda z: (z["di"],), _normal(1.0, "w"), True),
    ("out_proj", lambda z: (z["di"], z["H"]), _normal(0, "w"), False),
)
ATTN_LEAVES = (
    ("wq", lambda z: (z["H"], z["heads"] * z["d"]), _normal(0, "w"), False),
    ("wk", lambda z: (z["H"], z["kvh"] * z["d"]), _normal(0, "w"), False),
    ("wv", lambda z: (z["H"], z["kvh"] * z["d"]), _normal(0, "w"), False),
    ("wo", lambda z: (z["heads"] * z["d"], z["H"]), _normal(0, "w"), False),
)
BLOCK_LEAVES = (
    ("norm1", lambda z: (z["H"],), _normal(1.0, "w"), True),
    ("norm2", lambda z: (z["H"],), _normal(1.0, "w"), True),
    ("mlp_in", lambda z: (z["H"], 2 * z["F"]), _normal(0, "w"), False),
    ("mlp_out", lambda z: (z["F"], z["H"]), _normal(0, "w"), False),
)
TOP_LEAVES = (
    ("embed", lambda z: (z["V"], z["H"]), _normal(0, "w"), False),
    ("final_norm", lambda z: (z["H"],), _normal(1.0, "w"), True),
)


def sizes(cfg: dict) -> dict:
    if cfg["mamba_n_groups"] != 1:
        raise ValueError("one group of B and C (mamba_n_groups 1) only")
    H, mh, P = cfg["hidden_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    N = cfg["mamba_d_state"]
    di = cfg["mamba_expand"] * H
    if di != mh * P:
        raise ValueError(f"d_inner {di} != heads x d_head {mh * P}")
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return {
        "H": H, "L": cfg["num_hidden_layers"],
        "heads": cfg["num_attention_heads"],
        "d": H // cfg["num_attention_heads"],
        "kvh": cfg["num_key_value_heads"], "V": cfg["vocab_size"],
        "F": cfg["shared_intermediate_size"],
        "mh": mh, "P": P, "N": N, "di": di, "conv": di + 2 * N,
        "K": cfg["mamba_d_conv"], "chunk": cfg["mamba_chunk_size"],
        "eps": cfg["rms_norm_eps"],
        "attn": tuple(l for l, t in enumerate(types) if t == "attention"),
        "emb_mult": cfg["embedding_multiplier"],
        "res_mult": cfg["residual_multiplier"],
        "attn_mult": cfg["attention_multiplier"],
        "logit_scale": cfg["logits_scaling"],
        "std": cfg["assumed"]["initializer_range"],
    }


def is_attn(z: dict, l: int) -> bool:
    return l in z["attn"]


def layer_leaves(z: dict, l: int):
    return (ATTN_LEAVES if is_attn(z, l) else MAMBA_LEAVES) + BLOCK_LEAVES


def seed_key(seed: int):
    """Any whole seed up to 2**62 folds into one key (the driver's seeds
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
        for i, (name, shape, draw, f32) in enumerate(layer_leaves(z, l)):
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
                        for name, _, _, _ in layer_leaves(z, l)}
                       for l in range(z["L"])]
        # the head's divisor rides with the weights: ``logits_of`` is
        # handed the weights alone
        p["logits_scaling"] = jnp.float32(z["logit_scale"])
        return p

    return build(seed_key(seed))


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
    (on the TPU a convert to bfloat16 and straight back can be fused
    away, and the reference would then err less than the arithmetic it
    states: PERF.md, PR 35)."""
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


def attention(u, lp, z, precision):
    B, S, _ = u.shape
    heads, kvh, d = z["heads"], z["kvh"], z["d"]
    q = _mm("bsh,hn->bsn", u, lp["wq"], precision).reshape(B, S, heads, d)
    k = _mm("bsh,hn->bsn", u, lp["wk"], precision).reshape(B, S, kvh, d)
    v = _mm("bsh,hn->bsn", u, lp["wv"], precision).reshape(B, S, kvh, d)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def one_kv_head(j):
        """The heads/kvh query heads that read kv head ``j``."""
        qg = jax.lax.dynamic_slice_in_dim(
            q, j * (heads // kvh), heads // kvh, axis=2)
        kj = jax.lax.dynamic_index_in_dim(k, j, axis=2, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(v, j, axis=2, keepdims=False)
        scores = _mm("bqgd,bkd->bgqk", qg, kj, precision) * z["attn_mult"]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = _store(jax.nn.softmax(scores, axis=-1), precision)
        return _mm("bgqk,bkd->bqgd", probs, vj, precision)

    ctx = jax.lax.map(one_kv_head, jnp.arange(kvh))     # [kvh, B, S, g, d]
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(B, S, heads * d)
    return _mm("bsn,nh->bsh", ctx, lp["wo"], precision)


def causal_conv(x, w, b):
    """Depthwise causal convolution over time, plus a bias: ``y_t = b +
    sum_i w[i] x_{t-(K-1)+i}``, zeros before the sequence's start."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return b + sum(xp[:, i:i + S] * w[i] for i in range(K))


def ssm_scan(x, Bm, Cm, dt, A):
    """The recurrence as written, one token at a time from ``h_0 = 0``:
    ``x`` [S, heads, P], ``Bm``, ``Cm`` [S, N], ``dt`` [S, heads], ``A``
    [heads]; float32 throughout.  Returns ``y`` [S, heads, P] (without
    the ``D`` skip)."""
    heads, P = x.shape[1], x.shape[2]

    def step(h, xs):
        x_t, b_t, c_t, dt_t = xs
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return h, jnp.einsum("hpn,n->hp", h, c_t, precision=_HI)

    _, y = jax.lax.scan(step, jnp.zeros((heads, P, Bm.shape[1]),
                                        jnp.float32), (x, Bm, Cm, dt))
    return y


def mamba(u, lp, z, precision):
    B, S, _ = u.shape
    di, conv = z["di"], z["conv"]
    zxbcdt = _mm("bsh,hn->bsn", u, lp["in_proj"], precision)
    gate = zxbcdt[..., :di]
    xbc = silu(causal_conv(zxbcdt[..., di:di + conv], lp["conv_w"],
                           lp["conv_b"]))
    x = xbc[..., :di].reshape(B, S, z["mh"], z["P"])
    Bm, Cm = xbc[..., di:di + z["N"]], xbc[..., di + z["N"]:]
    dt = jax.nn.softplus(zxbcdt[..., di + conv:] + lp["dt_bias"])
    A = -jnp.exp(lp["A_log"])
    y = jax.vmap(ssm_scan, in_axes=(0, 0, 0, 0, None))(x, Bm, Cm, dt, A)
    y = (y + lp["D"][:, None] * x).reshape(B, S, di)
    g = rms_norm(y * silu(gate), lp["norm"], z["eps"])
    return _mm("bsn,nh->bsh", _store(g, precision), lp["out_proj"],
               precision)


def mlp(u, lp, z, precision):
    a = _mm("bsh,hf->bsf", u, lp["mlp_in"], precision)
    act = _store(silu(a[..., :z["F"]]) * a[..., z["F"]:], precision)
    return _mm("bsf,fh->bsh", act, lp["mlp_out"], precision)


def hidden_states(params, tokens, cfg: dict, precision: str = "f32"):
    """Final-norm hidden states ``[B, S, H]`` for token ids ``[B, S]``."""
    z = sizes(cfg)
    x = _store(params["embed"][tokens].astype(jnp.float32) * z["emb_mult"],
               precision)
    for l, lp in enumerate(params["layers"]):
        u = _store(rms_norm(x, lp["norm1"], z["eps"]), precision)
        mixer = attention if is_attn(z, l) else mamba
        x = _store(x + z["res_mult"] * mixer(u, lp, z, precision), precision)
        h = _store(rms_norm(x, lp["norm2"], z["eps"]), precision)
        x = _store(x + z["res_mult"] * mlp(h, lp, z, precision), precision)
    return _store(rms_norm(x, params["final_norm"], z["eps"]), precision)


def logits_of(params, hidden, precision: str = "f32"):
    """The tied head: ``hidden E^T / logits_scaling``, over the whole
    vocabulary."""
    return jnp.einsum(
        "...h,vh->...v", _round_to(hidden, precision),
        _round_to(params["embed"], precision), precision=_HI,
        preferred_element_type=jnp.float32) / params["logits_scaling"]


def loss_fn(params, batch, cfg: dict, precision: str = "f32"):
    """Weighted mean next-token cross-entropy; ``batch = (tokens, targets,
    weights)``.  No cell trains this configuration (16 B a parameter is
    51 GB)."""
    tokens, targets, weights = batch
    logits = logits_of(params, hidden_states(params, tokens, cfg, precision),
                       precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * weights) / jnp.maximum(jnp.sum(weights), 1.0)
