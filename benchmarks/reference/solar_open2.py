"""Plain reference for the ``solar_open2`` model type (upstage/
Solar-Open2-250B ``config.json``; the KDA layer as Kimi Linear's released
gated delta-rule attention, which the ``kda_*`` keys name): the forward
pass in straightforward ``jax.numpy`` — the recurrence token by token as
it is written, no chunking, no cache, no batching tricks, no kernels.  It
imports nothing of the program under test and takes nothing the program
has made: weights come from ``make_params`` (the seed), inputs from the
benchmark's traffic generator.

The model is given as ONE CHIP'S SHARE of the deployment the
configuration states (its ``deployment`` key): the layers, the routed
experts and the rows of the vocabulary held here.  The router keeps its
published width (``published.n_routed_experts``) and its experts per
token; what the absent experts would add to a layer's output is left out,
here as in the program, and that partial result goes on to the next layer.

Layers (every norm RMSNorm; ``h = x + Mixer(norm1(x))``, ``y = h +
MoE(norm2(h))``; final norm, untied head):

- layers in ``gqa_layers``: ``q = u Wq`` (heads x d), ``k, v = u Wk, u
  Wv`` (kv heads x d), no position encoding, causal ``softmax(q k^T /
  sqrt(d)) v`` with each kv head serving heads/kv_heads query heads, then
  ``Wo [ctx * sigmoid(u Wgate)]``;
- the others (KDA), per head: ``q~, k~, v = SiLU(conv_K(u Wq)), ...``
  (depthwise causal convolution over time), ``q = l2norm(q~)/sqrt(d)``,
  ``k = l2norm(k~)``, ``g_t = -exp(A_log) softplus((u Wf_down) Wf_up +
  dt_bias)``, ``b_t = 2 sigmoid(u Wb)``,
  ``S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T``,
  ``o_t = S_t^T q_t``, output ``Wo [rmsnorm_head(o_t) * sigmoid((u
  Wg_down) Wg_up)]``;
- MoE: ``s = sigmoid(u Wr)`` in float32 over all routed experts, the
  ``num_experts_per_tok`` largest of ``s + bias`` picked, weights ``s_e /
  sum over the picked`` x ``routed_scaling_factor``, ``y = Shared(u) +
  sum over the picked AND held of w_e Expert_e(u)``, every expert
  ``Wdown(SiLU(Wgate u) * Wup u)``.

The router's selection bias is not drawn: it is what load balancing
without an auxiliary loss (the lineage's own way of keeping experts
evenly used) converges to, computed when the weights are made
(``balanced_bias``): random weights give every token's hidden state a
large common component (SiLU's positive mean through the delta-rule
layers), which makes a few experts popular with ALL tokens, differently
for every seed, and the time of a step then follows which experts a seed
leaves empty (measured: 11-17% of the picks held and a 5% range of the
decode step over four seeds, PERF.md section 6).  The bias offsets each
expert's score by its own 1 - k/E quantile over a calibration sequence of
the seed's random tokens, so that every expert is picked about equally
often, as in a trained model.

Every other leaf is drawn from the seed in float32 and ROUNDED TO BFLOAT16
ONCE, so that the program (which holds its matrices in bfloat16) and this
reference hold equal numbers; the matrices stay in bfloat16 storage and
are widened where they are used.

``precision``: ``"f32"`` is float32 at ``highest`` throughout (the
reference proper).  ``"bf16"`` is the arithmetic the serve arm states:
matmul operands in bfloat16 with float32 accumulation and every tensor
between operations stored in bfloat16, but float32 for the router's
scores, norm statistics, softmax, ``g``, ``b`` and the state ``S``.
``"fp8"`` is the lower-precision control: as ``"bf16"`` with both matmul
operands rounded to e4m3 first.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST

# name -> (shape from sizes, (mean, std), held in float32 by both sides)
GQA_LEAVES = (
    ("wq", lambda z: (z["H"], z["heads"] * z["d"]), (0, "w"), False),
    ("wk", lambda z: (z["H"], z["kvh"] * z["d"]), (0, "w"), False),
    ("wv", lambda z: (z["H"], z["kvh"] * z["d"]), (0, "w"), False),
    ("wgate", lambda z: (z["H"], z["heads"] * z["d"]), (0, "w"), False),
    ("wo", lambda z: (z["heads"] * z["d"], z["H"]), (0, "w"), False),
)
KDA_LEAVES = (
    ("wq", lambda z: (z["H"], z["n"]), (0, "w"), False),
    ("wk", lambda z: (z["H"], z["n"]), (0, "w"), False),
    ("wv", lambda z: (z["H"], z["n"]), (0, "w"), False),
    ("conv_q", lambda z: (z["K"], z["n"]), (0, 0.5), True),
    ("conv_k", lambda z: (z["K"], z["n"]), (0, 0.5), True),
    ("conv_v", lambda z: (z["K"], z["n"]), (0, 0.5), True),
    ("wf_down", lambda z: (z["H"], z["r"]), (0, "w"), False),
    ("wf_up", lambda z: (z["r"], z["n"]), (0, "w"), False),
    # softplus(-4) = 0.018: the state forgets over some tens of tokens,
    # not within one or two
    ("dt_bias", lambda z: (z["n"],), (-4.0, 1.0), True),
    ("A_log", lambda z: (z["kh"],), (0, 0.5), True),
    ("wb", lambda z: (z["H"], z["kh"]), (0, "w"), False),
    ("wg_down", lambda z: (z["H"], z["r"]), (0, "w"), False),
    ("wg_up", lambda z: (z["r"], z["n"]), (0, "w"), False),
    ("o_norm", lambda z: (z["kd"],), (1.0, "w"), True),
    ("wo", lambda z: (z["n"], z["H"]), (0, "w"), False),
)
MOE_LEAVES = (
    ("norm1", lambda z: (z["H"],), (1.0, "w"), True),
    ("norm2", lambda z: (z["H"],), (1.0, "w"), True),
    ("router", lambda z: (z["H"], z["E"]), (0, "w"), True),
    ("router_bias", lambda z: (z["E"],), None, True),    # balanced_bias
    ("shared_gate", lambda z: (z["H"], z["Fs"]), (0, "w"), False),
    ("shared_up", lambda z: (z["H"], z["Fs"]), (0, "w"), False),
    ("shared_down", lambda z: (z["Fs"], z["H"]), (0, "w"), False),
    ("exp_gate", lambda z: (z["Eh"], z["H"], z["F"]), (0, "w"), False),
    ("exp_up", lambda z: (z["Eh"], z["H"], z["F"]), (0, "w"), False),
    ("exp_down", lambda z: (z["Eh"], z["F"], z["H"]), (0, "w"), False),
)
TOP_LEAVES = (
    ("embed", lambda z: (z["V"], z["H"]), (0, "w"), False),
    ("head", lambda z: (z["H"], z["V"]), (0, "w"), False),
    ("final_norm", lambda z: (z["H"],), (1.0, "w"), True),
)


def sizes(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    a = cfg["assumed"]
    kh, kd = lin["num_heads"], lin["head_dim"]
    return {
        "H": cfg["hidden_size"], "L": cfg["num_hidden_layers"],
        "heads": cfg["num_attention_heads"], "d": cfg["head_dim"],
        "kvh": cfg["num_key_value_heads"], "V": cfg["vocab_size"],
        "kh": kh, "kd": kd, "n": kh * kd,
        "K": lin["short_conv_kernel_size"], "r": a["kda_gate_rank"],
        # the router's width is the published count; the experts held
        # here are ``first .. first + Eh``
        "E": cfg["published"]["n_routed_experts"],
        "Eh": cfg["n_routed_experts"], "first": a["experts_held_from"],
        "k": cfg["num_experts_per_tok"], "F": cfg["moe_intermediate_size"],
        "Fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        "scale": cfg["routed_scaling_factor"],
        "norm_topk": cfg["norm_topk_prob"], "eps": cfg["rms_norm_eps"],
        "gqa": tuple(l for l in cfg["gqa_layers"]
                     if l < cfg["num_hidden_layers"]),
        "neg": cfg["kda_allow_neg_eigval"],
        "std": a["initializer_range"],
    }


def is_gqa(z: dict, l: int) -> bool:
    return l in z["gqa"]


def layer_leaves(z: dict, l: int):
    return (GQA_LEAVES if is_gqa(z, l) else KDA_LEAVES) + MOE_LEAVES


def seed_key(seed: int):
    """Any whole seed up to 2**62 folds into one key (the driver's seeds
    pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


CALIBRATION_TOKENS = 512     # the sequence the selection bias is balanced on
BIAS_STEP = 1.0 / 64         # the bias is a multiple of this (exact in bf16)


def _leaf(key, shape, init, f32: bool, std: float):
    if init is None:                    # set after the draw: balanced_bias
        return jnp.zeros(shape, jnp.float32)
    mean, sd = init
    x = mean + (std if sd == "w" else sd) * jax.random.normal(
        key, shape, jnp.float32)
    x = x.astype(jnp.bfloat16)          # rounded ONCE: both sides' numbers
    return x.astype(jnp.float32) if f32 else x


def leaf_values(cfg: dict, seed):
    """Every leaf from the seed, keyed ``(name, layer | None)``: the one
    place values are drawn, whatever layout they are handed out in.
    Traceable (``seed`` may be a key from ``seed_key``)."""
    z = sizes(cfg)
    key = seed if not isinstance(seed, int) else seed_key(seed)
    out = {}
    for i, (name, shape, init, f32) in enumerate(TOP_LEAVES):
        out[(name, None)] = _leaf(jax.random.fold_in(key, i), shape(z),
                                  init, f32, z["std"])
    for l in range(z["L"]):
        kl = jax.random.fold_in(key, 1000 + l)
        for i, (name, shape, init, f32) in enumerate(layer_leaves(z, l)):
            out[(name, l)] = _leaf(jax.random.fold_in(kl, i), shape(z),
                                   init, f32, z["std"])
    return _with_balanced_bias(out, z, jax.random.fold_in(key, 999))


def balanced_bias(h, router, z):
    """The selection bias that evens the experts' use over the tokens of
    ``h`` [1, T, H]: each expert's score is offset by its own 1 - k/E
    quantile over the tokens (about the median expert's, so the bias has
    no common part), in steps of ``BIAS_STEP``.  Every expert then passes
    the common threshold for about k/E of the tokens."""
    s = jnp.sort(jax.nn.sigmoid(_f32("bsh,he->bse", h, router))[0], axis=0)
    T = s.shape[0]
    q = s[T - max(1, round(T * z["k"] / z["E"]))]
    return jnp.round((jnp.median(q) - q) / BIAS_STEP) * BIAS_STEP


def _with_balanced_bias(leaves: dict, z: dict, key) -> dict:
    """One float32 pass of the model over a calibration sequence of random
    token ids, a layer at a time: each layer's bias is balanced on its own
    router input, then used for the layer's output."""
    tokens = jax.random.randint(key, (1, CALIBRATION_TOKENS), 1, z["V"])
    x = leaves[("embed", None)][tokens].astype(jnp.float32)
    for l in range(z["L"]):
        lp = {name: leaves[(name, l)] for name, _, _, _ in layer_leaves(z, l)}
        x, h = _mix(x, lp, z, l, "f32")
        lp["router_bias"] = leaves[("router_bias", l)] = balanced_bias(
            h, lp["router"], z)
        x = x + moe(h, lp, z, "f32")
    return leaves


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
    bfloat16."""
    if precision == "f32":
        return x
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mm(eq: str, a, w, precision: str):
    return _store(jnp.einsum(eq, _round_to(a, precision),
                             _round_to(w, precision), precision=_HI,
                             preferred_element_type=jnp.float32), precision)


def _f32(eq: str, a, b):
    """A product the arm states in float32, whatever the precision."""
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=_HI)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def silu(x):
    return x * jax.nn.sigmoid(x)


def gqa_mixer(u, lp, z, precision):
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
        scores = _mm("bqgd,bkd->bgqk", qg, kj, precision) / math.sqrt(d)
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = _store(jax.nn.softmax(scores, axis=-1), precision)
        return _mm("bgqk,bkd->bqgd", probs, vj, precision)

    ctx = jax.lax.map(one_kv_head, jnp.arange(kvh))     # [kvh, B, S, g, d]
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(B, S, heads * d)
    gate = jax.nn.sigmoid(_mm("bsh,hn->bsn", u, lp["wgate"], precision))
    return _mm("bsn,nh->bsh", _store(ctx * gate, precision), lp["wo"],
               precision)


def causal_conv(x, w):
    """Depthwise causal convolution over time: ``y_t = sum_i w[i]
    x_{t-(K-1)+i}``, zeros before the sequence's start."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, i:i + S] * w[i] for i in range(K))


def l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def delta_rule(q, k, v, g, beta):
    """The recurrence as written, one token at a time from ``S_0 = 0``:
    ``q``, ``k``, ``v``, ``g`` [S, heads, d], ``beta`` [S, heads];
    float32 throughout.  Returns ``o`` [S, heads, d_v]."""
    heads, d = q.shape[1], q.shape[2]

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        Sd = jnp.exp(g_t)[:, :, None] * S               # Diag(a_t) S_{t-1}
        kS = jnp.einsum("hk,hkv->hv", k_t, Sd, precision=_HI)
        S = (Sd - b_t[:, None, None] * k_t[:, :, None] * kS[:, None, :]
             + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :])
        return S, jnp.einsum("hk,hkv->hv", q_t, S, precision=_HI)

    _, o = jax.lax.scan(step, jnp.zeros((heads, d, v.shape[2]), jnp.float32),
                        (q, k, v, g, beta))
    return o


def kda_mixer(u, lp, z, precision):
    B, S, _ = u.shape
    kh, kd = z["kh"], z["kd"]

    def branch(w, conv):
        x = _mm("bsh,hn->bsn", u, lp[w], precision)
        return silu(causal_conv(x, lp[conv])).reshape(B, S, kh, kd)

    q = l2norm(branch("wq", "conv_q")) / math.sqrt(kd)
    k = l2norm(branch("wk", "conv_k"))
    v = branch("wv", "conv_v")
    f = _mm("bsr,rn->bsn", _mm("bsh,hr->bsr", u, lp["wf_down"], precision),
            lp["wf_up"], precision)
    g = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(
        (f + lp["dt_bias"]).reshape(B, S, kh, kd))
    beta = jax.nn.sigmoid(_f32("bsh,hn->bsn", u, lp["wb"]))
    if z["neg"]:
        beta = 2.0 * beta
    o = jax.vmap(delta_rule)(q, k, v, g, beta)          # [B, S, kh, kd]
    o = rms_norm(o, lp["o_norm"], z["eps"])
    gate = jax.nn.sigmoid(_mm(
        "bsr,rn->bsn", _mm("bsh,hr->bsr", u, lp["wg_down"], precision),
        lp["wg_up"], precision)).reshape(B, S, kh, kd)
    return _mm("bsn,nh->bsh",
               _store(o * gate, precision).reshape(B, S, kh * kd),
               lp["wo"], precision)


def moe(u, lp, z, precision):
    """The shared expert plus the held experts' part of the routed sum."""
    B, S, H = u.shape
    s = jax.nn.sigmoid(_f32("bsh,he->bse", u, lp["router"]))
    _, picked = jax.lax.top_k(s + lp["router_bias"], z["k"])
    w = jnp.take_along_axis(s, picked, axis=-1)
    if z["norm_topk"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * z["scale"]
    # [B, S, E]: a token's weight for each expert, 0 where not picked
    weight = jnp.sum(jax.nn.one_hot(picked, z["E"], dtype=jnp.float32)
                     * w[..., None], axis=-2)

    def ffn(x, gate, up, down):
        return _mm("bsf,fh->bsh", _store(
            silu(_mm("bsh,hf->bsf", x, gate, precision))
            * _mm("bsh,hf->bsf", x, up, precision), precision), down,
            precision)

    def add_expert(y, e):
        w_e = jax.lax.dynamic_index_in_dim(weight, z["first"] + e, axis=2)
        out = ffn(u, lp["exp_gate"][e], lp["exp_up"][e], lp["exp_down"][e])
        return y + _store(w_e * out, precision), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), jnp.arange(z["Eh"]))
    return y + ffn(u, lp["shared_gate"], lp["shared_up"], lp["shared_down"])


def _mix(x, lp, z, l: int, precision: str):
    """A layer up to its router: ``(x + Mixer(norm1(x)), norm2 of it)``."""
    u = _store(rms_norm(x, lp["norm1"], z["eps"]), precision)
    mixer = gqa_mixer if is_gqa(z, l) else kda_mixer
    x = _store(x + mixer(u, lp, z, precision), precision)
    return x, _store(rms_norm(x, lp["norm2"], z["eps"]), precision)


def hidden_states(params, tokens, cfg: dict, precision: str = "f32"):
    """Final-norm hidden states ``[B, S, H]`` for token ids ``[B, S]``."""
    z = sizes(cfg)
    x = params["embed"][tokens].astype(jnp.float32)
    for l, lp in enumerate(params["layers"]):
        x, h = _mix(x, lp, z, l, precision)
        x = _store(x + moe(h, lp, z, precision), precision)
    return _store(rms_norm(x, params["final_norm"], z["eps"]), precision)


def logits_of(params, hidden, precision: str = "f32"):
    """Untied output projection over the rows of the vocabulary held."""
    return jnp.einsum("...h,hv->...v", _round_to(hidden, precision),
                      _round_to(params["head"], precision), precision=_HI,
                      preferred_element_type=jnp.float32)


def loss_fn(params, batch, cfg: dict, precision: str = "f32"):
    """Weighted mean next-token cross-entropy over the rows held; ``batch
    = (tokens, targets, weights)``.  No cell trains this configuration
    (16 B/parameter does not fit one chip at the floors of depth)."""
    tokens, targets, weights = batch
    logits = logits_of(params, hidden_states(params, tokens, cfg, precision),
                       precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * weights) / jnp.maximum(jnp.sum(weights), 1.0)
