"""Everything the harness needs to know about the ``mellum2`` family (a
served family: the exports listed at the head of ``families/gpt2.py``
that a serve cell reads), and the byte and operation counts its metric
readers divide by a peak, kept with the benchmark so that no change to
the program can move them.

The configuration is a serving stage of the published model: its first
``num_hidden_layers`` layers (whole periods: three sliding-window layers
and one full-attention layer each), every width, every expert and every
row of the vocabulary, with the embedding and the head.  A token
multiplies the attention projections, the router, its ``k`` picked
experts of every layer and the head."""

from __future__ import annotations

import copy

from families.solar_open2 import part_seconds  # noqa: F401  (exported)
from reference import mellum2 as reference  # noqa: F401  (exported)

# ---------------------------------------------------------------------
# weights: the reference's leaves -> the program's tree


def program_tree(leaves: dict, cfg: dict) -> dict:
    """``models/mellum2.Mellum2LM``'s flax tree (matrices in bfloat16,
    norm scales and the router in float32, as the program declares
    them): a renaming, nothing is reshaped."""
    z = reference.sizes(cfg)
    tree = {"tok_embed": {"embedding": leaves[("embed", None)]},
            "lm_head": leaves[("head", None)],
            "final_norm": {"scale": leaves[("final_norm", None)]}}
    for l in range(z["L"]):
        g = lambda name: leaves[(name, l)]      # noqa: E731
        tree[f"layer_{l}_norm1"] = {"scale": g("norm1")}
        tree[f"layer_{l}_mixer"] = {n: g(n) for n in ("wq", "wk", "wv",
                                                       "wo")}
        tree[f"layer_{l}_norm2"] = {"scale": g("norm2")}
        tree[f"layer_{l}_moe"] = {
            "router": {"kernel": g("router")},
            "wg": g("exp_gate"), "wi": g("exp_up"), "wo": g("exp_down")}
    return tree


def vocab_size(cfg: dict) -> int:
    """Every row of the vocabulary: the traffic draws its ids from them,
    and the logits are over them."""
    return cfg["vocab_size"]


# ---------------------------------------------------------------------
# operations and bytes from shapes (a multiply-add is 2)


def params(cfg: dict) -> dict:
    """Parameter counts: ``attn`` one layer's projections, ``router``,
    ``expert`` one expert's three matrices, ``layer`` one layer whole,
    ``per_token`` the matrix parameters one token multiplies through all
    layers and the head, ``total`` every parameter held."""
    z = reference.sizes(cfg)
    H = z["H"]
    attn = 2 * H * z["heads"] * z["d"] + 2 * H * z["kvh"] * z["d"]
    router = H * z["E"]
    expert = 3 * H * z["F"]
    layer = attn + router + z["E"] * expert + 2 * H
    return {
        "attn": attn, "router": router, "expert": expert, "layer": layer,
        "window_layers": sum(reference.is_window(z, l)
                             for l in range(z["L"])),
        "per_token": (z["L"] * (attn + router + z["k"] * expert)
                      + H * z["V"]),
        "total": z["L"] * layer + 2 * z["V"] * H + H,
    }


def decode_flops_per_token(cfg: dict) -> float:
    """2 x the matrix parameters a token multiplies (attention over the
    cache left out: a lower bound)."""
    return 2.0 * params(cfg)["per_token"]


def band_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a causal window of ``window`` keys sees over
    ``seq`` positions: ``min(i + 1, window)`` a query."""
    if seq <= window:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def window_band_flops(cfg: dict, seq: int) -> float:
    """One window layer's prefill attention at a padded length ``seq``,
    the band only: ``q k^T`` and ``p v`` (2 each a pair, a head and a
    lane of the head)."""
    z = reference.sizes(cfg)
    return 4.0 * z["heads"] * z["d"] * band_pairs(seq, z["W"])


def window_band_bytes(cfg: dict, seq: int) -> float:
    """The bytes one window layer's prefill attention must move at a
    padded length ``seq``: q, k and v read and the output written once,
    bfloat16, k and v at every query head as the kernel is handed them."""
    z = reference.sizes(cfg)
    return 4.0 * seq * z["heads"] * z["d"] * 2


def sequence_forward_flops(cfg: dict, seq: int) -> float:
    """One causal forward over ``seq`` tokens: the per-token work, and
    ``q k^T`` and ``p v`` under each layer's mask (the triangle on full
    layers, the band on window layers)."""
    z = reference.sizes(cfg)
    n_win = params(cfg)["window_layers"]
    full = (z["L"] - n_win) * 4.0 * z["heads"] * z["d"] * band_pairs(seq,
                                                                      seq)
    return (seq * decode_flops_per_token(cfg) + full
            + n_win * window_band_flops(cfg, seq))


def moe_decode_bytes(cfg: dict, experts_hit: float) -> float:
    """The bytes a decode step MUST move for the routed experts: the
    bfloat16 matrices of each expert its rows picked (``experts_hit``
    summed over the layers), and every layer's float32 router."""
    z = reference.sizes(cfg)
    p = params(cfg)
    return experts_hit * p["expert"] * 2 + z["L"] * p["router"] * 4


# ---------------------------------------------------------------------
# the CPU rehearsal's sizes

TINY = {"hidden_size": 64, "num_attention_heads": 4, "head_dim": 16,
        "num_key_value_heads": 2, "vocab_size": 256, "num_experts": 8,
        "num_experts_per_tok": 2, "moe_intermediate_size": 32,
        "num_hidden_layers": 4, "sliding_window": 8}


def tiny_config(cfg: dict) -> dict:
    cfg = copy.deepcopy(cfg)
    cfg.update(TINY)
    # 0.1 x sqrt(64) ~ 0.02 x sqrt(2304): a tiny layer then adds to the
    # residual stream what a published-width one does
    cfg["assumed"]["initializer_range"] = 0.1
    return cfg


def program_sizes(cfg: dict) -> dict:
    """``models/mellum2.Mellum2LM``'s fields for ``cfg``."""
    z = reference.sizes(cfg)
    full = z["rope"]["full_attention"]
    if z["rope"]["sliding_attention"]["rope_theta"] != full["rope_theta"]:
        raise ValueError("one rope_theta for both layer types")
    return dict(
        vocab_size=z["V"], hidden=z["H"], layer_types=z["types"],
        heads=z["heads"], kv_heads=z["kvh"], head_dim=z["d"],
        n_experts=z["E"], top_k=z["k"], expert_ffn=z["F"], window=z["W"],
        rope_theta=float(full["rope_theta"]),
        yarn=(("factor", float(full["factor"])),
              ("original", full["original_max_position_embeddings"]),
              ("beta_fast", float(full["beta_fast"])),
              ("beta_slow", float(full["beta_slow"])),
              ("attention_factor", full["attention_factor"])),
        eps=z["eps"])


def shrink_program(cfg: dict) -> None:
    """Point the program's registry entry for this model at a member of
    the tiny configuration's sizes (this process only)."""
    from tpu_hc_bench.models import mellum2 as mm

    setattr(mm, cfg["program_model"], mm._factory(**program_sizes(cfg)))
