"""Everything the harness needs to know about the ``granite4h`` family
(a served family: the exports listed at the head of ``families/gpt2.py``
that a serve cell reads), and the byte and operation counts its metric
readers divide by a peak, kept with the benchmark so that no change to
the program can move them.

The configuration is the whole published model on one chip: every
layer, every width and every row of the vocabulary.  A token multiplies
every matrix once (the head is the embedding's transpose) and steps the
state of every Mamba-2 layer."""

from __future__ import annotations

import copy

from families.solar_open2 import part_seconds  # noqa: F401  (exported)
from reference import granite4h as reference  # noqa: F401  (exported)

# ---------------------------------------------------------------------
# weights: the reference's leaves -> the program's tree


def program_tree(leaves: dict, cfg: dict) -> dict:
    """``models/granite4h.GraniteHybridLM``'s flax tree (matrices in
    bfloat16, vectors and the convolution's taps in float32, as the
    program declares them): a renaming, nothing is reshaped."""
    z = reference.sizes(cfg)
    tree = {"tok_embed": {"embedding": leaves[("embed", None)]},
            "final_norm": {"scale": leaves[("final_norm", None)]}}
    for l in range(z["L"]):
        g = lambda name: leaves[(name, l)]      # noqa: E731
        names = (reference.ATTN_LEAVES if reference.is_attn(z, l)
                 else reference.MAMBA_LEAVES)
        tree[f"layer_{l}_mixer"] = {n: g(n) for n, _, _, _ in names}
        tree[f"layer_{l}_norm1"] = {"scale": g("norm1")}
        tree[f"layer_{l}_norm2"] = {"scale": g("norm2")}
        tree[f"layer_{l}_mlp"] = {"w_in": g("mlp_in"), "w_out": g("mlp_out")}
    return tree


def vocab_size(cfg: dict) -> int:
    """Every row of the vocabulary: the traffic draws its ids from them,
    and the logits are over them."""
    return cfg["vocab_size"]


# ---------------------------------------------------------------------
# operations and bytes from shapes (a multiply-add is 2)


def params(cfg: dict) -> dict:
    """Parameter counts: ``mamba`` / ``attn`` one mixer of each kind,
    ``mlp`` one layer's FFN, ``per_token`` the matrix parameters one token
    multiplies through all layers and the tied head, ``total`` every
    parameter held."""
    z = reference.sizes(cfg)
    H = z["H"]
    mamba_mm = H * (z["di"] + z["conv"] + z["mh"]) + z["di"] * H
    mamba = mamba_mm + (z["K"] + 1) * z["conv"] + 3 * z["mh"] + z["di"]
    attn = 2 * H * z["heads"] * z["d"] + 2 * H * z["kvh"] * z["d"]
    mlp = 3 * H * z["F"]
    n_attn = len(z["attn"])
    n_mamba = z["L"] - n_attn
    return {
        "mamba": mamba, "attn": attn, "mlp": mlp,
        "mamba_layers": n_mamba, "attn_layers": n_attn,
        "per_token": (n_mamba * mamba_mm + n_attn * attn + z["L"] * mlp
                      + H * z["V"]),
        "total": (n_mamba * mamba + n_attn * attn + z["L"] * (mlp + 2 * H)
                  + z["V"] * H + H),
    }


def state_update_flops(cfg: dict) -> float:
    """One token's recurrence steps over the Mamba-2 layers: per head the
    decay, the input ``dt x B^T`` (2) and ``h C`` (2) over a ``P x N``
    state."""
    z = reference.sizes(cfg)
    return 5.0 * params(cfg)["mamba_layers"] * z["mh"] * z["P"] * z["N"]


def decode_flops_per_token(cfg: dict) -> float:
    """2 x the matrix parameters a token multiplies + the state update
    (attention over the cache left out: a lower bound)."""
    return 2.0 * params(cfg)["per_token"] + state_update_flops(cfg)


def sequence_forward_flops(cfg: dict, seq: int) -> float:
    """One causal forward over ``seq`` tokens: the per-token work, and
    QK^T and PV of the softmax layers under the causal mask."""
    z = reference.sizes(cfg)
    attn = (len(z["attn"]) * 2 * 2.0 * seq * seq * z["heads"] * z["d"]) / 2
    return seq * decode_flops_per_token(cfg) + attn


def ssm_decode_bytes(cfg: dict, rows: float, itemsize: int = 2) -> float:
    """The bytes one decode step over ``rows`` active rows MUST move for
    the Mamba-2 layers, each counted once: every row's state read and
    written (float32), its convolution tail read and written, and the
    Mamba-2 mixers' weights read once."""
    z = reference.sizes(cfg)
    p = params(cfg)
    state = 2 * rows * z["mh"] * z["P"] * z["N"] * 4
    tails = 2 * rows * (z["K"] - 1) * z["conv"] * itemsize
    return p["mamba_layers"] * (state + tails + p["mamba"] * itemsize)


# ---------------------------------------------------------------------
# the CPU rehearsal's sizes

TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 256,
        "intermediate_size": 96, "shared_intermediate_size": 96,
        "num_hidden_layers": 4,
        "layer_types": ["mamba", "attention", "mamba", "mamba"],
        "mamba_n_heads": 4, "mamba_d_head": 32, "mamba_d_state": 16,
        "mamba_chunk_size": 8}


def tiny_config(cfg: dict) -> dict:
    cfg = copy.deepcopy(cfg)
    cfg.update(TINY)
    # 0.1 x sqrt(64) ~ 0.02 x sqrt(2048): a tiny layer then adds to the
    # residual stream what a published-width one does
    cfg["assumed"]["initializer_range"] = 0.1
    return cfg


def program_sizes(cfg: dict) -> dict:
    """``models/granite4h.GraniteHybridLM``'s fields for ``cfg``."""
    z = reference.sizes(cfg)
    return dict(
        vocab_size=z["V"], hidden=z["H"],
        layer_types=tuple(cfg["layer_types"][:z["L"]]), heads=z["heads"],
        kv_heads=z["kvh"], mamba_heads=z["mh"], mamba_head_dim=z["P"],
        d_state=z["N"], conv_kernel=z["K"], chunk=z["chunk"], ffn=z["F"],
        embedding_mult=z["emb_mult"], residual_mult=z["res_mult"],
        attn_scale=z["attn_mult"], logits_scaling=z["logit_scale"],
        eps=z["eps"])


def shrink_program(cfg: dict) -> None:
    """Point the program's registry entry for this model at a member of
    the tiny configuration's sizes (this process only)."""
    from tpu_hc_bench.models import granite4h as gh

    setattr(gh, cfg["program_model"], gh._factory(**program_sizes(cfg)))
