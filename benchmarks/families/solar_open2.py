"""Everything the harness needs to know about the ``solar_open2`` family
(a served family: the exports listed at the head of ``families/gpt2.py``
that a serve cell reads), and the byte and operation counts its metric
readers divide by a peak, kept with the benchmark so that no change to
the program can move them.

The configuration is one chip's share of a deployment: the experts, the
rows of the vocabulary and the layers held here.  A token multiplies the
mixers, the router, the shared expert, its EXPECTED share of the routed
experts held here (``num_experts_per_tok x held / published``) and the
held rows of the head."""

from __future__ import annotations

import copy

from reference import solar_open2 as reference  # noqa: F401  (exported)

# ---------------------------------------------------------------------
# weights: the reference's leaves -> the program's tree


def program_tree(leaves: dict, cfg: dict) -> dict:
    """``models/solar_open2.SolarOpen2LM``'s flax tree (matrices in
    bfloat16, vectors and the router in float32, as the program declares
    them): a renaming, nothing is reshaped."""
    z = reference.sizes(cfg)
    tree = {"tok_embed": {"embedding": leaves[("embed", None)]},
            "lm_head": leaves[("head", None)],
            "final_norm": {"scale": leaves[("final_norm", None)]}}
    for l in range(z["L"]):
        g = lambda name: leaves[(name, l)]      # noqa: E731
        names = (reference.GQA_LEAVES if reference.is_gqa(z, l)
                 else reference.KDA_LEAVES)
        tree[f"layer_{l}_mixer"] = {n: g(n) for n, _, _, _ in names}
        tree[f"layer_{l}_norm1"] = {"scale": g("norm1")}
        tree[f"layer_{l}_norm2"] = {"scale": g("norm2")}
        tree[f"layer_{l}_moe"] = {
            "router": {"kernel": g("router")},
            "router_bias": g("router_bias"),
            "wg": g("exp_gate"), "wi": g("exp_up"), "wo": g("exp_down"),
            "shared_gate": {"kernel": g("shared_gate")},
            "shared_up": {"kernel": g("shared_up")},
            "shared_down": {"kernel": g("shared_down")}}
    return tree


def vocab_size(cfg: dict) -> int:
    """The rows of the vocabulary held here: the traffic draws its ids
    from them, and the logits are over them."""
    return cfg["vocab_size"]


# ---------------------------------------------------------------------
# operations and bytes from shapes (a multiply-add is 2)


def params(cfg: dict) -> dict:
    """Parameter counts of the share: ``kda`` / ``gqa`` one mixer of each
    kind, ``moe_held`` one layer's router, shared expert and held
    experts, ``per_token`` what one token multiplies through all layers
    and the head, ``total`` everything held."""
    z = reference.sizes(cfg)
    H, n, r = z["H"], z["n"], z["r"]
    kda = (3 * H * n + n * H + 2 * (H * r + r * n) + H * z["kh"]
           + 3 * z["K"] * n + n + z["kh"] + z["kd"])
    gqa = (2 * H * z["heads"] * z["d"] + 2 * H * z["kvh"] * z["d"]
           + z["heads"] * z["d"] * H)
    expert = 3 * H * z["F"]
    shared = 3 * H * z["Fs"]
    router = H * z["E"]
    n_gqa = len(z["gqa"])
    n_kda = z["L"] - n_gqa
    mixers = n_kda * kda + n_gqa * gqa
    expected = z["k"] * z["Eh"] / z["E"]
    return {
        "kda": kda, "gqa": gqa, "kda_layers": n_kda, "gqa_layers": n_gqa,
        "moe_held": router + shared + z["Eh"] * expert,
        "per_token": (mixers + z["L"] * (router + shared + expected * expert)
                      + H * z["V"]),
        "total": (mixers + z["L"] * (router + shared + z["Eh"] * expert)
                  + 2 * H * z["V"]),
    }


def state_update_flops(cfg: dict) -> float:
    """One token's recurrence steps over the KDA layers: per head the
    decay (1 an element), ``k^T S`` and ``S^T q`` (2 each) and the
    rank-1 update (2) over a ``d_k x d_v`` state."""
    z = reference.sizes(cfg)
    p = params(cfg)
    return 7.0 * p["kda_layers"] * z["kh"] * z["kd"] * z["kd"]


def decode_flops_per_token(cfg: dict) -> float:
    """2 x the parameters a token multiplies here + the state update
    (attention over the cache left out: a lower bound)."""
    return 2.0 * params(cfg)["per_token"] + state_update_flops(cfg)


def sequence_forward_flops(cfg: dict, seq: int) -> float:
    """One causal forward over ``seq`` tokens: the per-token work, and
    QK^T and PV of the softmax layers under the causal mask."""
    z = reference.sizes(cfg)
    attn = (len(z["gqa"]) * 2 * 2.0 * seq * seq * z["heads"] * z["d"]) / 2
    return seq * decode_flops_per_token(cfg) + attn


def kda_decode_bytes(cfg: dict, rows: float, itemsize: int = 2) -> float:
    """The bytes one decode step over ``rows`` active rows MUST move for
    the KDA layers, each counted once: every row's state read and
    written (float32), its convolution tail read and written, and the
    KDA mixers' weights read once."""
    z = reference.sizes(cfg)
    p = params(cfg)
    state = 2 * rows * z["kh"] * z["kd"] * z["kd"] * 4
    tails = 2 * rows * (z["K"] - 1) * 3 * z["n"] * itemsize
    return p["kda_layers"] * (state + tails + p["kda"] * itemsize)


# ---------------------------------------------------------------------
# the program's named parts in a device trace


def part_seconds(ctx: dict, kinds=("prefill", "decode")) -> dict | None:
    """Traced device seconds by named part (``kda``, ``gqa``, ``moe``,
    ``head``) of the programs of ``kinds``.

    A trace's events carry the compiled instruction (``fusion.12``), not
    the ``jax.named_scope`` it was traced under, so the program's
    summary maps each program's instructions to their part
    (``op_parts``: ``{"decode@128": {"fusion.12:bf16[..]": "kda"}}``,
    keyed as ``harness/xplane.clean_name`` keys the trace).  Where one
    key belongs to several parts across programs its seconds are split
    evenly among them.  None where the program has no such map (a parent
    that lacks it) or the run has no trace."""
    tr = ctx.get("trace")
    op_parts = (ctx.get("summary") or {}).get("op_parts")
    if not tr or not op_parts:
        return None
    owners: dict = {}
    for program, ops in op_parts.items():
        if program.split("@")[0] in kinds:
            for key, part in ops.items():
                owners.setdefault(key, set()).add(part)
    out: dict = {}
    for key, seconds in tr["ops"].items():
        for part in owners.get(key, ()):
            out[part] = out.get(part, 0.0) + seconds / len(owners[key])
    return out or None


# ---------------------------------------------------------------------
# the CPU rehearsal's sizes

TINY = {"hidden_size": 64, "num_attention_heads": 4, "head_dim": 16,
        "num_key_value_heads": 2, "vocab_size": 256,
        "moe_intermediate_size": 32, "n_routed_experts": 2,
        "num_experts_per_tok": 4}


def tiny_config(cfg: dict) -> dict:
    cfg = copy.deepcopy(cfg)
    cfg.update(TINY)
    cfg["linear_attn_config"].update(head_dim=16, num_heads=4)
    cfg["published"]["n_routed_experts"] = 16
    # 0.16 x sqrt(64) = 0.02 x sqrt(4096): a tiny layer then adds to the
    # residual stream what a published-width one does
    cfg["assumed"].update(kda_gate_rank=8, initializer_range=0.16)
    return cfg


def shrink_program(cfg: dict) -> None:
    """Point the program's registry entry for this model at a member of
    the tiny configuration's sizes (this process only)."""
    from tpu_hc_bench.models import solar_open2 as so

    z = reference.sizes(cfg)
    setattr(so, cfg["program_model"], so._factory(
        vocab_size=z["V"], hidden=z["H"], num_layers=z["L"],
        heads=z["heads"], kv_heads=z["kvh"], head_dim=z["d"],
        gqa_layers=z["gqa"], kda_heads=z["kh"], kda_head_dim=z["kd"],
        conv_kernel=z["K"], gate_rank=z["r"], n_routed=z["E"],
        experts_held=(z["first"], z["first"] + z["Eh"]), top_k=z["k"],
        expert_ffn=z["F"], shared_ffn=z["Fs"], eps=z["eps"]))
