"""Everything the harness needs to know about the GPT-2 family, in one
file: a configuration names its family (``"family": "gpt2"``), the
harness finds this file by that name, and no file under ``harness/``
names a model module.  Another family (ResNet, BERT, ViT) adds a file
like this one beside it, with its plain reference under ``reference/``.

What a family file exports (the harness's whole demand on it):

- ``reference``: the plain reference module.  Every reference has
  ``seed_key``, ``leaf_values``, ``make_params`` and ``loss_fn(params,
  batch, cfg, precision)``; one that is served also has
  ``hidden_states`` and ``logits_of``;
- ``program_tree``, ``program_parts``, ``reference_parts``: the
  benchmark's weights in the program's layout, and both sides' trees as
  ``{(part, layer): array}`` so that norms are compared part by part;
- ``SYNTHETIC_SOURCE``, ``program_constants``, ``train_flags``,
  ``train_batch``: the seams of the train lane that depend on the model;
- ``vocab_size``, the operation counts (``decode_flops_per_token``,
  ``sequence_forward_flops``, ``train_step_flops_per_example``,
  ``attention_calls``) that the metric readers divide by a peak;
- ``tiny_config``, ``shrink_program``: the CPU rehearsal's sizes.
"""

from __future__ import annotations

import copy

import numpy as np

from harness import traffic
from reference import gpt2 as reference  # noqa: F401  (exported)

# ---------------------------------------------------------------------
# weights: the published layout -> the program's


def program_tree(leaves: dict, cfg: dict) -> dict:
    """Published GPT-2 layout -> ``models/gpt.GPTLM``'s flax tree:
    ``c_attn`` [H, 3H] -> ``qkv/kernel`` [H, 3, heads, d]; ``c_proj``
    [H, H] -> ``out/kernel`` [heads, d, H]."""
    H, heads = cfg["n_embd"], cfg["n_head"]
    d = H // heads
    tree = {
        "wte": {"embedding": leaves[("wte", None)]},
        "wpe": {"embedding": leaves[("wpe", None)]},
        "ln_f": {"scale": leaves[("ln_f_g", None)],
                 "bias": leaves[("ln_f_b", None)]},
    }
    for l in range(cfg["n_layer"]):
        g = lambda name: leaves[(name, l)]      # noqa: E731
        tree[f"layer_{l}"] = {
            "ln1": {"scale": g("ln_1_g"), "bias": g("ln_1_b")},
            "MultiHeadAttention_0": {
                "qkv": {"kernel": g("c_attn_w").reshape(H, 3, heads, d),
                        "bias": g("c_attn_b").reshape(3, heads, d)},
                "out": {"kernel": g("c_proj_w").reshape(heads, d, H),
                        "bias": g("c_proj_b")},
            },
            "ln2": {"scale": g("ln_2_g"), "bias": g("ln_2_b")},
            "fc": {"kernel": g("fc_w"), "bias": g("fc_b")},
            "proj": {"kernel": g("proj_w"), "bias": g("proj_b")},
        }
    return tree


def program_parts(tree: dict, cfg: dict) -> dict:
    """The program's tree (of arrays) -> ``{(part, layer): array}`` with
    the fused q|k|v leaves split: a key's bias has no gradient under the
    softmax while the query's and the value's have, so the three are
    compared apart."""
    out = {("wte", None): tree["wte"]["embedding"],
           ("wpe", None): tree["wpe"]["embedding"],
           ("ln_f_g", None): tree["ln_f"]["scale"],
           ("ln_f_b", None): tree["ln_f"]["bias"]}
    for l in range(cfg["n_layer"]):
        t = tree[f"layer_{l}"]
        a = t["MultiHeadAttention_0"]
        for i, part in enumerate("qkv"):
            out[(f"c_attn_w.{part}", l)] = a["qkv"]["kernel"][:, i]
            out[(f"c_attn_b.{part}", l)] = a["qkv"]["bias"][i]
        out.update({
            ("ln_1_g", l): t["ln1"]["scale"], ("ln_1_b", l): t["ln1"]["bias"],
            ("c_proj_w", l): a["out"]["kernel"],
            ("c_proj_b", l): a["out"]["bias"],
            ("ln_2_g", l): t["ln2"]["scale"], ("ln_2_b", l): t["ln2"]["bias"],
            ("fc_w", l): t["fc"]["kernel"], ("fc_b", l): t["fc"]["bias"],
            ("proj_w", l): t["proj"]["kernel"],
            ("proj_b", l): t["proj"]["bias"]})
    return out


def reference_parts(params: dict, cfg: dict) -> dict:
    """The reference's stacked tree -> the same ``{(part, layer): array}``."""
    out = {(k, None): v for k, v in params.items() if k != "h"}
    for name, stacked in params["h"].items():
        for l in range(cfg["n_layer"]):
            x = stacked[l]
            if name.startswith("c_attn"):
                for part, piece in zip("qkv", _split3(x)):
                    out[(f"{name}.{part}", l)] = piece
            else:
                out[(name, l)] = x
    return out


def _split3(x):
    n = x.shape[-1] // 3
    return x[..., :n], x[..., n:2 * n], x[..., 2 * n:]


# ---------------------------------------------------------------------
# the train lane's seams that depend on the model

# the class of ``train/driver.py`` whose batches the benchmark's replace
SYNTHETIC_SOURCE = "SyntheticTokens"


def program_constants(cfg: dict) -> list[tuple[str, str, object]]:
    """``(module, attribute, value)`` set before the program builds its
    model: the configuration's ``assumed`` dropout rates (0: a plain
    reference cannot draw the program's masks, and the program has no
    flag).  ``models/gpt.py`` has no constant for attention dropout: it
    applies none."""
    a = cfg["assumed"]
    return [("tpu_hc_bench.models.gpt", "EMBED_DROPOUT", a["embd_pdrop"]),
            ("tpu_hc_bench.models.gpt", "RESID_DROPOUT", a["resid_pdrop"])]


def train_flags(cfg: dict, mix: dict, arm_flags: list[str],
                rehearsal: bool) -> list[str]:
    """The program's flags that this family's mixes and arm add; the CPU
    rehearsal runs neither the Mosaic kernel nor bfloat16."""
    if rehearsal:
        arm_flags = [f for f in arm_flags if "attention_impl" not in f
                     and "use_fp16" not in f]
    return [f"--seq_len={mix['seq_len']}"] + arm_flags


def vocab_size(cfg: dict) -> int:
    return cfg["vocab_size"]


def train_batch(cfg: dict, mix: dict, seed: int, chips: int):
    """The fixed token batch the train lane feeds every step: rows that
    all differ, drawn from the seed; ``(tokens, targets, weights)`` as
    the driver's synthetic source hands them over."""
    b = mix["batch_per_chip"] * chips
    s = mix["seq_len"]
    tokens = traffic.seed_rng(seed, 6).integers(
        1, vocab_size(cfg), (b, s)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    weights = np.ones_like(tokens, np.float32)
    weights[:, -1] = 0.0
    return tokens, targets, weights


# ---------------------------------------------------------------------
# operations from shapes (a multiply-add is 2), kept with the benchmark
# so that no change to the program can move them


def dims(cfg: dict) -> dict:
    H = cfg["n_embd"]
    return {"H": H, "F": cfg["assumed"].get("n_inner") or 4 * H,
            "L": cfg["n_layer"], "V": cfg["vocab_size"],
            "P": cfg["n_positions"], "heads": cfg["n_head"]}


def params(cfg: dict) -> dict:
    """Parameter counts: ``matmul`` is what a token multiplies through
    (layers + the tied head), ``total`` adds the position table, norms
    and biases."""
    z = dims(cfg)
    H, F, L, V, P = z["H"], z["F"], z["L"], z["V"], z["P"]
    per_layer = 4 * H * H + 2 * H * F
    small = L * (4 * H + 3 * H + H + F + H) + 2 * H
    return {"matmul": L * per_layer + V * H,
            "total": L * per_layer + V * H + P * H + small}


def decode_flops_per_token(cfg: dict) -> float:
    """2 x matmul parameters: the weight reads' arithmetic for one row of
    a decode step (attention over the cache left out: a lower bound)."""
    return 2.0 * params(cfg)["matmul"]


def attention_forward_flops(cfg: dict, seq: int, causal: bool = True) -> float:
    """QK^T and PV over one sequence, every layer: 2 matmuls of
    2 x S x S x H, halved under the causal mask."""
    z = dims(cfg)
    full = z["L"] * 2 * 2.0 * seq * seq * z["H"]
    return full / 2 if causal else full


def sequence_forward_flops(cfg: dict, seq: int) -> float:
    """One causal forward over ``seq`` tokens (prefill then decode adds
    up to the same sum)."""
    return seq * decode_flops_per_token(cfg) + attention_forward_flops(
        cfg, seq)


def train_step_flops_per_example(cfg: dict, mix: dict) -> float:
    """Forward + backward (2x forward), nothing recomputed, the output
    head computed at every position."""
    return 3.0 * sequence_forward_flops(cfg, mix["seq_len"])


def attention_calls(cfg: dict, mix: dict) -> dict:
    """The shapes of one attention-kernel call in a train step, and how
    many layers make one (forward and backward each once a layer)."""
    z = dims(cfg)
    return {"batch": mix["batch_per_chip"], "seq": mix["seq_len"],
            "heads": z["heads"], "head_dim": z["H"] // z["heads"],
            "layers": z["L"]}


# ---------------------------------------------------------------------
# the CPU rehearsal's sizes

# 0.08 x sqrt(64) = 0.02 x sqrt(1024): a tiny layer then adds to the
# residual stream what a published-width one does
TINY = {"n_layer": 2, "n_embd": 64, "n_head": 4, "initializer_range": 0.08}


def tiny_config(cfg: dict) -> dict:
    cfg = copy.deepcopy(cfg)
    cfg.update(TINY)
    cfg["assumed"]["n_inner"] = 4 * TINY["n_embd"]
    # the CPU multiplies float32 in float32, whatever precision is asked
    # for: there the serve arm's stated arithmetic is plain float32
    cfg["serve_arm"]["reference_precision"] = "f32"
    return cfg


def shrink_program(cfg: dict) -> None:
    """Point the program's registry entry for this model at a tiny
    member of the same family (the registry reads the factory when asked,
    so this holds for this process only)."""
    from tpu_hc_bench.models import gpt

    def tiny(num_classes=0, dtype=None, attention_impl="dense",
             max_len=None, remat=False, seq_axis=None, scan_layers=False):
        import jax.numpy as jnp

        return gpt.GPTLM(hidden=cfg["n_embd"], num_layers=cfg["n_layer"],
                         heads=cfg["n_head"], ffn=4 * cfg["n_embd"],
                         dtype=dtype or jnp.float32,
                         attention_impl=attention_impl,
                         max_len=max(cfg["n_positions"], max_len or 0),
                         remat=remat, seq_axis=seq_axis,
                         scan_layers=scan_layers)

    setattr(gpt, cfg["program_model"], tiny)
