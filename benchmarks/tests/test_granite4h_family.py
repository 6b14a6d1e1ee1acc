"""The ``granite4h`` family's benchmark files: the configuration against
the catalog's numbers, the family's counts against ISSUE 35's
arithmetic, the weight mapping against the program's own tree at the
published size, the reference's precisions, the new readers on
hand-made contexts, the cell's mix, and the CPU rehearsal of the cell
PR 35 added."""

import json
import os
import subprocess
import sys

import pytest

from families import granite4h as fam
from harness import spec, traffic

BENCH = spec.load_benchmark()
CFG = spec.config_of(BENCH, "granite_4_0_h_micro")
CELL = "granite4h-micro-serve-chat-backlog"


def test_counts_are_the_issues_arithmetic():
    """25.85 M a Mamba-2 mixer, 10.49 M an attention mixer, 50.33 M an
    MLP, 3.19 B in all; 36 + 4 layers; a token multiplies every matrix
    once (the head is the embedding)."""
    p = fam.params(CFG)
    assert round(p["mamba"] / 1e6, 2) == 25.85
    assert round(p["attn"] / 1e6, 2) == 10.49
    assert round(p["mlp"] / 1e6, 2) == 50.33
    assert round(p["total"] / 1e9, 2) == 3.19
    assert (p["mamba_layers"], p["attn_layers"]) == (36, 4)
    assert 3.18e9 < p["per_token"] < p["total"]
    # 64 rows: 9.66 GB of state read and written, 1.86 GB of the 36
    # mixers' weights, 0.12 GB of tails: 69% of a 16.7 GB step
    b = fam.ssm_decode_bytes(CFG, 64)
    state = 36 * 2 * 64 * 64 * 64 * 128 * 4
    weights = 36 * p["mamba"] * 2
    tails = 36 * 2 * 64 * 3 * 4352 * 2
    assert b == state + weights + tails
    assert round(state / 1e9, 2) == 9.66 and round(weights / 1e9, 2) == 1.86


def test_no_published_width_differs_and_nothing_is_cut():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "granite_4_0_h_micro")
    assert entry["reduced"] == []
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 8, "intermediate_size": 8192,
        "shared_intermediate_size": 8192, "num_hidden_layers": 40,
        "vocab_size": 100352, "mamba_n_heads": 64, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_n_groups": 1, "mamba_expand": 2,
        "mamba_d_conv": 4, "mamba_chunk_size": 256,
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 8,
        "rms_norm_eps": 1e-05, "num_local_experts": 0,
        "position_embedding_type": "nope", "tie_word_embeddings": True}
    for k, v in published.items():
        assert CFG[k] == v, k
    assert [l for l, t in enumerate(CFG["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]


def test_the_program_preset_is_the_configuration():
    from tpu_hc_bench.models import granite4h as gh

    model = gh.granite_4_0_h_micro()
    for k, v in fam.program_sizes(CFG).items():
        assert getattr(model, k) == v, k


@pytest.mark.parametrize("size", ["tiny", "published"])
def test_program_tree_is_a_renaming_in_the_programs_types(size):
    import jax
    import jax.numpy as jnp

    from tpu_hc_bench.models import granite4h as gh

    cfg = fam.tiny_config(CFG) if size == "tiny" else CFG
    model = gh.GraniteHybridLM(dtype=jnp.bfloat16, **fam.program_sizes(cfg))
    want = jax.tree.map(
        lambda x: (x.shape, str(x.dtype)),
        jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
            train=False))["params"])
    got = jax.tree.map(
        lambda x: (x.shape, str(x.dtype)),
        jax.eval_shape(lambda: fam.program_tree(
            fam.reference.leaf_values(cfg, 5), cfg)))
    assert got == want
    if size == "published":
        n = sum(int(jnp.prod(jnp.array(s))) for s, _ in jax.tree.leaves(
            got, is_leaf=lambda x: isinstance(x, tuple)))
        assert n == fam.params(CFG)["total"]


def test_lower_precision_reads_a_larger_error():
    """At the tiny size: the stated bf16 arithmetic errs against float32,
    fp8 operands err several times more, and float32 is deterministic."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = fam.tiny_config(CFG)
    ref = fam.reference
    params = ref.make_params(cfg, 3)
    toks = np.random.default_rng(0).integers(1, 256, (1, 24)).astype(
        np.int32)

    def logits(precision):
        with jax.default_matmul_precision("highest"):
            return ref.logits_of(
                params, ref.hidden_states(params, toks, cfg, precision),
                precision)

    exact = logits("f32")
    err = {p: float(jnp.sqrt(jnp.mean(jnp.square(logits(p) - exact))))
           for p in ("f32", "bf16", "fp8")}
    assert err["f32"] == 0.0
    assert 0 < err["bf16"] < err["fp8"] / 3


def test_new_readers_read_hand_made_contexts():
    read = spec.reader_of
    summary = {"bucket_util": {"decode@64": {
                   "steps": 10, "rows": 640, "active_rows": 640,
                   "wall_s": 0.2}},
               "op_parts": {"decode@64": {"fusion.1:f32[8]": "ssm",
                                          "fusion.2:f32[8]": "mlp"},
                            "prefill@256": {"fusion.9:f32[8]": "ssm"}}}
    ctx = {"summary": summary, "config": CFG, "window_s": 10.0,
           "peaks": {"hbm_bytes_per_s": 819e9},
           "trace": {"window_s": 1.0, "busy_s": 0.5,
                     "ops": {"fusion.1:f32[8]": 0.02,
                             "fusion.2:f32[8]": 0.01,
                             "fusion.9:f32[8]": 0.08}}}
    assert read("serve.device_share.ssm")(ctx) == pytest.approx(20.0)
    # one decode step in the traced second, 0.02 s under ssm
    share = read("serve.ssm_decode_hbm_share")(ctx)
    assert share == pytest.approx(
        100 * fam.ssm_decode_bytes(CFG, 64) / (0.02 * 819e9))
    # a program without the scope (the parent, or another family)
    other = dict(ctx, summary=dict(summary, op_parts={
        "decode@64": {"fusion.1:f32[8]": "kda"}}))
    assert read("serve.device_share.ssm")(other) is None
    assert read("serve.ssm_decode_hbm_share")(other) is None


def test_the_chat_mix_is_a_short_backlog_inside_the_context():
    mix = traffic.load_mix("chat-short-backlog-64")
    reqs = traffic.generator_of(mix).requests(mix, 51.0, 2**31 + 5,
                                              fam.vocab_size(CFG))
    assert len(reqs) % 4 == 0 and len(reqs) >= 300
    assert max(r["arrival_s"] for r in reqs) <= 5.1
    lens = [len(r["prompt"]) for r in reqs]
    outs = [r["output_len"] for r in reqs]
    assert 32 <= min(lens) and max(lens) <= 1024
    assert 32 <= min(outs) and max(outs) <= 512
    assert max(len(r["prompt"]) + r["output_len"] for r in reqs) <= 1536
    assert max(int(r["prompt"].max()) for r in reqs) < CFG["vocab_size"]
    assert mix["max_in_flight"] == 64 and mix["close_window_at_seconds"]


def test_run_py_rehearses_the_new_cell_from_the_command_line():
    got = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 35),
         "--seconds", "2", "--trace", "0", "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=spec.ROOT, capture_output=True, text=True, timeout=900)
    assert got.returncode == 0, got.stderr[-2000:]
    line = json.loads(got.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] == CELL and line["correct"] is True
    counts = line["counts"]
    assert counts["failed"] == 0 and counts["requests_finished"] > 0
    assert "metrics" not in line and "device" not in line
