"""The ``solar_open2`` family's benchmark files: the configuration against
the catalog's numbers, the family's counts against the cut's arithmetic,
the reference's precisions, the readers of the new per-layer metrics on
hand-made contexts, and the CPU rehearsal of both cells PR 29 added."""

import argparse
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from families import solar_open2 as fam
from harness import rehearse, spec, traffic

BENCH = spec.load_benchmark()
CFG = spec.config_of(BENCH, "solar_open2_250b_ep8")
NEW_CELLS = ["solar-open2-ep8-serve-reason", "gpt2m-train-1k-dp4"]


def test_counts_are_the_cuts_arithmetic():
    """ISSUE 29's table: 137.7 M a KDA mixer, 109.1 M the GQA mixer,
    646.2 M of MoE held a layer, 3.31 B in all; a token multiplies the
    mixers, the routers, the shared experts, ONE expected routed expert a
    layer (8 x 40/320) and the held rows of the head."""
    p = fam.params(CFG)
    assert round(p["kda"] / 1e6, 1) == 137.7
    assert round(p["gqa"] / 1e6, 1) == 109.1
    assert round(p["moe_held"] / 1e6, 1) == 646.2
    assert round(p["total"] / 1e9, 2) == 3.31
    assert (p["kda_layers"], p["gqa_layers"]) == (3, 1)
    expert = 3 * 4096 * 1280
    want = (3 * p["kda"] + p["gqa"]
            + 4 * (4096 * 320 + expert + 1.0 * expert) + 4096 * 24576)
    assert p["per_token"] == want
    assert fam.decode_flops_per_token(CFG) == (
        2 * want + 7 * 3 * 64 * 128 * 128)
    # 128 rows: 3.22 GB of state read and written, 0.83 GB of weights
    b = fam.kda_decode_bytes(CFG, 128)
    assert 4.0e9 < b < 4.3e9
    assert fam.kda_decode_bytes(CFG, 64) < b


def test_no_published_width_differs_from_the_catalog_row():
    """Every number of the catalog row's ``config`` is in the file under
    the same key, but the three keys ``reduced`` names, whose published
    values stand beside them."""
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "solar_open2_250b_ep8")
    published = {
        "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "rms_norm_eps": 1e-05,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "gqa_interval": 3, "routed_scaling_factor": 1, "rope_theta": 10000,
        "partial_rotary_factor": 1}
    for k, v in published.items():
        assert CFG[k] == v, k
    assert CFG["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert CFG["gqa_layers"] == list(range(0, 48, 4))
    assert CFG["use_rope"] is False and CFG["use_gqa_gate"] is True
    assert sorted(entry["reduced"]) == sorted(CFG["published"])
    assert CFG["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 320,
                                "vocab_size": 196608}
    # the floors: a whole period, >= 8 experts, >= an eighth of the rows
    assert CFG["num_hidden_layers"] == 4 and CFG["n_routed_experts"] >= 8
    assert CFG["vocab_size"] * 8 >= CFG["published"]["vocab_size"]


def test_lower_precision_reads_a_larger_error():
    """At the tiny size: the stated bf16 arithmetic errs against float32,
    fp8 operands err several times more, and float32 is deterministic."""
    import jax
    import jax.numpy as jnp

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


def test_program_tree_is_a_renaming_in_the_programs_types():
    import jax
    import jax.numpy as jnp

    from tpu_hc_bench.models import create_model

    cfg = fam.tiny_config(CFG)
    fam.shrink_program(cfg)
    model, _ = create_model(cfg["program_model"], dtype=jnp.bfloat16)
    want = jax.tree.map(
        lambda x: (x.shape, str(x.dtype)),
        jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
            train=False))["params"])
    got = jax.tree.map(
        lambda x: (x.shape, str(x.dtype)),
        fam.program_tree(fam.reference.leaf_values(cfg, 5), cfg))
    assert got == want


def test_part_seconds_splits_a_shared_name_and_ignores_other_programs():
    ctx = {"trace": {"ops": {"fusion.1:f32[8]": 2.0, "fusion.2:f32[8]": 1.0,
                             "copy.3:f32[2]": 5.0}},
           "summary": {"op_parts": {
               "decode@4": {"fusion.1:f32[8]": "kda",
                            "fusion.2:f32[8]": "moe"},
               "prefill@8": {"fusion.1:f32[8]": "gqa"}}}}
    assert fam.part_seconds(ctx) == {"kda": 1.0, "gqa": 1.0, "moe": 1.0}
    assert fam.part_seconds(ctx, kinds=("decode",)) == {"kda": 2.0,
                                                        "moe": 1.0}
    assert fam.part_seconds({"trace": ctx["trace"], "summary": {}}) is None
    assert fam.part_seconds({"summary": ctx["summary"]}) is None


def test_new_readers_read_hand_made_contexts():
    read = spec.reader_of
    summary = {"state_slots": 96, "state_slot_steps": 128, "moe_picks": 800,
               "moe_picks_held": 100,
               "bucket_util": {"decode@128": {
                   "steps": 10, "rows": 1280, "active_rows": 1280,
                   "wall_s": 0.4}},
               "op_parts": {"decode@128": {"fusion.1:f32[8]": "kda"},
                            "prefill@512": {"fusion.9:f32[8]": "kda"}}}
    assert read("serve.state_slots_occupancy")({"summary": summary}) == 75.0
    assert read("serve.moe_picks_held_share")({"summary": summary}) == 12.5
    ctx = {"summary": summary, "config": CFG, "window_s": 10.0,
           "peaks": {"hbm_bytes_per_s": 819e9},
           "trace": {"window_s": 1.0, "busy_s": 0.5,
                     "ops": {"fusion.1:f32[8]": 0.01,
                             "fusion.9:f32[8]": 0.09}}}
    assert read("serve.device_share.kda")(ctx) == pytest.approx(20.0)
    # one decode step in the traced second, 0.01 s under kda
    share = read("serve.kda_decode_hbm_share")(ctx)
    assert share == pytest.approx(
        100 * fam.kda_decode_bytes(CFG, 128) / (0.01 * 819e9))
    assert read("train.collective_exposed_share")(
        {"chips": 4, "trace": {"window_s": 2.0,
                               "collective_exposed_s": 0.5}}) == 25.0
    assert read("train.collective_exposed_share")(
        {"chips": 1, "trace": {"window_s": 2.0,
                               "collective_exposed_s": 0.0}}) is None


def test_the_reason_mix_is_a_backlog_inside_the_configurations_context():
    mix = traffic.load_mix("reason-backlog-128")
    reqs = traffic.generator_of(mix).requests(mix, 51.0, 2**31 + 5,
                                              fam.vocab_size(CFG))
    assert len(reqs) % 4 == 0 and len(reqs) >= 300
    assert max(r["arrival_s"] for r in reqs) <= 5.1
    lens = [len(r["prompt"]) for r in reqs]
    outs = [r["output_len"] for r in reqs]
    assert 128 <= min(lens) and max(lens) <= 1536
    assert 192 <= min(outs) and max(outs) <= 768
    assert max(len(r["prompt"]) + r["output_len"] for r in reqs) <= 2304
    assert max(int(r["prompt"].max()) for r in reqs) < CFG["vocab_size"]
    assert mix["max_in_flight"] == 128 and mix["close_window_at_seconds"]


def test_the_dp4_cell_is_the_only_one_on_four_chips():
    four = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    assert four == ["gpt2m-train-1k-dp4"]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    one = traffic.load_mix("train-steady")
    dp4 = traffic.load_mix("train-steady-dp4")
    assert all(dp4[k] == one[k] for k in ("batch_per_chip", "seq_len",
                                          "fabric", "generator"))


def test_rehearsal_of_the_serve_cell_in_process(capsys):
    name = NEW_CELLS[0]
    cell = spec.cell_of(BENCH, name)
    cfg = spec.config_of(BENCH, cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    args = argparse.Namespace(seed=2**31 + 29, seconds=2.0, trace=0)
    result: dict = {}
    rc = rehearse.run(cell, cfg, mix, args,
                      os.path.join(spec.ROOT, ".bench_work"), result=result)
    out = capsys.readouterr().out
    assert rc == 0 and result["correct"] is True, out[-2000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["platform"] == "cpu" and "metrics" not in line


@pytest.mark.parametrize("name,flags", [
    (NEW_CELLS[0], ""),
    # the four-chip cell on four virtual CPU devices
    (NEW_CELLS[1], "--xla_force_host_platform_device_count=4"),
])
def test_run_py_rehearses_the_new_cells_from_the_command_line(name, flags):
    got = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", name, "--seed", str(2**31 + 27),
         "--seconds", "2", "--trace", "0", "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=flags),
        cwd=spec.ROOT, capture_output=True, text=True, timeout=900)
    assert got.returncode == 0, got.stderr[-2000:]
    line = json.loads(got.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] == name and line["correct"] is True
    counts = line["counts"]
    assert counts["failed"] == 0
    assert counts.get("requests_finished", counts.get("steps", 0)) > 0
    assert "metrics" not in line and "device" not in line


def test_the_selection_bias_evens_the_experts_use():
    """On fresh random tokens (not the calibration sequence) the balanced
    bias spreads the picks far more evenly over the experts than no bias
    does, layer by layer, and the share held lands near held / published."""
    import jax
    import jax.numpy as jnp

    cfg = fam.tiny_config(CFG)
    ref = fam.reference
    z = ref.sizes(cfg)
    params = ref.make_params(cfg, 11)
    toks = np.random.default_rng(1).integers(1, 256, (1, 400)).astype(
        np.int32)
    x = params["embed"][toks].astype(jnp.float32)
    worse = []
    for l, lp in enumerate(params["layers"]):
        x, h = ref._mix(x, lp, z, l, "f32")
        s = jax.nn.sigmoid(ref._f32("bsh,he->bse", h, lp["router"]))[0]

        def loads(bias):
            _, picked = jax.lax.top_k(s + bias, z["k"])
            return np.bincount(np.asarray(picked).ravel(),
                               minlength=z["E"]) / picked.size

        even, skewed = loads(lp["router_bias"]), loads(0.0)
        assert np.all(np.asarray(lp["router_bias"]) * 64 % 1 == 0)
        assert even.std() < 0.6 / z["E"]
        worse.append(skewed.std() / even.std())
        assert abs(even[:z["Eh"]].sum() - z["Eh"] / z["E"]) < 0.06
        x = x + ref.moe(h, lp, z, "f32")
    assert max(worse) > 2
