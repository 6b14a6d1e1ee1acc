"""The ``mellum2`` family's benchmark files: the configuration against the
catalog's numbers, the family's counts against the configuration's
arithmetic, the weight mapping against the program's own tree at the
published size, the reference's precisions, the new readers on
hand-made contexts, the cell's mix, and the CPU rehearsal of the
cell."""

import json
import os
import subprocess
import sys

import pytest

from families import mellum2 as fam
from harness import spec, traffic

BENCH = spec.load_benchmark()
CFG = spec.config_of(BENCH, "mellum2_12b_a2_5b_8l")
CELL = "mellum2-8l-serve-code-backlog"


def test_counts_are_the_configurations_arithmetic():
    """21,233,664 attention, 147,456 router, 396,361,728 experts and 4,608
    of norms a layer (417,747,456); 3,794,966,784 held; 6 window layers
    of 8; a token multiplies 8 of the 64 experts."""
    p = fam.params(CFG)
    assert p["attn"] == 21_233_664 and p["router"] == 147_456
    assert 64 * p["expert"] == 396_361_728
    assert p["layer"] == 417_747_456
    assert p["total"] == 3_794_966_784
    assert p["window_layers"] == 6
    assert p["per_token"] == 8 * (p["attn"] + p["router"] + 8 * p["expert"]
                                  ) + 2304 * 98304
    # a decode step over every expert of every layer reads ~6.3 GB of them
    assert round(fam.moe_decode_bytes(CFG, 8 * 64) / 1e9, 2) == 6.35


def test_the_window_band_is_the_masks_pairs():
    """The band's operations at a length under, at and over the window,
    against the pairs the mask shows."""
    import numpy as np

    for s in (100, 1024, 3000):
        i, j = np.arange(s)[:, None], np.arange(s)[None]
        pairs = int(((j <= i) & (j > i - 1024)).sum())
        assert fam.window_band_flops(CFG, s) == 4.0 * 32 * 128 * pairs
    assert fam.window_band_bytes(CFG, 8192) == 4 * 8192 * 32 * 128 * 2


def test_no_published_width_differs_and_depth_alone_is_cut():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "mellum2_12b_a2_5b_8l")
    assert entry["reduced"] == ["num_hidden_layers"] == CFG["reduced"]
    published = CFG["published"]
    for k, v in published.items():
        if k != "num_hidden_layers":
            assert CFG[k] == v, k
    assert published["num_hidden_layers"] == 28 and CFG[
        "num_hidden_layers"] == 8
    assert (CFG["num_experts"], CFG["vocab_size"], CFG["sliding_window"]) == (
        64, 98304, 1024)
    assert CFG["layer_types"][:8] == ["sliding_attention"] * 3 + [
        "full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert CFG["deployment"]["chips_sharing_a_layer"] == 1
    assert "mtp_head" in CFG["assumed"]


def test_the_program_preset_is_the_configuration():
    from tpu_hc_bench.models import mellum2 as mm

    model = mm.mellum2_12b_a2_5b_8l()
    for k, v in fam.program_sizes(CFG).items():
        assert getattr(model, k) == v, k


@pytest.mark.parametrize("size", ["tiny", "published"])
def test_program_tree_is_a_renaming_in_the_programs_types(size):
    import jax
    import jax.numpy as jnp

    from tpu_hc_bench.models import mellum2 as mm

    cfg = fam.tiny_config(CFG) if size == "tiny" else CFG
    model = mm.Mellum2LM(dtype=jnp.bfloat16, **fam.program_sizes(cfg))
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
    summary = {"bucket_util": {
                   "decode@128": {"steps": 10, "rows": 1280,
                                  "active_rows": 1280, "wall_s": 0.2},
                   "prefill@2048": {"steps": 1, "rows": 2048,
                                    "active_rows": 1500, "wall_s": 0.05}},
               "moe_experts_hit": 10 * 8 * 60,
               "op_parts": {"decode@128": {"fusion.1:f32[8]": "swa",
                                           "fusion.2:f32[8]": "moe"},
                            "prefill@2048": {"fusion.9:f32[8]": "swa"}}}
    ctx = {"summary": summary, "config": CFG, "window_s": 10.0,
           "seconds": 10.0,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
           "records": [{"arrival_s": 0.0, "queue_ms": 2600.0,
                        "ttft_ms": 2700.0, "prompt_len": 1500}],
           "trace": {"window_s": 1.0, "busy_s": 0.5,
                     "ops": {"fusion.1:f32[8]": 0.02,
                             "fusion.2:f32[8]": 0.01,
                             "fusion.9:f32[8]": 0.08},
                     "custom_calls": {
                         "flash_window_fwd.3:bf16[32,2048,128]": 0.001,
                         "flash_attention_fwd.2:bf16[32,2048,128]": 0.5}}}
    assert read("serve.device_share.swa")(ctx) == pytest.approx(20.0)
    # one decode step in the traced second, 0.01 s under moe, 480 experts
    share = read("serve.moe_decode_hbm_share")(ctx)
    assert share == pytest.approx(
        100 * fam.moe_decode_bytes(CFG, 480) / (0.01 * 819e9))
    # the trace opens at 2.5 s: the whole prefill of 2,048 lies in it
    least = fam.window_band_flops(CFG, 2048) / 197e12
    assert fam.window_band_bytes(CFG, 2048) / 819e9 < least
    assert read("kernel.flash_window_roofline")(ctx) == pytest.approx(
        100 * 6 * least / 0.001)
    # a program without the scope or the counter (the parent, another
    # family)
    other = dict(ctx, summary=dict(summary, op_parts={
        "decode@128": {"fusion.1:f32[8]": "ssm"}}, moe_experts_hit=None),
        trace=dict(ctx["trace"], custom_calls={}))
    for name in ("serve.device_share.swa", "serve.moe_decode_hbm_share",
                 "kernel.flash_window_roofline"):
        assert read(name)(other) is None


def test_the_code_mix_is_a_mixed_backlog_inside_the_context():
    mix = traffic.load_mix("code-mixed-backlog-128")
    reqs = traffic.generator_of(mix).requests(mix, 51.0, 2**31 + 5,
                                              fam.vocab_size(CFG))
    assert len(reqs) % 4 == 0 and len(reqs) >= 1000
    assert max(r["arrival_s"] for r in reqs) <= 5.1
    lens = [len(r["prompt"]) for r in reqs]
    outs = [r["output_len"] for r in reqs]
    assert 128 <= min(lens) and max(lens) <= 7680
    assert 32 <= min(outs) and max(outs) <= 512
    assert max(len(r["prompt"]) + r["output_len"] for r in reqs) <= 8192
    # about two thirds past the window, about one in seven past 4,096
    assert 0.6 < sum(n > 1024 for n in lens) / len(lens) < 0.75
    assert 0.1 < sum(n > 4096 for n in lens) / len(lens) < 0.18
    assert max(int(r["prompt"].max()) for r in reqs) < CFG["vocab_size"]
    assert mix["max_in_flight"] == 128 and mix["close_window_at_seconds"]


def test_run_py_rehearses_the_new_cell_from_the_command_line():
    got = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 37),
         "--seconds", "2", "--trace", "0", "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=spec.ROOT, capture_output=True, text=True, timeout=900)
    assert got.returncode == 0, got.stderr[-2000:]
    line = json.loads(got.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] == CELL and line["correct"] is True
    counts = line["counts"]
    assert counts["failed"] == 0 and counts["requests_finished"] > 0
    assert "metrics" not in line and "device" not in line
