"""3-D hybrid sharding (round 2): DPxSPxTP and DPxPPxTP.

The one-minor-axis restriction is lifted: ``build_mesh`` composes minor
axes, and the step builders run PP/SP as *manual* shard_map axes with the
model axis *auto* (GSPMD partitions the per-shard math and inserts the
Megatron all-reduces).  Numeric checks pin the hybrid against a control
with the SAME dp/sp (or dp/pp) degrees on half the devices, so tensor
parallelism is the only difference — its transparency is the property
under test.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_hc_bench import flags
from tpu_hc_bench.data.synthetic import SyntheticTokens
from tpu_hc_bench.models import create_model
from tpu_hc_bench.topology import (
    DATA_AXIS, MODEL_AXIS, PIPE_AXIS, SEQ_AXIS, build_mesh, compute_layout,
)
from tpu_hc_bench.train import step as step_mod


def test_build_mesh_composes_minor_axes(devices):
    layout = compute_layout(1, len(devices), len(devices))
    mesh = build_mesh(layout, pipeline_parallel=2, model_parallel=2)
    assert mesh.axis_names == (DATA_AXIS, PIPE_AXIS, MODEL_AXIS)
    assert mesh.shape == {DATA_AXIS: 2, PIPE_AXIS: 2, MODEL_AXIS: 2}
    mesh = build_mesh(layout, sequence_parallel=2, model_parallel=2)
    assert mesh.axis_names == (DATA_AXIS, SEQ_AXIS, MODEL_AXIS)
    # DP-only keeps the 2-D (data, model=1) shape
    mesh = build_mesh(layout)
    assert mesh.axis_names == (DATA_AXIS, MODEL_AXIS)
    assert mesh.shape[MODEL_AXIS] == 1
    with pytest.raises(ValueError, match="not divisible"):
        build_mesh(layout, pipeline_parallel=3, model_parallel=2)


def _sp_tp_setup(devices, n_devices, tp):
    """llama_tiny (no dropout) with ring attention, dp=2 x sp=2 x tp."""
    layout = compute_layout(1, n_devices, len(devices))
    mesh = build_mesh(layout, sequence_parallel=2, model_parallel=tp)
    cfg = flags.BenchmarkConfig(
        model="llama_tiny", batch_size=1, sequence_parallel=2,
        model_parallel=tp, attention_impl="ring",
    ).resolve()
    model, spec = create_model("llama_tiny", attention_impl="ring",
                               seq_axis=SEQ_AXIS)
    batch = SyntheticTokens(4, 64, vocab_size=1024, seed=0,
                            causal_lm=True).batch()
    init_model = model.clone(attention_impl="dense", seq_axis=None)
    state = step_mod.make_train_state(init_model, cfg, batch)
    state = state.replace(apply_fn=model.apply)
    if tp > 1:
        state = step_mod.shard_state_tp(state, mesh)
    else:
        state = step_mod.replicate_state(state, mesh)
    train_step = step_mod.build_train_step(mesh, cfg, spec)
    from jax.sharding import PartitionSpec as P

    dev_batch = step_mod.shard_batch(batch, mesh, P(DATA_AXIS, SEQ_AXIS))
    return state, train_step, dev_batch


def test_dp_sp_tp_matches_dp_sp(devices):
    """dp2 x sp2 x tp2 (8 devs) == dp2 x sp2 (4 devs): TP transparent."""
    rng = jax.random.PRNGKey(0)
    losses = []
    for n, tp in ((4, 1), (8, 2)):
        state, train_step, batch = _sp_tp_setup(devices, n, tp)
        if tp > 1:
            wq = state.params["layer_0"]["attn"]["wq"]["kernel"]
            assert MODEL_AXIS in wq.sharding.spec
        for _ in range(3):
            state, metrics = train_step(state, batch, rng)
        losses.append(float(jax.device_get(metrics["loss"])))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-4)


def _pp_tp_setup(devices, n_devices, tp):
    """GPT-tiny, deterministic (dropout off), dp=2 x pp=2 x tp."""
    from tpu_hc_bench.models.gpt import GPTLM
    from tpu_hc_bench.parallel import pipeline as pipe_mod

    layout = compute_layout(1, n_devices, len(devices))
    mesh = build_mesh(layout, pipeline_parallel=2, model_parallel=tp)
    cfg = flags.BenchmarkConfig(model="gpt2", batch_size=4,
                                pipeline_parallel=2).resolve()
    model = GPTLM(vocab_size=64, hidden=32, num_layers=4, heads=4,
                  ffn=64, max_len=16)
    batch = SyntheticTokens(8, 16, vocab_size=64, seed=0,
                            causal_lm=True).batch()
    params, opt_state = pipe_mod.make_pp_state(model, cfg, batch[0], mesh,
                                               tp=tp > 1)
    step, _ = pipe_mod.build_pp_train_step(
        mesh, model, cfg, 2, params, opt_state, deterministic=True,
        tp=tp > 1)
    from jax.sharding import NamedSharding, PartitionSpec as P

    dev_batch = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P(DATA_AXIS))),
        batch)
    return params, opt_state, step, dev_batch


def test_dp_pp_tp_matches_dp_pp(devices):
    """dp2 x pp2 x tp2 (8 devs) == dp2 x pp2 (4 devs)."""
    losses = []
    for n, tp in ((4, 1), (8, 2)):
        params, opt_state, step, batch = _pp_tp_setup(devices, n, tp)
        if tp > 1:
            fc = params["trunk"]["fc"]["kernel"]
            assert MODEL_AXIS in fc.sharding.spec
            assert fc.sharding.spec[0] == PIPE_AXIS
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(jax.device_get(loss)))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-4)


def test_driver_sp_tp_end_to_end(mesh8):
    """--sequence_parallel 2 --model_parallel 2 through run_benchmark."""
    from tpu_hc_bench.train import driver

    cfg = flags.BenchmarkConfig(
        model="llama_tiny", batch_size=2, num_warmup_batches=1,
        num_batches=2, display_every=1, sequence_parallel=2,
        model_parallel=2, attention_impl="ring",
    ).resolve()
    out = []
    res = driver.run_benchmark(cfg, print_fn=out.append)
    text = "\n".join(out)
    assert "tensor parallel: 2-way (hybrid with SP)" in text
    assert np.isfinite(res.final_loss)


def test_driver_pp_tp_end_to_end(mesh8):
    """--pipeline_parallel 2 --model_parallel 2 through run_benchmark."""
    from tpu_hc_bench.train import driver

    cfg = flags.BenchmarkConfig(
        model="moe_tiny", batch_size=4, num_warmup_batches=1,
        num_batches=2, display_every=1, pipeline_parallel=2,
        model_parallel=2,
    ).resolve()
    out = []
    res = driver.run_benchmark(cfg, print_fn=out.append)
    text = "\n".join(out)
    assert "tensor parallel: 2-way (hybrid with PP)" in text
    assert np.isfinite(res.final_loss)


def test_rejects_unsupported_combos():
    # rejected at flag resolution, before any mesh is built
    with pytest.raises(ValueError, match="not a supported composition"):
        flags.BenchmarkConfig(
            model="bert_tiny", batch_size=2, pipeline_parallel=2,
            sequence_parallel=2,
        ).resolve()
    with pytest.raises(ValueError, match="'model' axis"):
        flags.BenchmarkConfig(
            model="moe_tiny", batch_size=2, model_parallel=2,
            expert_parallel=2,
        ).resolve()
    with pytest.raises(ValueError, match="data parallelism only"):
        flags.BenchmarkConfig(
            model="moe_tiny", batch_size=2, expert_parallel=2,
            pipeline_parallel=2,
        ).resolve()
