"""Tensor parallelism (GSPMD, Megatron-style shardings) on the virtual mesh.

DP x TP runs on the 8-device CPU mesh: params sharded per
``step.tp_param_spec``, batch over the data axis, XLA inserting the TP
collectives.  Checked against the replicated GSPMD step numerically.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_hc_bench import flags

# the TP-sharded forward matches the replicated arm to the GSPMD
# partitioner's reassociation error
TP_RTOL = 1e-4
VIT_TP_RTOL = 1e-4
from tpu_hc_bench.data.synthetic import SyntheticTokens
from tpu_hc_bench.models import create_model
from tpu_hc_bench.topology import MODEL_AXIS, build_mesh, compute_layout
from tpu_hc_bench.train import step as step_mod


def _setup(model_parallel, devices, batch=8, model_name="bert_tiny",
           num_classes=1000, make_batch=None):
    layout = compute_layout(num_hosts=1, workers_per_host=len(devices),
                            chips_per_host=len(devices))
    mesh = build_mesh(layout, model_parallel=model_parallel)
    cfg = flags.BenchmarkConfig(
        model=model_name, batch_size=1, variable_update="replicated",
        model_parallel=model_parallel, num_classes=num_classes,
    ).resolve()
    model, spec = create_model(model_name, num_classes=num_classes)
    raw = (make_batch(batch) if make_batch is not None
           else SyntheticTokens(batch, 32, vocab_size=1024, seed=0).batch())
    state = step_mod.make_train_state(model, cfg, raw)
    if model_parallel > 1:
        state = step_mod.shard_state_tp(state, mesh)
    else:
        state = step_mod.replicate_state(state, mesh)
    train_step = step_mod.build_train_step(mesh, cfg, spec)
    dev_batch = step_mod.shard_batch(raw, mesh)
    return state, train_step, dev_batch


def test_tp_param_spec_rules():
    spec = step_mod.tp_param_spec("layer_0/MultiHeadAttention_0/qkv/kernel", 4)
    assert MODEL_AXIS in spec
    assert step_mod.tp_param_spec("layer_0/Dense_0/kernel", 2)[1] == MODEL_AXIS
    assert step_mod.tp_param_spec("layer_0/Dense_1/kernel", 2)[0] == MODEL_AXIS
    # unmatched and CNN params replicate
    assert step_mod.tp_param_spec("conv_init/kernel", 4) == jax.sharding.PartitionSpec()


def test_tp_matches_replicated(devices):
    rng = jax.random.PRNGKey(0)
    state_r, step_r, batch_r = _setup(1, devices)
    state_t, step_t, batch_t = _setup(2, devices)

    # qkv kernels really are sharded over the model axis
    qkv = state_t.params["layer_0"]["MultiHeadAttention_0"]["qkv"]["kernel"]
    assert MODEL_AXIS in qkv.sharding.spec

    losses = []
    for state, train_step, batch in ((state_r, step_r, batch_r),
                                     (state_t, step_t, batch_t)):
        for _ in range(3):
            state, metrics = train_step(state, batch, rng)
        losses.append(float(jax.device_get(metrics["loss"])))
    np.testing.assert_allclose(losses[0], losses[1], rtol=TP_RTOL)


def test_vit_tp_matches_replicated(devices):
    """ViT is tensor-parallel for free: its encoder block shares the
    qkv/out/fc/proj param names the Megatron TP rules match."""
    from tpu_hc_bench.data.synthetic import SyntheticImages

    def images(batch):
        return SyntheticImages(batch, (32, 32, 3), num_classes=10).batch()

    rng = jax.random.PRNGKey(0)
    losses = []
    for mp in (1, 2):
        state, train_step, batch = _setup(
            mp, devices, model_name="vit_tiny", num_classes=10,
            make_batch=images)
        if mp > 1:
            qkv = state.params["layer_0"]["MultiHeadAttention_0"]["qkv"][
                "kernel"]
            assert MODEL_AXIS in qkv.sharding.spec
        for _ in range(2):
            state, metrics = train_step(state, batch, rng)
        losses.append(float(jax.device_get(metrics["loss"])))
    np.testing.assert_allclose(losses[0], losses[1], rtol=VIT_TP_RTOL)


def test_llama_tp_matches_replicated(devices):
    """llama's wq/wk/wv/wo + gate/up/down names have their own TP rules;
    before them, --model_parallel on llama silently degraded to DP."""
    rng = jax.random.PRNGKey(0)
    losses = []
    for mp in (1, 2):
        state, train_step, batch = _setup(mp, devices,
                                          model_name="llama_tiny")
        if mp > 1:
            wq = state.params["layer_0"]["attn"]["wq"]["kernel"]
            gate = state.params["layer_0"]["gate"]["kernel"]
            assert MODEL_AXIS in wq.sharding.spec
            assert MODEL_AXIS in gate.sharding.spec
        for _ in range(3):
            state, metrics = train_step(state, batch, rng)
        losses.append(float(jax.device_get(metrics["loss"])))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-4)


def test_tp_rejects_unmatched_model(devices):
    """Non-transformer params match no TP rule -> loud error, not silent
    DP degradation (ADVICE r1 medium)."""
    from tpu_hc_bench.data.synthetic import SyntheticImages

    def images(batch):
        return SyntheticImages(batch, (28, 28, 3), num_classes=10).batch()

    with pytest.raises(ValueError, match="no param matched"):
        _setup(2, devices, model_name="lenet", num_classes=10,
               make_batch=images)


def test_tp_rejects_bad_degree(devices):
    layout = compute_layout(num_hosts=1, workers_per_host=len(devices),
                            chips_per_host=len(devices))
    with pytest.raises(ValueError, match="divisible"):
        build_mesh(layout, model_parallel=3)
