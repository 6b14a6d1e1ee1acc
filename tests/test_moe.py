"""Mixture-of-Experts routing + expert parallelism on the virtual mesh.

Routing invariants (capacity, gate normalization, aux loss) are checked
directly on ``top_k_routing``; the DP x EP path (expert dim sharded over
the mesh "model" axis, GSPMD all-to-all dispatch) is checked numerically
against the replicated GSPMD step, mirroring test_tensor_parallel.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_hc_bench import flags
from tpu_hc_bench.data.synthetic import SyntheticTokens
from tpu_hc_bench.models import create_model
from tpu_hc_bench.models.moe import MoEFFN, top_k_routing
from tpu_hc_bench.topology import MODEL_AXIS, build_mesh, compute_layout
from tpu_hc_bench.train import step as step_mod


def test_routing_dispatch_invariants():
    b, s, e = 2, 16, 4
    c = s  # capacity == group size: overflow is impossible
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(0), (b, s, e)), axis=-1)
    dispatch, combine, aux = top_k_routing(probs, top_k=2, capacity=c)
    assert dispatch.shape == (b, s, e, c)
    # nothing dropped: every token occupies exactly top_k slots with
    # combine weights summing to 1
    np.testing.assert_allclose(dispatch.sum(axis=(2, 3)), 2.0, atol=1e-6)
    np.testing.assert_allclose(combine.sum(axis=(2, 3)), 1.0, atol=1e-6)
    # each expert slot holds at most one token (per group)
    assert float(dispatch.sum(axis=1).max()) <= 1.0 + 1e-6
    # aux loss is ~1 for near-balanced routing, >= 1 in general
    assert 0.5 < float(aux) < 4.0


def test_routing_respects_capacity():
    # all tokens prefer expert 0 -> only `capacity` survive there
    b, s, e, c = 1, 12, 4, 2
    logits = jnp.zeros((b, s, e)).at[..., 0].set(10.0)
    probs = jax.nn.softmax(logits, axis=-1)
    dispatch, combine, _ = top_k_routing(probs, top_k=1, capacity=c)
    assert float(dispatch[..., 0, :].sum()) == pytest.approx(c)
    # dropped tokens have zero combine weight (residual carries them)
    per_token = combine.sum(axis=(2, 3))[0]
    assert float(per_token[:c].min()) > 0.9
    np.testing.assert_allclose(per_token[c:], 0.0, atol=1e-6)


def test_moe_ffn_forward_backward():
    layer = MoEFFN(hidden=16, ffn=32, num_experts=4, top_k=2)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 16))
    params = layer.init(jax.random.PRNGKey(2), x)["params"]

    def loss_fn(p):
        y, updated = layer.apply({"params": p}, x, mutable=["losses"])
        aux = sum(jnp.sum(t) for t in jax.tree.leaves(updated["losses"]))
        return jnp.sum(y ** 2) + 0.01 * aux

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    # router and both expert tensors receive gradient
    for name in ("router", "wi", "wo"):
        leaf = grads[name]["kernel"] if name == "router" else grads[name]
        assert float(jnp.abs(leaf).max()) > 0.0


def _setup(expert_parallel, devices, batch=8):
    layout = compute_layout(num_hosts=1, workers_per_host=len(devices),
                            chips_per_host=len(devices))
    mesh = build_mesh(layout, model_parallel=expert_parallel)
    cfg = flags.BenchmarkConfig(
        model="moe_tiny", batch_size=1, variable_update="replicated",
        expert_parallel=expert_parallel,
    ).resolve()
    model, spec = create_model("moe_tiny")
    raw = SyntheticTokens(batch, 32, vocab_size=1024, seed=0,
                          causal_lm=True).batch()
    state = step_mod.make_train_state(model, cfg, raw)
    if expert_parallel > 1:
        state = step_mod.shard_state_tp(state, mesh, mode="ep")
    else:
        state = step_mod.replicate_state(state, mesh)
    train_step = step_mod.build_train_step(mesh, cfg, spec)
    dev_batch = step_mod.shard_batch(raw, mesh)
    return state, train_step, dev_batch


def test_ep_param_spec_rules():
    spec = step_mod.tp_param_spec("layer_0/moe/wi", 3, mode="ep")
    assert spec[0] == MODEL_AXIS
    spec = step_mod.tp_param_spec("layer_0/moe/wo", 3, mode="ep")
    assert spec[0] == MODEL_AXIS
    # ep mode leaves the dense trunk replicated (unlike tp mode)
    assert (step_mod.tp_param_spec("layer_0/MultiHeadAttention_0/qkv/kernel",
                                   4, mode="ep")
            == jax.sharding.PartitionSpec())


def test_ep_matches_replicated(devices):
    rng = jax.random.PRNGKey(0)
    state_r, step_r, batch_r = _setup(1, devices)
    state_e, step_e, batch_e = _setup(4, devices)

    # expert tensors really are sharded over the model axis
    wi = state_e.params["layer_0"]["moe"]["wi"]
    assert wi.sharding.spec[0] == MODEL_AXIS

    losses = []
    for state, train_step, batch in ((state_r, step_r, batch_r),
                                     (state_e, step_e, batch_e)):
        for _ in range(3):
            state, metrics = train_step(state, batch, rng)
        losses.append(float(jax.device_get(metrics["loss"])))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-4)


def test_ragged_matches_einsum_no_drops():
    """With capacity sized so nothing drops, the ragged (grouped-matmul)
    impl must equal the GShard einsum impl exactly (same routing, same
    gates; only the data movement differs)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    # capacity_factor = e/top_k makes capacity == s: overflow impossible
    kw = dict(hidden=32, ffn=64, num_experts=4, top_k=2,
              capacity_factor=2.0)
    einsum_layer = MoEFFN(**kw, impl="einsum")
    ragged_layer = MoEFFN(**kw, impl="ragged")
    params = einsum_layer.init(jax.random.PRNGKey(2), x)["params"]

    def run(layer):
        y, upd = layer.apply({"params": params}, x, mutable=["losses"])
        aux = sum(jnp.sum(t) for t in jax.tree.leaves(upd["losses"]))
        return y, aux

    y_e, aux_e = run(einsum_layer)
    y_r, aux_r = run(ragged_layer)
    np.testing.assert_allclose(np.asarray(y_e), np.asarray(y_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(aux_e), float(aux_r), rtol=1e-6)


def test_ragged_backward_and_no_drops():
    """Ragged impl: gradients flow to router and experts; capacity-free
    dispatch keeps every token (combine weights sum to 1)."""
    layer = MoEFFN(hidden=16, ffn=32, num_experts=4, top_k=2, impl="ragged")
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 16))
    params = layer.init(jax.random.PRNGKey(4), x)["params"]

    def loss_fn(p):
        y, _ = layer.apply({"params": p}, x, mutable=["losses"])
        return jnp.sum(y ** 2)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    for name in ("router", "wi", "wo"):
        leaf = grads[name]["kernel"] if name == "router" else grads[name]
        assert float(jnp.abs(leaf).max()) > 0.0


def test_ragged_chunked_matches_unchunked():
    """The chunked grouped-matmul path (round 2: bounded VMEM via lax.map
    over sorted chunks) is bitwise-equivalent routing to the one-shot
    ragged_dot — only the matmul tiling differs."""
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 16))
    kw = dict(hidden=16, ffn=32, num_experts=4, top_k=2, impl="ragged")
    one_shot = MoEFFN(**kw, ragged_chunk=1 << 20)
    chunked = MoEFFN(**kw, ragged_chunk=16)     # 2*32*2=128 pairs -> 8 chunks
    params = one_shot.init(jax.random.PRNGKey(8), x)["params"]

    def run(layer, p):
        y, _ = layer.apply({"params": p}, x, mutable=["losses"])
        return y

    y1 = run(one_shot, params)
    y2 = run(chunked, params)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-6)
    # gradients flow through the chunked lax.map path too
    g = jax.grad(lambda p: jnp.sum(run(chunked, p) ** 2))(params)
    assert float(jnp.abs(g["wi"]).max()) > 0.0


def test_capacity_factor_plumbs_through():
    """--moe_capacity_factor reaches MoEFFN; lower factor drops tokens."""
    model, _ = create_model("moe_tiny", moe_capacity_factor=0.5)
    assert model.moe_capacity_factor == 0.5
    with pytest.raises(ValueError, match="MoE members"):
        create_model("gpt2", moe_capacity_factor=0.5)
    # behavioral: capacity 0.5 drops tokens that capacity 2.0 keeps
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 32, 16))
    tight = MoEFFN(hidden=16, ffn=32, num_experts=4, top_k=2,
                   capacity_factor=0.25)
    roomy = MoEFFN(hidden=16, ffn=32, num_experts=4, top_k=2,
                   capacity_factor=2.0)
    params = tight.init(jax.random.PRNGKey(10), x)["params"]
    yt, _ = tight.apply({"params": params}, x, mutable=["losses"])
    yr, _ = roomy.apply({"params": params}, x, mutable=["losses"])
    assert not np.allclose(np.asarray(yt), np.asarray(yr))


def test_moe_impl_flag_guards():
    with pytest.raises(ValueError, match="moe_impl=einsum"):
        flags.BenchmarkConfig(expert_parallel=2, moe_impl="ragged").resolve()
    # capacity factor is an einsum-only concept: loud error, not silence
    with pytest.raises(ValueError, match="einsum dispatch only"):
        flags.BenchmarkConfig(moe_impl="ragged",
                              moe_capacity_factor=0.5).resolve()
    # TP also shards the expert tensors (tp_param_spec moe/ rules)
    with pytest.raises(ValueError, match="moe_impl=einsum"):
        flags.BenchmarkConfig(model_parallel=2, moe_impl="ragged").resolve()
    from tpu_hc_bench.models import create_model
    with pytest.raises(ValueError, match="MoE members"):
        create_model("gpt2", moe_impl="ragged")


def test_ep_exclusive_with_tp():
    with pytest.raises(ValueError, match="exclusive"):
        flags.BenchmarkConfig(model_parallel=2, expert_parallel=2).resolve()


def test_moe_impl_auto_translation():
    """--moe_impl=auto picks by the measured crossover (round 3,
    BASELINE.md): einsum short-seq/EP/TP, ragged at long seq."""
    from tpu_hc_bench import flags as fl

    cfg = fl.BenchmarkConfig(model="moe_tiny", moe_impl="auto").resolve()
    assert cfg.moe_impl == "einsum"              # short seq
    assert any("auto->einsum" in l for l in cfg.summary_lines())
    cfg = fl.BenchmarkConfig(model="gpt2_moe", moe_impl="auto",
                             seq_len=4096).resolve()
    assert cfg.moe_impl == "ragged"              # long seq, single-shard
    cfg = fl.BenchmarkConfig(model="gpt2_moe", moe_impl="auto",
                             seq_len=4096, expert_parallel=2).resolve()
    assert cfg.moe_impl == "einsum"              # EP needs GSPMD einsum


def test_ragged_f_chunk_matches_full_width():
    """The F-tiled grouped matmuls (round 4: slicing the [E,H,F]/[E,F,H]
    weights so Mosaic's scoped-VMEM never sees the full contraction) are
    numerically the full-width ragged path: gelu is elementwise over F
    and the second matmul's F-contraction distributes over slices.
    ffn=36 with chunk 8 also exercises the zero-padding tail."""
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 16, 12))
    kw = dict(hidden=12, ffn=36, num_experts=4, top_k=2, impl="ragged")
    full = MoEFFN(**kw, ragged_f_chunk=0)
    tiled = MoEFFN(**kw, ragged_f_chunk=8)
    params = full.init(jax.random.PRNGKey(10), x)["params"]

    def run(layer, p):
        y, _ = layer.apply({"params": p}, x, mutable=["losses"])
        return y

    np.testing.assert_allclose(np.asarray(run(full, params)),
                               np.asarray(run(tiled, params)),
                               rtol=1e-5, atol=1e-6)
    g = jax.grad(lambda p: jnp.sum(run(tiled, p) ** 2))(params)
    assert float(jnp.abs(g["wi"]).max()) > 0.0
    # the tiled path also composes with row-chunking (the lax.map arm)
    both = MoEFFN(**kw, ragged_f_chunk=8, ragged_chunk=16)
    np.testing.assert_allclose(np.asarray(run(full, params)),
                               np.asarray(run(both, params)),
                               rtol=1e-5, atol=1e-6)
