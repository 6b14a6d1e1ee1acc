"""ZeRO-1 arm (--variable_update=zero1) + --overlap_grad_comm.

Budget-conscious layout (tier-1 sits near the 870s ceiling): ONE
module-scoped fixture runs the psum and zero1 steps side by side and
every equivalence/memory assertion reads from it; the driver e2e is a
single kill/resume pair on the trivial member, which doubles as the
sharded-opt-state checkpoint proof.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_hc_bench import flags, resilience
from tpu_hc_bench.data.synthetic import SyntheticImages
from tpu_hc_bench.models import ModelSpec, TrivialModel
from tpu_hc_bench.train import driver, step as step_mod
from tpu_hc_bench.utils import checkpoint as ckpt


def tiny_cfg(**kw):
    base = dict(
        batch_size=2, num_warmup_batches=1, num_batches=4, display_every=2,
        model="trivial", num_classes=10, init_learning_rate=0.05,
    )
    base.update(kw)
    return flags.BenchmarkConfig(**base).resolve()


@pytest.fixture(scope="module")
def arm_states(mesh8):
    """psum and zero1 arms advanced 3 steps from identical init, with a
    small threshold so the gradient tree spans several buckets."""
    shape = (8, 8, 3)
    spec = ModelSpec("trivial", TrivialModel, shape, 1e6)
    model = TrivialModel(num_classes=10)
    batch = SyntheticImages(16, shape, num_classes=10).batch()
    dev_batch = step_mod.shard_batch(batch, mesh8)
    cfg_p = tiny_cfg(variable_update="psum", fusion_threshold_bytes=256)
    cfg_z = tiny_cfg(variable_update="zero1", fusion_threshold_bytes=256)
    state_p = step_mod.replicate_state(
        step_mod.make_train_state(model, cfg_p, batch), mesh8)
    state_z = step_mod.place_zero1_state(
        step_mod.make_zero1_state(model, cfg_z, batch, 8), mesh8)
    param_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree.leaves(state_z.params))
    sp = step_mod.build_train_step(mesh8, cfg_p, spec)
    sz = step_mod.build_train_step(mesh8, cfg_z, spec)
    rng = jax.random.PRNGKey(0)
    losses_p, losses_z = [], []
    for _ in range(3):
        state_p, mp = sp(state_p, dev_batch, rng)
        state_z, mz = sz(state_z, dev_batch, rng)
        losses_p.append(float(mp["loss"]))
        losses_z.append(float(mz["loss"]))
    return {"model": model, "spec": spec, "batch": batch,
            "dev_batch": dev_batch, "mesh": mesh8,
            "state_p": state_p, "state_z": state_z,
            "losses_p": losses_p, "losses_z": losses_z,
            "param_bytes": param_bytes}


def test_zero1_matches_psum_to_the_last_bits(arm_states):
    """Acceptance: the zero1 arm proves numerical equivalence to psum —
    the three steps' losses are EQUAL, and the f32 params after them
    agree leaf by leaf to the last bits.  Not bitwise: the scatter /
    shard-update / gather pipeline is elementwise-identical math, but
    ``psum`` and ``psum_scatter`` do not reduce across devices in one
    order on this JAX (0.9.0, 8 CPU devices).  Measured on this tree
    (PR 33): 220 of the kernel's 1,920 entries and 2 of the bias's 10
    differ, by at most 1.49e-8 absolute (one f32 ulp at the kernel's
    largest entry, 0.21) and 1.75e-5 relative (on entries near zero,
    which is why the limit is absolute); the limit is ~7x the former."""
    assert arm_states["losses_p"] == arm_states["losses_z"]
    leaves_p = jax.tree.leaves(arm_states["state_p"].params)
    leaves_z = jax.tree.leaves(arm_states["state_z"].params)
    assert len(leaves_p) == len(leaves_z)
    for p, z in zip(leaves_p, leaves_z):
        np.testing.assert_allclose(np.asarray(z), np.asarray(p),
                                   rtol=0.0, atol=1e-7)


def test_zero1_opt_state_bytes_one_over_n(arm_states):
    """Acceptance: per-device optimizer-state bytes drop ~1/N, asserted
    by live-array inspection (each sharded leaf's per-device shard)."""
    state_z = arm_states["state_z"]
    local = 0
    sharded_leaves = 0
    for leaf in jax.tree.leaves(state_z.opt_state):
        if not isinstance(leaf, jax.Array) or leaf.ndim < 2:
            continue
        shard_shape = leaf.sharding.shard_shape(leaf.shape)
        assert shard_shape[0] == leaf.shape[0] // 8  # data-axis sharded
        local += int(np.prod(shard_shape)) * leaf.dtype.itemsize
        sharded_leaves += 1
    assert sharded_leaves > 0
    # momentum trace mirrors the param tree: per-device bytes within
    # padding slack of param_bytes / 8
    assert local <= arm_states["param_bytes"] / 8 * 1.1
    assert local >= arm_states["param_bytes"] / 8 * 0.9


def test_zero1_overlap_off_same_values(arm_states):
    """--overlap_grad_comm=off (full-tree barrier, forward-order
    buckets) changes only the schedule, never the update."""
    mesh8 = arm_states["mesh"]
    cfg = tiny_cfg(variable_update="zero1", fusion_threshold_bytes=256,
                   overlap_grad_comm="off")
    state = step_mod.place_zero1_state(
        step_mod.make_zero1_state(arm_states["model"], cfg,
                                  arm_states["batch"], 8), mesh8)
    step = step_mod.build_train_step(mesh8, cfg, arm_states["spec"])
    rng = jax.random.PRNGKey(0)
    for _ in range(3):
        state, _ = step(state, arm_states["dev_batch"], rng)
    assert ckpt.fingerprint(state.params) == ckpt.fingerprint(
        arm_states["state_z"].params)


def test_zero1_checkpoint_roundtrip(arm_states, tmp_path):
    """Gather-on-save + restore into a fresh zero1 template is bitwise
    (params AND the sharded optimizer state)."""
    state_z = arm_states["state_z"]
    path = ckpt.save(state_z, tmp_path)
    assert path.exists()
    fresh = step_mod.make_zero1_state(
        arm_states["model"],
        tiny_cfg(variable_update="zero1", fusion_threshold_bytes=256),
        arm_states["batch"], 8)
    restored = ckpt.restore(fresh, tmp_path)
    assert ckpt.fingerprint(restored.params) == ckpt.fingerprint(
        state_z.params)
    assert ckpt.fingerprint(restored.opt_state) == ckpt.fingerprint(
        state_z.opt_state)


def test_zero1_flag_rules():
    """Every unsupported composition dies at flag time."""
    with pytest.raises(ValueError, match="plain data parallelism"):
        tiny_cfg(variable_update="zero1", model_parallel=2)
    with pytest.raises(ValueError, match="plain data parallelism"):
        tiny_cfg(variable_update="zero1", expert_parallel=2)
    with pytest.raises(ValueError, match="pipeline"):
        tiny_cfg(variable_update="zero1", pipeline_parallel=2)
    with pytest.raises(ValueError, match="data-axis only"):
        tiny_cfg(variable_update="zero1", sequence_parallel=2)
    with pytest.raises(ValueError, match="data-axis only"):
        tiny_cfg(variable_update="zero1", attention_impl="ring")
    with pytest.raises(ValueError, match="forward-only"):
        tiny_cfg(variable_update="zero1", forward_only=True)
    with pytest.raises(ValueError, match="overlap_grad_comm"):
        tiny_cfg(overlap_grad_comm="maybe")
    # accum composes (the scan's mean grads feed the reduce-scatter)
    cfg = tiny_cfg(variable_update="zero1",
                   gradient_accumulation_steps=2)
    assert cfg.variable_update == "zero1"
    # the GSPMD arm records the flag as n/a instead of silently eating it
    cfg = tiny_cfg(variable_update="replicated", overlap_grad_comm="off")
    assert "overlap_grad_comm" in cfg.translations
    # banner carries the arm + overlap setting
    assert any("overlap_grad_comm=on" in ln
               for ln in tiny_cfg(variable_update="zero1").summary_lines())


def test_zero1_step_rejects_host_fabric(arm_states):
    from tpu_hc_bench.parallel import fabric as fabric_mod

    cfg = tiny_cfg(variable_update="zero1")
    with pytest.raises(ValueError, match="device fabric"):
        step_mod.build_train_step(arm_states["mesh"], cfg,
                                  arm_states["spec"],
                                  fabric_mod.Fabric.HOST)


def test_zero1_driver_kill_resume_fingerprint(mesh8, tmp_path):
    """Acceptance: the kill/resume fingerprint proof passes with the
    SHARDED optimizer state — emergency save at sigterm, resume
    restores bitwise-identical params, manifest notes gather-on-save."""
    import json
    import os

    ck = str(tmp_path / "ck")
    md = str(tmp_path / "m")
    base = dict(batch_size=2, num_warmup_batches=1, num_batches=4,
                display_every=2, model="trivial", num_classes=10,
                init_learning_rate=0.05, variable_update="zero1",
                train_dir=ck, metrics_dir=md)
    out: list[str] = []
    with pytest.raises(resilience.PreemptedError):
        driver.run_benchmark(
            flags.BenchmarkConfig(**base, inject_fault="sigterm@2"
                                  ).resolve(),
            print_fn=out.append)
    fp_save = [l for l in out if "params fingerprint" in l]
    assert fp_save, out
    out2: list[str] = []
    res = driver.run_benchmark(
        flags.BenchmarkConfig(**base, resume="must").resolve(),
        print_fn=out2.append)
    fp_restore = [l for l in out2 if "params fingerprint" in l]
    assert fp_restore and fp_restore[0] == fp_save[-1]
    assert any("restored checkpoint" in l for l in out2)
    assert any("zero1: optimizer state sharded 8-way" in l for l in out2)
    assert np.isfinite(res.final_loss)
    manifest = json.load(open(os.path.join(md, "manifest.json")))
    assert manifest["zero1"] == {"opt_state_sharded": True,
                                 "opt_shards": 8,
                                 "checkpoint": "gather-on-save"}
    assert manifest["config"]["overlap_grad_comm"] == "on"
