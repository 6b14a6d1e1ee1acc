"""Checkpoint, sanity-report, hostfile, and hw-table tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_hc_bench import flags
from tpu_hc_bench.data.synthetic import SyntheticImages
from tpu_hc_bench.models import TrivialModel
from tpu_hc_bench.parallel import distributed
from tpu_hc_bench.train import step as step_mod
from tpu_hc_bench.utils import checkpoint, hw, sanity


def make_state(lr=0.05):
    cfg = flags.BenchmarkConfig(
        batch_size=2, model="trivial", num_classes=10,
        init_learning_rate=lr,
    ).resolve()
    model = TrivialModel(num_classes=10)
    batch = SyntheticImages(8, (8, 8, 3), num_classes=10).batch()
    return step_mod.make_train_state(model, cfg, batch), batch


def test_checkpoint_roundtrip(tmp_path):
    state, _ = make_state()
    state = state.replace(step=jnp.asarray(7, jnp.int32))
    checkpoint.save(state, tmp_path)
    assert checkpoint.latest_step(tmp_path) == 7

    fresh, _ = make_state()
    restored = checkpoint.restore(fresh, tmp_path)
    assert int(restored.step) == 7
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_multiple_steps(tmp_path):
    state, _ = make_state()
    for s in (1, 5, 3):
        checkpoint.save(state.replace(step=jnp.asarray(s, jnp.int32)), tmp_path)
    assert checkpoint.latest_step(tmp_path) == 5
    restored = checkpoint.restore(make_state()[0], tmp_path, step=3)
    assert int(restored.step) == 3


def test_restore_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        checkpoint.restore(make_state()[0], tmp_path / "nope")


def test_sanity_report_passes_on_cpu_mesh(devices):
    lines, failures = sanity.collect_report()
    assert failures == [], failures
    text = "\n".join(lines)
    assert "jax:" in text and "matmul smoke test: ok" in text
    assert "psum smoke test: ok over 8 device(s)" in text


def test_hostfile_parsing(tmp_path):
    p = tmp_path / "nodeips.txt"
    p.write_text("# head node first\n10.0.0.1\n10.0.0.2\n\n10.0.0.3\n")
    hosts = distributed.read_hostfile(p)
    assert hosts == ["10.0.0.1", "10.0.0.2", "10.0.0.3"]
    (tmp_path / "empty.txt").write_text("\n# nothing\n")
    with pytest.raises(ValueError):
        distributed.read_hostfile(tmp_path / "empty.txt")


def test_peak_flops_table():
    # the CPU test mesh has its own (nominal) row
    assert hw.peak_flops(dtype="bfloat16") > 0
    assert hw.peak_flops(dtype="float32") > 0


def test_peak_flops_is_exact_match_and_unknown_raises():
    class Fake:
        def __init__(self, kind):
            self.device_kind = kind

    # what the v5e reports
    assert hw.peak_flops(Fake("TPU v5 lite")) == 197e12
    assert hw.peak_flops(Fake("TPU v5 lite"), dtype="float32") == 98e12
    # a kind a substring match would have waved through, and one it
    # would have handed the CPU's nominal figure
    for kind in ("TPU v5 lite pod", "Some Future Chip"):
        with pytest.raises(KeyError, match="no peak-FLOPs row"):
            hw.peak_flops(Fake(kind))


def test_pallas_interprets_on_cpu_only(monkeypatch):
    from tpu_hc_bench.ops import _pallas

    assert _pallas.interpret() is True          # the CPU test mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _pallas.interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        _pallas.interpret()


def test_lanes_refuse_a_cpu_nobody_asked_for(tmp_path, monkeypatch):
    """JAX only warns when it finds no TPU and falls back to the CPU;
    both lanes must stop there instead of benchmarking it."""
    from tpu_hc_bench import launcher
    from tpu_hc_bench.serve import cli as serve_cli

    monkeypatch.setenv("HOME", str(tmp_path))
    # the backend query says cpu (it is), and nothing asked for it
    monkeypatch.setattr(hw, "_requested_platforms", lambda: "")
    with pytest.raises(RuntimeError, match="CPU was not asked for"):
        launcher.main(["1", "0", "2", "ici", "--model=trivial",
                       "--num_batches=1"])
    with pytest.raises(RuntimeError, match="CPU was not asked for"):
        serve_cli.main(["--model=trivial", "--num_requests=1"],
                       print_fn=lambda m: None)
    # the two ways of asking: --virtual_devices, or naming the platform
    hw.require_accelerator(virtual_devices=8)
    monkeypatch.setattr(hw, "_requested_platforms", lambda: "cpu")
    hw.require_accelerator()
    # an accelerator that is not a TPU is refused either way
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        hw.require_accelerator(virtual_devices=8)


def test_ici_topology_lines():
    # CPU mesh: no coords -> graceful virtual-mesh line
    lines = hw.ici_topology_lines()
    assert lines and lines[0].startswith("ici:")
    assert "virtual/CPU mesh" in lines[0]

    # TPU-shaped fakes: coords -> slice shape + per-host chip map
    class FakeDev:
        def __init__(self, i, coords):
            self.id = i
            self.coords = coords
            self.process_index = 0
            self.core_on_chip = 0
            self.device_kind = "TPU v5 lite"

    devs = [FakeDev(i, (i % 2, i // 2, 0)) for i in range(4)]
    lines = hw.ici_topology_lines(devs)
    assert "slice_shape=2x2x1" in lines[0]
    assert "chips=4" in lines[0]
    assert "d0@0,0,0" in lines[1]
