"""Round 10: the latency-hiding layer — async checkpointing, persistent
compile cache, non-blocking sync windows, prefetch depth.

Default-lane cost discipline: the driver-level assertions share TWO
tiny module-scoped runs (async and sync-baseline, same model so the
in-process jit cache absorbs the second compile); everything else is
unit-level.  The crash-mid-async-save proof runs the writer in a
subprocess and SIGKILLs it between snapshot and commit — the async
extension of the round-8 kill/resume contract.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from tpu_hc_bench import flags
from tpu_hc_bench.obs import metrics as obs_metrics
from tpu_hc_bench.train import driver
from tpu_hc_bench.utils import checkpoint as ckpt
from tpu_hc_bench.utils import compile_cache

REPO = Path(__file__).resolve().parent.parent


def tiny_cfg(**kw):
    base = dict(
        batch_size=2, num_warmup_batches=1, num_batches=6, display_every=2,
        model="trivial", num_classes=10, init_learning_rate=0.05,
    )
    base.update(kw)
    return flags.BenchmarkConfig(**base).resolve()


def _tiny_state():
    from tpu_hc_bench.data.synthetic import SyntheticImages
    from tpu_hc_bench.models import create_model
    from tpu_hc_bench.train import step as step_mod

    cfg = tiny_cfg()
    model, spec = create_model("trivial", num_classes=10)
    batch = SyntheticImages(2, spec.input_shape, num_classes=10,
                            seed=0).batch()
    return step_mod.make_train_state(model, cfg, batch)


def read_metrics(metrics_dir):
    with open(os.path.join(metrics_dir, "metrics.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------------
# 1. AsyncCheckpointWriter: commit protocol, bounded in-flight, barrier
#    error propagation


def test_async_writer_roundtrip_and_bounded_inflight(mesh8, tmp_path):
    import jax
    import jax.numpy as jnp

    state = _tiny_state()
    w = ckpt.AsyncCheckpointWriter(tmp_path)
    step1 = w.submit(state)
    # in-flight <= 1: the next submit barriers on the previous write,
    # so by the time it returns, step1 is committed on disk
    state2 = state.replace(
        step=jnp.asarray(7, jnp.int32),
        params=jax.tree.map(lambda x: x + 1.0, state.params))
    step2 = w.submit(state2)
    assert step1 in ckpt.complete_steps(tmp_path)
    w.wait()
    assert ckpt.complete_steps(tmp_path) == [step1, step2]
    assert [c["step"] for c in w.commits] == [step1, step2]
    # the committed bytes match the snapshotted state bitwise
    restored = ckpt.restore(state, tmp_path, step=step2)
    assert ckpt.fingerprint(restored.params) == ckpt.fingerprint(
        state2.params)


def test_async_writer_error_surfaces_at_barrier(tmp_path, monkeypatch):
    """A persistent write failure exhausts the retry budget (same
    retry_io contract as the sync path) and re-raises at the barrier;
    a transient one is absorbed and the save lands."""
    from tpu_hc_bench.resilience import retry as retry_mod

    state = _tiny_state()
    w = ckpt.AsyncCheckpointWriter(tmp_path)
    boom = [1] * retry_mod.DEFAULT_ATTEMPTS    # every attempt fails

    def failing(payload, directory, step, topology=None):
        if boom:
            boom.pop()
            raise OSError("disk full")
        return real(payload, directory, step, topology=topology)

    real = ckpt.write_host_payload
    monkeypatch.setattr(ckpt, "write_host_payload", failing)
    w.submit(state)
    with pytest.raises(OSError, match="disk full"):
        w.wait()
    # the error cleared at the barrier: the writer is usable again
    # (and a transient single failure would have been retried away)
    w.submit(state)
    w.wait()
    assert ckpt.complete_steps(tmp_path)


def test_snapshot_to_host_is_host_arrays(mesh8):
    state = _tiny_state()
    step, payload = ckpt.snapshot_to_host(state)
    assert step == int(np.asarray(payload["step"]))
    for leaf in __import__("jax").tree.leaves(payload["params"]):
        assert isinstance(leaf, np.ndarray)


# ---------------------------------------------------------------------
# 2. the driver's async save path (shared runs: async + sync baseline)


@pytest.fixture(scope="module")
def async_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("latency_async")
    mdir, ckdir = str(tmp / "m"), str(tmp / "ck")
    out: list[str] = []
    res = driver.run_benchmark(
        tiny_cfg(train_dir=ckdir, metrics_dir=mdir, save_model_steps=2),
        print_fn=out.append)
    return {"out": out, "mdir": mdir, "ckdir": ckdir, "result": res}


@pytest.fixture(scope="module")
def sync_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("latency_sync")
    mdir, ckdir = str(tmp / "m"), str(tmp / "ck")
    out: list[str] = []
    res = driver.run_benchmark(
        tiny_cfg(train_dir=ckdir, metrics_dir=mdir, save_model_steps=2,
                 async_checkpoint=False),
        print_fn=out.append)
    return {"out": out, "mdir": mdir, "ckdir": ckdir, "result": res}


def test_async_run_overlaps_saves(async_run):
    text = "\n".join(async_run["out"])
    assert "checkpointing: async" in text
    assert "checkpoint snapshot: step" in text     # the blocking slice
    assert "(async write" in text                  # the overlapped write
    recs = read_metrics(async_run["mdir"])
    phases = [r.get("phase") for r in recs if r.get("kind") == "phase"]
    assert "checkpoint_async" in phases
    assert "checkpoint" not in phases              # nothing saved sync
    # every save landed and was reported through the main thread
    commits = [r for r in recs if r["kind"] == "checkpoint_commit"]
    # saves at timed steps 2, 4 and the final 6 -> counters 3, 5, 7
    assert [c["step"] for c in commits] == [3, 5, 7]
    assert ckpt.latest_step(async_run["ckdir"]) == 7
    # the ledger separates blocking snapshot cost from overlapped writes
    assert "checkpoint_async" in async_run["result"].goodput_phases
    assert "checkpoint" not in async_run["result"].goodput_phases
    # ... and summarize surfaces the overlapped writes from the artifacts
    text = "\n".join(obs_metrics.summarize_run(async_run["mdir"]))
    assert "async checkpoints: 3 landed" in text


def test_sync_baseline_still_blocks(sync_run):
    text = "\n".join(sync_run["out"])
    assert "checkpointing: async" not in text
    assert "(async write" not in text
    recs = read_metrics(sync_run["mdir"])
    phases = [r.get("phase") for r in recs if r.get("kind") == "phase"]
    assert "checkpoint" in phases
    assert "checkpoint_async" not in phases
    assert not [r for r in recs if r["kind"] == "checkpoint_commit"]
    assert "checkpoint" in sync_run["result"].goodput_phases


def test_async_run_resumes(async_run):
    out: list[str] = []
    res = driver.run_benchmark(
        tiny_cfg(train_dir=async_run["ckdir"], num_batches=2),
        print_fn=out.append)
    assert any("restored checkpoint step 7" in l for l in out)
    assert np.isfinite(res.final_loss)


def test_async_vs_sync_fingerprint_identical(async_run, sync_run):
    """Same seed, same schedule: the async writer must persist
    bit-identical state to the synchronous baseline.  Step pinned to 7
    (the shared runs' final save) — the resume test appends later
    checkpoints to the async dir."""
    state = _tiny_state()
    a = ckpt.restore(state, async_run["ckdir"], step=7)
    s = ckpt.restore(state, sync_run["ckdir"], step=7)
    assert ckpt.fingerprint(a.params) == ckpt.fingerprint(s.params)


# ---------------------------------------------------------------------
# 3. crash-mid-async-save: SIGKILL between snapshot and commit


_CRASH_PROG = """
import os, signal, sys, threading, time
import tpu_hc_bench
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
sys.path.insert(0, {test_dir!r})
from test_latency_hiding import _tiny_state
from tpu_hc_bench.utils import checkpoint as ckpt

d = {ckdir!r}
state = _tiny_state().replace(step=jnp.asarray(1, jnp.int32))
ckpt.save(state, d)                        # the last COMPLETE step
print("fp_complete:", ckpt.fingerprint(state.params), flush=True)

in_commit = threading.Event()
def stuck_commit(*a, **k):
    in_commit.set()                        # tmp fully written, sentinel not
    time.sleep(300)
ckpt._commit_step_dir = stuck_commit

w = ckpt.AsyncCheckpointWriter(d)
state2 = state.replace(step=jnp.asarray(2, jnp.int32),
                       params=jax.tree.map(lambda x: x + 1.0, state.params))
w.submit(state2)
assert in_commit.wait(120), "writer never reached the commit"
os.kill(os.getpid(), signal.SIGKILL)       # die between snapshot and commit
"""


@pytest.mark.slow
def test_sigkill_mid_async_save_falls_back_to_complete_step(
        mesh8, tmp_path):
    """The async extension of the round-8 kill/resume proof: a writer
    SIGKILLed after the Orbax tmp write but before the sentinel commit
    must leave discovery on the newest COMPLETE step, and the restored
    params must be bitwise-identical to that step's (fingerprint).

    Slow lane, like the round-8 kill/resume e2e it extends: the
    subprocess pays a fresh jax import + state compile, and the
    commit-protocol fallback it proves is also pinned (in-process,
    cheaply) by test_latest_step_ignores_partial_dirs — tier-1 lands
    ~805s against the 870s budget, so the fresh compile can't ride the
    default lane."""
    ckdir = str(tmp_path / "ck")
    prog = _CRASH_PROG.format(test_dir=str(REPO / "tests"), ckdir=ckdir)
    proc = subprocess.run(
        [sys.executable, "-c", prog], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]
    fp_lines = [l for l in proc.stdout.splitlines()
                if l.startswith("fp_complete:")]
    assert fp_lines, proc.stdout
    fp_complete = fp_lines[0].split()[-1]

    # the crashed save left an uncommitted .tmp; discovery ignores it
    assert ckpt.complete_steps(ckdir) == [1]
    assert list(Path(ckdir).glob("step_*.tmp"))
    with pytest.raises(FileNotFoundError, match="incomplete|no complete"):
        ckpt.restore(_tiny_state(), ckdir, step=2)
    # restore falls back to the newest complete step, bit-identical
    restored = ckpt.restore(_tiny_state(), ckdir)
    assert int(np.asarray(restored.step)) == 1
    assert ckpt.fingerprint(restored.params) == fp_complete
    # retention GC reaps the crashed partial write
    ckpt.gc_checkpoints(ckdir, keep=1)
    assert not list(Path(ckdir).glob("step_*.tmp"))


# ---------------------------------------------------------------------
# 4. persistent compile cache resolution + accounting


@pytest.fixture()
def jax_cache_config():
    """Restore the process's compile-cache config after a test that
    lets the resolver write it."""
    import jax

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      saved[1])


def test_compile_cache_env_wins_and_nothing_else_is_configured(
        tmp_path, monkeypatch):
    import jax

    placed = str(tmp_path / "placed")
    monkeypatch.setenv(compile_cache.ENV_VAR, placed)
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    assert compile_cache.resolve(None) == placed
    assert os.path.isdir(placed)
    # JAX read the variable itself at import; the resolver wrote nothing
    assert (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs) == before
    # a path on the flag cannot override the environment: the flag
    # takes only "off", loudly
    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        compile_cache.resolve(str(tmp_path / "mine"))
    with pytest.raises(ValueError, match="only 'off'"):
        tiny_cfg(compile_cache=str(tmp_path / "mine"))
    assert compile_cache.resolve("off") is None


def test_compile_cache_default_is_the_checkout(tmp_path, monkeypatch,
                                               jax_cache_config):
    import jax

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    assert compile_cache.default_dir() == want
    # identical across calls, with and without --train_dir: never a
    # per-run directory (the path is part of the cache key)
    first = compile_cache.resolve(tiny_cfg().compile_cache)
    second = compile_cache.resolve(
        tiny_cfg(train_dir=str(tmp_path / "run")).compile_cache)
    assert first == second == want
    assert jax.config.jax_compilation_cache_dir == want
    assert not (tmp_path / "run").exists()


def test_cache_entry_count(tmp_path):
    (tmp_path / "sub").mkdir()
    (tmp_path / "a").write_text("x")
    (tmp_path / "sub" / "b").write_text("y")
    assert compile_cache.entry_count(str(tmp_path)) == 2


def test_update_manifest_merges(tmp_path):
    w = obs_metrics.MetricsWriter(str(tmp_path), {"schema": 1, "model": "t"},
                                  primary=True)
    w.update_manifest({"compile_cache": {"warm": True}})
    w.close()
    man = json.loads((tmp_path / "manifest.json").read_text())
    assert man["model"] == "t"
    assert man["compile_cache"] == {"warm": True}


def test_flags_validate_latency_hiding():
    with pytest.raises(ValueError, match="prefetch_depth"):
        tiny_cfg(prefetch_depth=0)
    cfg = tiny_cfg(prefetch_depth=4)
    assert any("prefetch_depth=4" in l for l in cfg.summary_lines())


def test_prefetch_honors_depth():
    pulled: list[int] = []

    def gen():
        for i in range(6):
            pulled.append(i)
            yield i

    it = driver._prefetch(gen(), 3)
    assert next(it) == 0
    assert pulled == [0, 1, 2]      # 3 batches in flight at first yield
    assert list(it) == [1, 2, 3, 4, 5]


# ---------------------------------------------------------------------
# 5. deferred guard fetch + diff's ledger-phase rows


def test_guard_tracker_handles_are_stable_snapshots():
    import jax
    import jax.numpy as jnp

    from tpu_hc_bench.resilience import guards

    t = guards.GuardTracker()
    t.update(jnp.int32(1))
    h = t.handles()                 # snapshot refs at "window 1"
    t.update(jnp.int32(1))
    # the held refs still read window 1's values after later updates
    assert [int(v) for v in jax.device_get(list(h))] == [1, 1, 1]
    assert t.poll() == (2, 2, 2)


def _ledger_dir(tmp_path, name, phases):
    d = tmp_path / name
    d.mkdir()
    (d / "manifest.json").write_text('{"schema": 1}\n')
    recs = [{"kind": "phase", "phase": p, "t": t, "step": s}
            for p, t, s in phases]
    recs.append({"kind": "summary", "mfu": 0.1, "goodput": 0.5})
    (d / "metrics.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in recs))
    return str(d)


def test_diff_renders_ledger_phase_rows(tmp_path):
    a = _ledger_dir(tmp_path, "a", [
        ("init", 0.0, None), ("compile", 1.0, None), ("step", 11.0, None),
        ("checkpoint", 15.0, 4), ("step", 17.0, 4), ("end", 20.0, 8)])
    b = _ledger_dir(tmp_path, "b", [
        ("init", 0.0, None), ("compile", 1.0, None), ("step", 2.5, None),
        ("checkpoint_async", 6.5, 4), ("step", 6.7, 4), ("end", 10.0, 8)])
    text = "\n".join(obs_metrics.diff_runs(a, b))
    assert "ledger phases (wall s)" in text
    assert "compile" in text and "-85.0%" in text    # 10s -> 1.5s
    assert "checkpoint_async" in text
