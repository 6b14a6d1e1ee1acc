"""tpu_hc_bench.resilience: fault injection, guards, preemption,
watchdog, checkpoint hardening.

Every recovery path is exercised by a real injected failure
(``--inject_fault``), per the round-8 acceptance criteria:
``nan_loss@N`` + ``--on_nonfinite=skip`` completes with the bad step
dropped and a ``nonfinite_skip`` metrics record; ``sigterm@N`` +
``--resume=auto`` kill/relaunch resumes from the emergency checkpoint
with bitwise-identical params (fingerprint lines); ``hang@N`` +
``--step_timeout_s`` aborts with a stack dump and the distinct
watchdog exit code instead of hanging.
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from tpu_hc_bench import flags, resilience
from tpu_hc_bench.resilience import (
    guards, inject, preempt, retry as retry_mod, watchdog,
)
from tpu_hc_bench.train import driver

REPO = Path(__file__).resolve().parent.parent


def tiny_cfg(**kw):
    base = dict(
        batch_size=2, num_warmup_batches=1, num_batches=6, display_every=2,
        model="trivial", num_classes=10, init_learning_rate=0.05,
    )
    base.update(kw)
    return flags.BenchmarkConfig(**base).resolve()


def read_metrics(metrics_dir):
    path = os.path.join(metrics_dir, "metrics.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def kinds(records):
    return [r["kind"] for r in records]


# ---------------------------------------------------------------------
# inject: the --inject_fault grammar


def test_parse_plan():
    plan = inject.parse_plan("nan_loss@40,hang@80:30,sigterm@120,"
                             "io_error@ckpt,nan_loss@41")
    assert plan.nan_loss == frozenset({40, 41})
    assert plan.hang == {80: 30.0}
    assert plan.sigterm == frozenset({120})
    assert plan.io_error == {"ckpt"}
    assert inject.parse_plan(None) is None
    assert inject.parse_plan("") is None


@pytest.mark.parametrize("bad", [
    "nan_loss", "nan_loss@", "nan_loss@0", "nan_loss@x", "hang@5",
    "hang@5:-1", "io_error@metrics", "explode@3", "sigterm@1.5",
])
def test_parse_plan_loud(bad):
    with pytest.raises(ValueError, match="malformed|grammar"):
        inject.parse_plan(bad)


def test_flags_validate_inject_and_policies():
    with pytest.raises(ValueError, match="malformed"):
        tiny_cfg(inject_fault="bogus@@")
    with pytest.raises(ValueError, match="rewind"):
        tiny_cfg(on_nonfinite="rewind")            # needs --train_dir
    with pytest.raises(ValueError, match="resume=never"):
        # rewind restores from --train_dir; never-resume contradicts it
        tiny_cfg(on_nonfinite="rewind", train_dir="/tmp/x", resume="never")
    with pytest.raises(ValueError, match="forward-only|--eval"):
        tiny_cfg(on_nonfinite="skip", eval=True)
    with pytest.raises(ValueError, match="GPipe|PP"):
        tiny_cfg(on_nonfinite="skip", model="gpt2_tiny",
                 pipeline_parallel=4)
    with pytest.raises(ValueError, match="step_timeout_s"):
        tiny_cfg(step_timeout_s="soon")
    with pytest.raises(ValueError, match="resume"):
        tiny_cfg(resume="maybe")
    with pytest.raises(ValueError, match="--resume=must"):
        tiny_cfg(resume="must")                    # needs --train_dir
    with pytest.raises(ValueError, match="max_bad_steps"):
        tiny_cfg(on_nonfinite="skip", max_bad_steps=0)


# ---------------------------------------------------------------------
# guards: jit-compatible detection + device-side budget counters


def test_finite_flag_and_select():
    import jax.numpy as jnp

    assert bool(guards.finite_flag(jnp.float32(1.0)))
    assert not bool(guards.finite_flag(jnp.float32(np.nan)))
    assert not bool(guards.finite_flag(
        jnp.float32(1.0), {"w": jnp.array([1.0, np.inf])}))
    new = {"w": jnp.array([2.0]), "n": jnp.int32(5)}
    old = {"w": jnp.array([1.0]), "n": jnp.int32(4)}
    kept = guards.select_state(guards.finite_flag(jnp.float32(np.nan)),
                               new, old)
    assert float(kept["w"][0]) == 1.0 and int(kept["n"]) == 4
    took = guards.select_state(guards.finite_flag(jnp.float32(0.5)),
                               new, old)
    assert float(took["w"][0]) == 2.0 and int(took["n"]) == 5


def test_guard_tracker_streak_resets_on_good_step():
    import jax.numpy as jnp

    t = guards.GuardTracker()
    for bad in (1, 1, 0, 1):
        t.update(jnp.int32(bad))
    streak, total, peak = t.poll()
    # peak remembers the 2-long run even though a good step reset the
    # live streak — the --max_bad_steps budget must not be dodgeable by
    # a streak that ends inside a sync window
    assert (streak, total, peak) == (1, 3, 2)
    t.reset()
    assert t.poll() == (0, 0, 0)


# ---------------------------------------------------------------------
# --on_nonfinite policies through the driver (nan_loss injection)


def test_nonfinite_abort_default(mesh8):
    with pytest.raises(resilience.NonFiniteError, match="abort"):
        driver.run_benchmark(tiny_cfg(inject_fault="nan_loss@2"),
                             print_fn=lambda s: None)


def test_nonfinite_skip_completes(mesh8, tmp_path):
    from tpu_hc_bench.obs import metrics as obs_metrics

    mdir = str(tmp_path / "m")
    out = []
    res = driver.run_benchmark(
        tiny_cfg(on_nonfinite="skip", inject_fault="nan_loss@3",
                 metrics_dir=mdir), print_fn=out.append)
    assert np.isfinite(res.final_loss)
    recs = read_metrics(mdir)
    assert "injected_fault" in kinds(recs)
    skip = [r for r in recs if r["kind"] == "nonfinite_skip"]
    assert skip and skip[0]["new_bad"] == 1
    assert any("dropped 1 update" in l for l in out)
    # ...and `obs summarize` surfaces the resilience events
    text = "\n".join(obs_metrics.summarize_run(mdir))
    assert "resilience:" in text
    assert "nonfinite_skip" in text and "injected_fault" in text


def test_nonfinite_skip_budget_terminates(mesh8):
    cfg = tiny_cfg(on_nonfinite="skip", max_bad_steps=2,
                   inject_fault="nan_loss@1,nan_loss@2,nan_loss@3,"
                                "nan_loss@4,nan_loss@5,nan_loss@6")
    with pytest.raises(resilience.GuardBudgetError, match="consecutive"):
        driver.run_benchmark(cfg, print_fn=lambda s: None)


def test_nonfinite_rewind_restores_and_completes(mesh8, tmp_path):
    # nan at step 1: the double-buffered guard fetch acts one window
    # late (snapshot at window 2, processed at window 4), so the poison
    # must land early enough that clean replay steps remain after the
    # restore
    mdir, ckdir = str(tmp_path / "m"), str(tmp_path / "ck")
    out = []
    res = driver.run_benchmark(
        tiny_cfg(on_nonfinite="rewind", inject_fault="nan_loss@1",
                 train_dir=ckdir, metrics_dir=mdir), print_fn=out.append)
    assert np.isfinite(res.final_loss)
    recs = read_metrics(mdir)
    rewinds = [r for r in recs if r["kind"] == "rewind"]
    assert rewinds and rewinds[0]["skipped_batches"] > 0
    assert any("rewind:" in l for l in out)


def test_rewind_budget_terminates_poisoned_run(mesh8, tmp_path):
    """Every window poisoned: back-to-back rewinds hit --max_bad_steps
    (same consecutive semantics as the skip budget) instead of
    rewind-looping to the end of the run.

    8 timed steps: under the double-buffered guard fetch a rewind wipes
    the following window's counters (the reset), so each rewind needs
    two windows of runway — and the wiped window must NOT pass as
    "observed clean" and break the consecutive-rewind streak (the
    guard_wiped_until accounting this test pins).
    """
    cfg = tiny_cfg(on_nonfinite="rewind", max_bad_steps=2, num_batches=8,
                   train_dir=str(tmp_path / "ck"),
                   inject_fault="nan_loss@1,nan_loss@2,nan_loss@3,"
                                "nan_loss@4,nan_loss@5,nan_loss@6,"
                                "nan_loss@7,nan_loss@8")
    with pytest.raises(resilience.GuardBudgetError, match="rewinds"):
        driver.run_benchmark(cfg, print_fn=lambda s: None)


# ---------------------------------------------------------------------
# preemption: sigterm -> emergency checkpoint -> resume


def test_preempt_emergency_checkpoint_and_resume(mesh8, tmp_path):
    from tpu_hc_bench.utils import checkpoint as ckpt

    ckdir, mdir = str(tmp_path / "ck"), str(tmp_path / "m")
    out = []
    with pytest.raises(resilience.PreemptedError) as ei:
        driver.run_benchmark(
            tiny_cfg(inject_fault="sigterm@2", train_dir=ckdir,
                     metrics_dir=mdir), print_fn=out.append)
    assert ei.value.step == 2 and ei.value.checkpoint_saved
    assert ckpt.latest_step(ckdir) == 3          # 1 warmup + 2 timed
    recs = read_metrics(mdir)
    assert "emergency_ckpt" in kinds(recs) and "preempt" in kinds(recs)
    fp_save = [l for l in out if "params fingerprint" in l]
    assert fp_save

    out2 = []
    res = driver.run_benchmark(tiny_cfg(train_dir=ckdir),
                               print_fn=out2.append)
    assert any("restored checkpoint step 3" in l for l in out2)
    fp_restore = [l for l in out2 if "params fingerprint" in l]
    # bitwise-identical params across the emergency save/restore boundary
    assert fp_restore[0] == fp_save[0]
    assert np.isfinite(res.final_loss)


def test_resume_policies_and_retention(mesh8, tmp_path):
    """One checkpointed run, then the --resume policy matrix against it
    (plus --keep_checkpoints retention through the driver, sharing the
    same run to keep the default lane cheap)."""
    from tpu_hc_bench.utils import checkpoint as ckpt

    ckdir = str(tmp_path / "ck")
    with pytest.raises(FileNotFoundError, match="resume=must"):
        driver.run_benchmark(tiny_cfg(train_dir=ckdir, resume="must"),
                             print_fn=lambda s: None)
    driver.run_benchmark(
        tiny_cfg(train_dir=ckdir, save_model_steps=2, keep_checkpoints=1),
        print_fn=lambda s: None)
    # saves at timed steps 2, 4 and the end (7 = 1 warmup + 6 timed);
    # retention keeps only the newest
    assert ckpt.complete_steps(ckdir) == [7]
    out = []
    driver.run_benchmark(tiny_cfg(train_dir=ckdir, resume="never",
                                  num_batches=2), print_fn=out.append)
    assert not any("restored checkpoint" in l for l in out)
    out = []
    driver.run_benchmark(tiny_cfg(train_dir=ckdir, resume="must",
                                  num_batches=2), print_fn=out.append)
    assert any("restored checkpoint step 7" in l for l in out)


# ---------------------------------------------------------------------
# watchdog


def test_resolve_timeout():
    assert watchdog.resolve_timeout(None) is None
    assert watchdog.resolve_timeout("off") is None
    assert watchdog.resolve_timeout("0") is None
    assert watchdog.resolve_timeout("12.5") == 12.5
    assert watchdog.resolve_timeout("auto") is None     # pre-warmup
    auto = watchdog.resolve_timeout("auto", warmup_step_s=2.0)
    assert auto == max(watchdog.AUTO_TIMEOUT_MIN_S,
                       watchdog.AUTO_TIMEOUT_MULT * 2.0)
    with pytest.raises(ValueError, match="step_timeout_s"):
        watchdog.resolve_timeout("-3")
    with pytest.raises(ValueError, match="step_timeout_s"):
        watchdog.resolve_timeout("soon")


def test_watchdog_fires_without_progress():
    fired = []
    dog = watchdog.Watchdog(
        0.2, progress_fn=lambda: None, print_fn=lambda s: None,
        on_timeout=fired.append, poll_s=0.05).start()
    deadline = time.monotonic() + 5.0
    while not fired and time.monotonic() < deadline:
        time.sleep(0.02)
    dog.stop()
    assert fired and fired[0] > 0.2 and dog.fired


def test_watchdog_quiet_with_progress():
    fired = []
    dog = watchdog.Watchdog(
        0.3, progress_fn=time.perf_counter, print_fn=lambda s: None,
        on_timeout=fired.append, poll_s=0.05).start()
    time.sleep(0.7)
    dog.stop()
    assert not fired and not dog.fired


def test_watchdog_pause_covers_long_checkpoint_saves():
    """A legitimate long stall (checkpoint save to slow storage) must
    not trip the watchdog while paused, and the paused span must not
    count after resume."""
    fired = []
    dog = watchdog.Watchdog(
        0.2, progress_fn=lambda: None, print_fn=lambda s: None,
        on_timeout=fired.append, poll_s=0.05).start()
    dog.pause()
    time.sleep(0.5)              # well past the timeout, but paused
    assert not fired
    dog.resume()
    time.sleep(0.1)              # fresh baseline: still inside timeout
    assert not fired
    deadline = time.monotonic() + 5.0
    while not fired and time.monotonic() < deadline:
        time.sleep(0.02)         # now it must fire
    dog.stop()
    assert fired


# ---------------------------------------------------------------------
# retry + checkpoint/metrics I/O hardening


def test_retry_io_bounded():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert retry_mod.retry_io(flaky, "t", base_delay_s=0.001) == "ok"
    with pytest.raises(OSError):
        retry_mod.retry_io(lambda: (_ for _ in ()).throw(OSError("dead")),
                           "t", attempts=2, base_delay_s=0.001)
    # non-OSError propagates immediately (not a transient I/O fault)
    boom = []

    def type_error():
        boom.append(1)
        raise TypeError("bug")

    with pytest.raises(TypeError):
        retry_mod.retry_io(type_error, "t", base_delay_s=0.001)
    assert len(boom) == 1


def test_checkpoint_io_error_injected_retries(mesh8, tmp_path):
    from tpu_hc_bench.utils import checkpoint as ckpt

    ckdir, mdir = str(tmp_path / "ck"), str(tmp_path / "m")
    out = []
    driver.run_benchmark(
        tiny_cfg(inject_fault="io_error@ckpt", train_dir=ckdir,
                 metrics_dir=mdir), print_fn=out.append)
    assert any("retrying" in l for l in out)
    assert "io_retry" in kinds(read_metrics(mdir))
    assert ckpt.latest_step(ckdir) is not None   # save ultimately landed


# ---------------------------------------------------------------------
# checkpoint hardening: atomic commit sentinel, fallback, retention GC


def _save_steps(state, directory, steps):
    import jax.numpy as jnp
    from tpu_hc_bench.utils import checkpoint as ckpt

    for s in steps:
        state = state.replace(step=jnp.asarray(s, jnp.int32))
        ckpt.save(state, directory)
    return state


def _tiny_state():
    from tpu_hc_bench.data.synthetic import SyntheticImages
    from tpu_hc_bench.models import create_model
    from tpu_hc_bench.train import step as step_mod

    cfg = tiny_cfg()
    model, spec = create_model("trivial", num_classes=10)
    batch = SyntheticImages(2, spec.input_shape, num_classes=10,
                            seed=0).batch()
    return step_mod.make_train_state(model, cfg, batch)


def test_read_run_skips_corrupt_lines(tmp_path):
    """A write interrupted mid-flush leaves a terminated fragment; the
    reader skips it instead of crashing summarize/diff on exactly the
    run whose telemetry survived an I/O incident."""
    from tpu_hc_bench.obs import metrics as obs_metrics

    mdir = tmp_path / "m"
    mdir.mkdir()
    (mdir / "metrics.jsonl").write_text(
        '{"kind": "window", "step": 2}\n'
        '{"kind": "window", "st\n'               # the fragment
        '{"kind": "summary", "mfu": 0.5}\n')
    _, records = obs_metrics.read_run(str(mdir))
    assert [r["kind"] for r in records] == ["window", "summary"]


def test_maybe_restore_warns_on_sentinel_less_dirs(mesh8, tmp_path):
    """Sentinel-less step dirs (crashed saves or pre-sentinel-era
    checkpoints) must produce a loud warning, not a silent restart."""
    ckdir = tmp_path / "ck"
    (ckdir / "step_00000005").mkdir(parents=True)
    out = []
    driver.run_benchmark(tiny_cfg(train_dir=str(ckdir), num_batches=2),
                         print_fn=out.append)
    warn = [l for l in out if "WARNING" in l and "sentinel" in l]
    assert warn and "step_00000005" in warn[0]


def test_latest_step_ignores_partial_dirs(tmp_path):
    from tpu_hc_bench.utils import checkpoint as ckpt

    state = _tiny_state()
    _save_steps(state, tmp_path, (1, 2))
    # a crash mid-save leaves a sentinel-less dir and a .tmp dir —
    # neither may be discovered as "latest"
    (tmp_path / "step_00000009").mkdir()
    (tmp_path / "step_00000007.tmp").mkdir()
    assert ckpt.complete_steps(tmp_path) == [1, 2]
    assert ckpt.latest_step(tmp_path) == 2
    restored = ckpt.restore(state, tmp_path)     # newest COMPLETE step
    assert int(np.asarray(restored.step)) == 2
    with pytest.raises(FileNotFoundError, match="incomplete"):
        ckpt.restore(state, tmp_path, step=9)


def test_retention_gc(tmp_path):
    from tpu_hc_bench.utils import checkpoint as ckpt

    state = _tiny_state()
    _save_steps(state, tmp_path, (1, 2, 3, 4))
    (tmp_path / "step_00000002.tmp").mkdir()     # stale partial write
    deleted = ckpt.gc_checkpoints(tmp_path, keep=2)
    assert deleted == [1, 2]
    assert ckpt.complete_steps(tmp_path) == [3, 4]
    assert not (tmp_path / "step_00000002.tmp").exists()
    assert ckpt.gc_checkpoints(tmp_path, keep=0) == []   # 0 = keep all


# ---------------------------------------------------------------------
# fetcher / prefetch error propagation (the "real error, not a hang"
# regression tests)


class _PoisonHandle:
    """jax.device_get(np.asarray) calls __array__ — raise the real error
    there, exactly where a poisoned data iterator's fetch would."""

    def __array__(self, dtype=None):
        raise ValueError("poisoned batch payload")


def test_fetcher_propagates_original_error_not_hang(mesh8):
    timeline = driver._AsyncTimeline(num_batches=4, display_every=2,
                                     global_batch=2)
    with pytest.raises(ValueError, match="poisoned batch payload") as ei:
        timeline.start(_PoisonHandle())
    # the original fetch-thread traceback survives the cross-thread
    # re-raise: the innermost frames are _run/device_get, not check()
    frames = []
    tb = ei.value.__traceback__
    while tb is not None:
        frames.append(tb.tb_frame.f_code.co_name)
        tb = tb.tb_next
    assert "_run" in frames


def test_fetcher_coalesces_completed_markers_only():
    """The loop dispatches far ahead of the device, so most queued
    markers are steps still running: the fetch thread may skip over a
    marker that has already completed, never over one that has not —
    otherwise every timed interval is a multi-step window (what the
    first run on a local chip measured: granularity 10, not 1)."""
    import threading

    entered, gate = threading.Event(), threading.Event()

    class Marker:
        def __init__(self, ready, blocks=False):
            self.ready, self.blocks = ready, blocks

        def is_ready(self):
            return self.ready

        def __array__(self, dtype=None):
            if self.blocks:
                entered.set()
                assert gate.wait(10)
            return np.zeros(())

    fetcher = driver._ArrivalFetcher()
    fetcher.put(0, Marker(True, blocks=True))
    assert entered.wait(10)     # the thread is inside marker 0's fetch
    for i, ready in ((1, True), (2, True), (3, False), (4, False)):
        fetcher.put(i, Marker(ready))
    gate.set()
    steps = [i for i, _, _ in fetcher.finish()]
    # 1 is skipped for 2 (both done); 3 and 4 were still running when
    # the thread looked, so each is timed on its own
    assert steps == [0, 2, 3, 4]
    assert [i for i, _ in fetcher.skipped] == [1]


def test_fetcher_record_surfaces_error(mesh8):
    import jax.numpy as jnp

    timeline = driver._AsyncTimeline(num_batches=8, display_every=2,
                                     global_batch=2)
    timeline.start(jnp.float32(0.0))
    with pytest.raises(ValueError, match="poisoned batch payload"):
        for i in range(1, 9):
            timeline.record(i, _PoisonHandle())
            time.sleep(0.01)


def test_prefetch_propagates_iterator_error():
    def poisoned():
        yield 1
        yield 2
        raise ValueError("poisoned iterator")

    got = []
    with pytest.raises(ValueError, match="poisoned iterator"):
        for x in driver._prefetch(poisoned(), lookahead=2):
            got.append(x)
    assert got == [1]     # lookahead was mid-flight when the poison hit


# ---------------------------------------------------------------------
# exit codes + subprocess end-to-end


def test_exit_codes_distinct_and_documented():
    codes = {resilience.EXIT_OK, resilience.EXIT_ZERO_THROUGHPUT,
             resilience.EXIT_WATCHDOG, resilience.EXIT_PREEMPTED}
    assert len(codes) == 4
    readme = (REPO / "README.md").read_text()
    for code in (resilience.EXIT_WATCHDOG, resilience.EXIT_PREEMPTED):
        assert str(code) in readme


def _launch(tmp_path, *extra, num_batches=6, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "tpu_hc_bench", "1", "0", "2", "ici",
           "--model", "trivial", "--num_classes", "10",
           "--num_warmup_batches", "1", "--num_batches", str(num_batches),
           "--display_every", "2", "--virtual_devices", "8",
           *extra]
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


def test_watchdog_aborts_hung_run_subprocess(tmp_path):
    """hang@N + --step_timeout_s: the run aborts with the distinct
    watchdog exit code and a full thread-stack dump, instead of hanging
    until the 60 s injected hang (or a real deadlock) resolves."""
    t0 = time.monotonic()
    proc = _launch(tmp_path, "--inject_fault", "hang@2:60",
                   "--step_timeout_s", "1.0", num_batches=4)
    elapsed = time.monotonic() - t0
    assert proc.returncode == resilience.EXIT_WATCHDOG, proc.stderr[-2000:]
    assert "watchdog: no step completed" in proc.stderr
    assert "Thread" in proc.stderr          # faulthandler stack dump
    assert "fire_step_faults" in proc.stderr  # names the hung frame
    assert elapsed < 55                     # did NOT sit out the hang


@pytest.mark.slow
def test_kill_resume_e2e_subprocess(tmp_path):
    """The full preemption contract: sigterm@N -> exit EXIT_PREEMPTED
    with an emergency checkpoint; relaunch with --resume=auto continues
    from it with bitwise-identical params (fingerprint log lines)."""
    ckdir = str(tmp_path / "ck")
    proc1 = _launch(tmp_path, "--inject_fault", "sigterm@2",
                    "--train_dir", ckdir)
    assert proc1.returncode == resilience.EXIT_PREEMPTED, \
        proc1.stdout[-2000:] + proc1.stderr[-2000:]
    assert "emergency checkpoint saved" in proc1.stdout
    fp1 = [l for l in proc1.stdout.splitlines()
           if "params fingerprint" in l]
    assert fp1

    proc2 = _launch(tmp_path, "--resume", "auto", "--train_dir", ckdir)
    assert proc2.returncode == resilience.EXIT_OK, \
        proc2.stdout[-2000:] + proc2.stderr[-2000:]
    assert "restored checkpoint step 3" in proc2.stdout
    fp2 = [l for l in proc2.stdout.splitlines()
           if "params fingerprint" in l]
    assert fp2[0] == fp1[0]


