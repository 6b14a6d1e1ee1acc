"""The runtime-observability subsystem: trace analysis, metrics, CLI.

Four layers, matching the acceptance contract:

1. ``obs.trace`` against a HAND-BUILT synthetic perfetto fixture: step
   reconstruction (step track + envelope fallback), per-step bucket
   attribution (compute/collective/host-transfer/idle-bubble, with the
   hand-computed numbers), and the same-tid containment rule (a long
   leaf overlapping siblings on ANOTHER track must be kept; a real
   container on its OWN track must be dropped).
2. ``obs.metrics`` + the ``python -m tpu_hc_bench.obs`` CLI on fixture
   runs: summarize renders, diff reports per-bucket deltas
   ("collective +40%, compute flat").
3. End-to-end: a real (CPU-mesh) driver run with ``--metrics_dir``
   produces a JSONL + manifest that summarize renders and diff compares;
   ``--profile_steps`` drives the windowed profiler through its single
   stop path.
4. Repo hygiene: no bytecode artifacts are ever tracked (the satellite
   that deleted the stale ``scripts/__pycache__``).
"""

from __future__ import annotations

import gzip
import io
import json
import subprocess
import sys
from pathlib import Path

import pytest

from tpu_hc_bench import flags
from tpu_hc_bench.obs import metrics as obs_metrics
from tpu_hc_bench.obs import trace as obs_trace
from tpu_hc_bench.obs.__main__ import main as obs_main
from tpu_hc_bench.train import driver

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------
# the synthetic perfetto fixture
#
# Device pid 100.  Track (100, 1) = compute stream, track (100, 2) = a
# concurrent DMA-style stream, track (100, 9) = the profiler's "Steps"
# track.  Two steps:
#
#   step 0, span [0, 100):
#     tid 1: fusion.1      [0, 40)    compute
#            all-reduce.2  [45, 75)   collective
#            mult.7        [76, 79)   compute
#            infeed.3      [80, 90)   host-transfer
#     tid 2: copy-done.5   [40, 90)   compute — strictly contains
#            all-reduce.2 and mult.7 on the OTHER track; the same-tid
#            rule must keep it (nothing on its own track is inside it)
#     busy union [0, 90) -> idle-bubble 10
#   step 1, span [120, 220):
#     tid 1: fusion.1      [120, 170) compute
#            all-reduce.2  [175, 215) collective
#     busy union 90 -> idle-bubble 10
#
# Hand totals: compute 93 + 50 = 143, collective 70, host-transfer 10,
# idle 20.

STEP_SPANS = [(0, 100), (120, 220)]
STEP0 = {"compute": 93.0, "collective": 30.0, "host-transfer": 10.0,
         "idle-bubble": 10.0}
STEP1 = {"compute": 50.0, "collective": 40.0, "host-transfer": 0.0,
         "idle-bubble": 10.0}


def _x(pid, tid, name, ts, dur):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name,
            "ts": ts, "dur": dur}


def fixture_events(with_step_track: bool = True) -> list[dict]:
    events = [
        {"ph": "M", "pid": 100, "name": "process_name",
         "args": {"name": "/device:TPU:0 (chip 0)"}},
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "python"}},
        # host-side event that must never be attributed
        _x(1, 7, "hostfn", 0, 500),
        # tid 1: compute stream, one jit envelope per step (containers)
        _x(100, 1, "jit_train_step", 0, 100),
        _x(100, 1, "fusion.1", 0, 40),
        _x(100, 1, "all-reduce.2", 45, 30),
        _x(100, 1, "mult.7", 76, 3),
        _x(100, 1, "infeed.3", 80, 10),
        _x(100, 1, "jit_train_step", 120, 100),
        _x(100, 1, "fusion.1", 120, 50),
        _x(100, 1, "all-reduce.2", 175, 40),
        # tid 2: long DMA-stream leaf overlapping two tid-1 ops
        _x(100, 2, "copy-done.5", 40, 50),
    ]
    if with_step_track:
        events += [
            {"ph": "M", "pid": 100, "tid": 9, "name": "thread_name",
             "args": {"name": "Steps"}},
            _x(100, 9, "1", 0, 100),
            _x(100, 9, "2", 120, 100),
        ]
    return events


def write_trace_dir(tmp_path: Path, events, name="run") -> Path:
    d = tmp_path / name / "plugins" / "profile" / "2026_08_02"
    d.mkdir(parents=True)
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    return tmp_path / name


# ---------------------------------------------------------------------
# 1. trace analysis


def test_same_tid_containment_keeps_cross_track_leaf():
    ops, counts = obs_trace.leaf_device_ops(fixture_events())
    # the cross-track long op survives (round-6 rule) ...
    assert ops["copy-done.5"] == 50
    # ... while the same-track jit envelopes are dropped as containers
    assert "jit_train_step" not in ops
    assert counts["fusion.1"] == 2 and counts["all-reduce.2"] == 2


def test_host_events_never_attributed():
    ops, _ = obs_trace.leaf_device_ops(fixture_events())
    assert "hostfn" not in ops


def test_no_device_track_is_loud():
    events = [e for e in fixture_events() if e.get("pid") != 100]
    with pytest.raises(RuntimeError, match="no TPU/GPU device track"):
        obs_trace.leaf_device_ops(events)


def test_step_reconstruction_from_step_track():
    spans, source = obs_trace.step_spans(fixture_events())
    assert source == "step-track"
    assert spans == STEP_SPANS


def test_step_reconstruction_envelope_fallback():
    spans, source = obs_trace.step_spans(fixture_events(False))
    assert source == "envelopes"
    assert spans == STEP_SPANS


def test_bucket_attribution_matches_hand_count():
    for with_steps in (True, False):
        s = obs_trace.summarize_trace(fixture_events(with_steps))
        assert len(s.steps) == 2
        assert s.steps[0].buckets == pytest.approx(STEP0)
        assert s.steps[1].buckets == pytest.approx(STEP1)
        assert s.totals == pytest.approx(
            {k: STEP0[k] + STEP1[k] for k in STEP0})


def test_step_track_envelopes_not_counted_as_device_work():
    # the "Steps" envelopes (100 us each, alone on their track) must not
    # inflate any bucket: totals are identical with and without them
    with_track = obs_trace.summarize_trace(fixture_events(True)).totals
    without = obs_trace.summarize_trace(fixture_events(False)).totals
    assert with_track == pytest.approx(without)


def test_device_op_times_excludes_step_track_envelopes(tmp_path):
    # the experiment scripts' entry point: the digit-named step
    # envelopes must not appear as giant "elementwise/other" leaves
    run = write_trace_dir(tmp_path, fixture_events(), "ops")
    ops, counts = obs_trace.device_op_times(str(run))
    assert "1" not in ops and "2" not in ops
    assert ops["copy-done.5"] == 50 and counts["fusion.1"] == 2


def test_summarize_accepts_uncompressed_trace_file(tmp_path):
    # a gunzipped trace (decompressed for inspection) routes to the
    # trace parser, not the metrics jsonl reader
    f = tmp_path / "host.trace.json"
    f.write_text(json.dumps({"traceEvents": fixture_events()}))
    out = io.StringIO()
    assert obs_main(["summarize", str(f)], out=out) == 0
    assert "collective" in out.getvalue()


def test_classify_and_buckets():
    assert obs_trace.classify("all-reduce.1") == "collective"
    assert obs_trace.classify("convert_reduce_fusion") == "reduce/norm"
    assert obs_trace.bucket_of("all-gather.3") == "collective"
    assert obs_trace.bucket_of("infeed.1") == "host-transfer"
    assert obs_trace.bucket_of("loop_fusion.9") == "compute"


def test_trace_cli_summarize_and_diff(tmp_path):
    run_a = write_trace_dir(tmp_path, fixture_events(), "a")
    # run_b: step 1's all-reduce grows 40 -> 50 us (moved to stay a leaf
    # inside its span), total collective 70 -> 80; compute unchanged
    events_b = []
    for e in fixture_events():
        e = dict(e)
        if e.get("name") == "all-reduce.2" and e.get("ts") == 175:
            e["ts"], e["dur"] = 170, 50
        events_b.append(e)
    run_b = write_trace_dir(tmp_path, events_b, "b")
    out = io.StringIO()
    assert obs_main(["summarize", str(run_a)], out=out) == 0
    text = out.getvalue()
    assert "collective" in text and "idle-bubble" in text
    out = io.StringIO()
    assert obs_main(["diff", str(run_a), str(run_b)], out=out) == 0
    text = out.getvalue()
    # 70 -> 80 us collective = +14.3%; compute flat
    assert "+14.3%" in text
    assert "collective" in text


# ---------------------------------------------------------------------
# 2. metrics fixtures + CLI


def write_metrics_run(tmp_path: Path, name: str, rate: float,
                      buckets: dict, config=None) -> Path:
    d = tmp_path / name
    writer = obs_metrics.MetricsWriter(
        str(d), {"schema": 1, "model": "trivial", "fabric": "ici",
                 "jax_version": "0", "jaxlib_version": "0",
                 "git_sha": "f" * 40, "process_count": 1,
                 "device_count": 8, "platform": "cpu",
                 "config": config or {"batch_size": 2}},
        primary=True)
    assert writer.enabled
    for step in (2, 4):
        writer.event("window", step=step, rate=rate,
                     step_ms=1e3 * 16 / rate, loss=4.2 - step / 10)
    writer.event("trace_buckets", buckets=buckets)
    writer.event("summary", total_images_per_sec=rate,
                 images_per_sec_per_chip=rate / 8,
                 mean_step_ms=1e3 * 16 / rate, p50_step_ms=1e3 * 16 / rate,
                 p50_step_granularity=1, mfu=0.01, final_loss=3.8)
    writer.close()
    return d


def test_metrics_summarize_renders_fixture(tmp_path):
    d = write_metrics_run(tmp_path, "a", 100.0,
                          {"compute": 100.0, "collective": 50.0,
                           "host-transfer": 10.0, "idle-bubble": 20.0})
    out = io.StringIO()
    assert obs_main(["summarize", str(d)], out=out) == 0
    text = out.getvalue()
    assert "model=trivial" in text
    assert "git=ffffffffffff" in text
    assert "trace buckets" in text


def test_metrics_diff_reports_bucket_deltas(tmp_path):
    a = write_metrics_run(tmp_path, "a", 100.0,
                          {"compute": 100.0, "collective": 50.0,
                           "host-transfer": 10.0, "idle-bubble": 20.0})
    b = write_metrics_run(tmp_path, "b", 80.0,
                          {"compute": 100.0, "collective": 70.0,
                           "host-transfer": 10.0, "idle-bubble": 20.0})
    out = io.StringIO()
    assert obs_main(["diff", str(a), str(b)], out=out) == 0
    text = out.getvalue()
    # the regression view: collective +40%, compute flat, rate -20%
    assert "+40.0%" in text
    assert "+0.0%" in text
    assert "-20.0%" in text


def test_metrics_diff_flags_config_drift(tmp_path):
    a = write_metrics_run(tmp_path, "a", 100.0, {"compute": 1.0},
                          config={"batch_size": 2})
    b = write_metrics_run(tmp_path, "b", 90.0, {"compute": 1.0},
                          config={"batch_size": 4})
    out = io.StringIO()
    obs_main(["diff", str(a), str(b)], out=out)
    assert "config: batch_size: 2 -> 4" in out.getvalue()


def test_cli_rejects_nonexistent_artifact(tmp_path, capsys):
    # one clear line + exit 2, not a traceback (the CLI meets operators
    # mid-incident; tests/test_goodput.py covers the degraded-dir matrix)
    rc = obs_main(["summarize", str(tmp_path / "nope")], out=io.StringIO())
    assert rc == 2
    assert capsys.readouterr().err.startswith("error:")


def test_writer_disabled_paths(tmp_path):
    w = obs_metrics.MetricsWriter(None)
    assert not w.enabled
    w.event("window", step=1)   # no-ops, no crash
    w.close()
    # non-primary process never writes
    w = obs_metrics.MetricsWriter(str(tmp_path / "np"), {"schema": 1},
                                  primary=False)
    assert not w.enabled and not (tmp_path / "np").exists()


# ---------------------------------------------------------------------
# 3. end-to-end: driver run -> artifact -> summarize/diff


def _tiny_cfg(**kw):
    base = dict(batch_size=2, num_warmup_batches=1, num_batches=4,
                display_every=2, model="trivial", num_classes=10)
    base.update(kw)
    return flags.BenchmarkConfig(**base).resolve()


def _run(tmp_path, name, **kw):
    cfg = _tiny_cfg(metrics_dir=str(tmp_path / name), **kw)
    out: list[str] = []
    res = driver.run_benchmark(cfg, print_fn=out.append)
    return cfg, res, out


def test_driver_run_writes_metrics_and_manifest(tmp_path):
    cfg, res, _ = _run(tmp_path, "run_a")
    run_dir = tmp_path / "run_a"
    manifest = json.loads((run_dir / "manifest.json").read_text())
    assert manifest["model"] == "trivial"
    assert manifest["config"]["num_batches"] == 4
    assert manifest["device_count"] == 8
    assert manifest["mesh_shape"]["data"] == 8   # DP mesh: (data, model=1)
    assert manifest["jax_version"]
    # "unknown" is the documented fallback on non-git checkouts
    assert manifest["git_sha"] == "unknown" or len(manifest["git_sha"]) == 40
    records = [json.loads(line) for line in
               (run_dir / "metrics.jsonl").read_text().splitlines()]
    kinds = [r["kind"] for r in records]
    assert kinds.count("window") == 2      # steps 2 and 4
    assert kinds[-1] == "summary"
    assert "memory" in kinds
    summary = records[-1]
    assert summary["total_images_per_sec"] == pytest.approx(
        res.total_images_per_sec)
    assert summary["p50_step_granularity"] == res.p50_step_granularity
    # CPU mesh completes fetches faster than steps retire: granularity
    # must be honest either way — a positive int no wider than the run
    assert 1 <= res.p50_step_granularity <= 4
    assert res.p50_step_ms > 0


def test_driver_metrics_summarize_and_diff_end_to_end(tmp_path):
    _run(tmp_path, "run_a")
    _run(tmp_path, "run_b", batch_size=4)
    out = io.StringIO()
    assert obs_main(["summarize", str(tmp_path / "run_a")], out=out) == 0
    assert "model=trivial" in out.getvalue()
    out = io.StringIO()
    assert obs_main(["diff", str(tmp_path / "run_a"),
                     str(tmp_path / "run_b")], out=out) == 0
    text = out.getvalue()
    assert "config: batch_size: 2 -> 4" in text
    assert "total ex/s" in text


def test_eval_run_writes_metrics(tmp_path):
    cfg = _tiny_cfg(metrics_dir=str(tmp_path / "ev"), eval=True)
    out: list[str] = []
    driver.run_benchmark(cfg, print_fn=out.append)
    records = [json.loads(line) for line in
               (tmp_path / "ev" / "metrics.jsonl").read_text().splitlines()]
    kinds = [r["kind"] for r in records]
    assert "window" in kinds and kinds[-1] == "summary"
    assert "eval_top_1" in records[-1]


def _profiler_works() -> bool:
    import tempfile

    import jax

    try:
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d)
            jax.profiler.stop_trace()
        return True
    except Exception:
        return False


def test_profile_steps_window_single_stop(tmp_path):
    if not _profiler_works():
        pytest.skip("jax.profiler unavailable on this backend")
    cfg = _tiny_cfg(trace_dir=str(tmp_path / "tr"), profile_steps="2:3",
                    num_batches=4)
    out: list[str] = []
    driver.run_benchmark(cfg, print_fn=out.append)  # double-stop would raise
    text = "\n".join(out)
    assert "profiler trace written" in text
    # CPU profiler writes host tracks only: the post-run summary must
    # degrade loudly-but-gracefully, not kill the run
    assert ("trace summary" in text) or ("bucket" in text)


def test_profile_steps_rejected_under_eval():
    with pytest.raises(ValueError, match="--eval"):
        flags.BenchmarkConfig(profile_steps="1:2", trace_dir="/tmp/x",
                              eval=True).resolve()


def test_profile_window_past_run_end_warns_loudly(tmp_path):
    # window start beyond the run: the profiler never starts, and the
    # run says so instead of silently writing no trace
    cfg = _tiny_cfg(trace_dir=str(tmp_path / "never"),
                    profile_steps="50:60", num_batches=3)
    out: list[str] = []
    driver.run_benchmark(cfg, print_fn=out.append)
    text = "\n".join(out)
    assert "never started" in text
    assert "profiler trace written" not in text


def test_profile_steps_window_past_run_end_stops_once(tmp_path):
    if not _profiler_works():
        pytest.skip("jax.profiler unavailable on this backend")
    # window end beyond num_batches: the post-loop stop is the only stop
    cfg = _tiny_cfg(trace_dir=str(tmp_path / "tr2"), profile_steps="1:99",
                    num_batches=3)
    out: list[str] = []
    driver.run_benchmark(cfg, print_fn=out.append)
    assert sum("profiler trace written" in ln for ln in out) == 1


# ---------------------------------------------------------------------
# 4. repo hygiene: bytecode never tracked (satellite)


def test_no_bytecode_tracked_in_git():
    ls = subprocess.run(["git", "-C", str(REPO), "ls-files"],
                        capture_output=True, text=True, timeout=30)
    if ls.returncode != 0:
        pytest.skip("not a git checkout")
    bad = [f for f in ls.stdout.splitlines()
           if f.endswith((".pyc", ".pyo")) or "__pycache__" in f]
    assert not bad, f"bytecode artifacts tracked: {bad}"
    gitignore = (REPO / ".gitignore").read_text()
    assert "__pycache__/" in gitignore and "*.pyc" in gitignore


def test_scripts_have_no_local_perfetto_parsing():
    """obs.trace is the one home of trace parsing: no script under
    scripts/ carries a perfetto parser of its own."""
    for path in sorted((REPO / "scripts").glob("*.py")):
        src = path.read_text()
        assert "traceEvents" not in src, path.name
        assert "trace.json.gz" not in src, path.name
