#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that both lanes still start on the chip.

Drives the main path of the training lane (``tpu_hc_bench.launcher.main``)
and the serving lane (``tpu_hc_bench.serve.cli.main``) once each, through
the entry points a user would call, at the published widths of models the
repo supports (resnet50; gpt2 12 L / 768 / 12 heads x 64), with seeded
random weights, and checks what comes out by the repo's own means.  It
fails if any part fails, and it refuses to run without a TPU: no CPU
fallback.

Process model: a chip belongs to one process at a time, so this file is
a PARENT that imports neither JAX nor ``tpu_hc_bench`` and runs the
stages as SEQUENTIAL children (``--child <stage>``), each of which is the
one process holding the chip while it runs.  Every child is started in
its own process group and killed with it on timeout or error.

Stages (children):

- ``train``   device banner; resnet50 reference experiment; gpt2 with the
              flash kernel against the dense arm.  On a host with several
              chips it also checks placement on every device, equal-
              global-batch loss against one chip, a ``zero1`` run and the
              OSU allreduce sweep on ICI.
- ``kernels`` each Pallas kernel on the serving/training hot paths
              against its reference at the real dims.
- ``serve``   gpt2 (float32: the serve lane is f32-only) gather arm,
              then the paged r25 lane; logit-level paged-vs-gather
              parity on one warmed engine pair; compile-only int8_kv and
              ``--decode_block_pages=4`` decode buckets.

Everything a stage writes goes under ``--out`` (default
``chiprun_out/chip_smoke`` beside this file — the directory the chip tool
copies back).  The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every stage passed.
Re-running in the same call with the same cache directory reports zero
new compile-cache entries per stage ("warm start") and the warm set-up
seconds beside the cold ones.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
STAGES = ("train", "kernels", "serve")
BUDGET_S = 1140.0           # the contract is 1200 s, compilation included

# ---------------------------------------------------------------------
# tolerances, each with its reason (widths are published; values seeded).
# "measured" = this file's first passes on the v5e (PR 21, single runs).

# gpt2 flash vs dense, loss at the first timed step (3 identical-seed
# warm-up steps before it), bf16 compute.  Both arms accumulate scores
# and the softmax in f32 and differ only in blocking/summation order, so
# the per-token difference is a few bf16 roundings (2^-8 relative) and
# the loss is a mean over 8 x 1024 tokens in which they average out.
# Measured |delta| 6.7e-5 on a loss of 11.27; the bound leaves ~75x.
# (At random init the loss is near ln(vocab) whatever attention does, so
# this is the end-to-end guard; the kernel stage below is the sharp one.)
FLASH_VS_DENSE_LOSS_ATOL = 5e-3

# Kernel stage.  Reference = the repo's dense/gather formula under
# jax.default_matmul_precision("highest"); the kernels run what the lanes
# run.  On a TPU a matmul on f32 operands is carried out in bf16 passes
# unless "highest" is asked for, and bf16 operands round each product's
# inputs to 2^-8: with head_dim 64 and unit-variance q/k the scores carry
# up to ~1e-2 absolute error, which the softmax and the value average
# pass on at about the same size.  Bounds are ~4x what was measured.
FLASH_FWD_ATOL = 3e-2       # bf16 q/k/v, outputs O(1); measured 7.8e-3
FLASH_BWD_RTOL = 2.5e-2     # of each gradient's max |value|; measured 5e-3
PAGED_ATOL = 1e-2           # f32 pool, and int8 pool against its own
                            # dequantized values; measured 2.6e-3
NORM_ATOL = 1e-5            # no matmul: f32 VPU math + one rsqrt;
                            # measured 9.5e-7

# paged vs gather decode LOGITS on one warmed engine pair, gpt2 f32 at
# default matmul precision in BOTH arms (what the serve lane runs; not
# "highest").  The arms differ in the attention inner products (Mosaic
# f32 matmul vs XLA's default-precision einsum), the lse merge of the
# fresh token and the fused norms; the bound is a fraction of the
# reference's logit range so it holds for any seed.  Measured max
# |delta| 1.8e-2 on a range of 9.47 (0.2%); the bound leaves ~5x.
PAGED_VS_GATHER_LOGIT_FRAC = 0.01

# several chips vs one chip at equal global batch (gpt2, global 8): GPT
# has dropout 0.1 and the per-device dropout key is folded with the
# device's axis index, so the masks differ between the layouts; at init
# the loss is ln(vocab) plus a dropout-dependent O(1e-2) term.  Measured
# on the 2x2 host: |delta| 7.7e-3 (and zero1 vs psum on the same four
# chips, same masks: 7.5e-5); the bound leaves ~6x.
MULTICHIP_LOSS_ATOL = 0.05


# ---------------------------------------------------------------------
# parent: no JAX in this process


def cache_dir() -> str:
    """Where the children's compile cache lives (the rule of
    ``tpu_hc_bench.utils.compile_cache``, restated so the parent can
    count entries without importing the package; the train child checks
    the two agree)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(HERE, ".jax_cache"))


def count_entries(path: str) -> int:
    return sum(len(files) for _, _, files in os.walk(path))


def run_child(stage: str, out: str, timeout_s: float) -> int:
    """One stage in its own process group, output teed to
    ``<out>/<stage>.log``; killed with its group on timeout."""
    env = dict(os.environ)
    # the launcher writes ~/logs and ~/.tpu_hc_bench/setenv: keep them
    # under the copy-back directory
    env["HOME"] = os.path.join(out, "home")
    env["PYTHONPATH"] = os.pathsep.join(
        [HERE, env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    env["PYTHONUNBUFFERED"] = "1"
    os.makedirs(env["HOME"], exist_ok=True)
    cmd = [sys.executable, os.path.abspath(__file__), "--child", stage,
           "--out", out]
    proc = subprocess.Popen(
        cmd, env=env, cwd=HERE, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, start_new_session=True)
    deadline = time.monotonic() + timeout_s

    def kill_group() -> None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass

    # a reader thread would outlive a hung child; a timer that kills the
    # group makes readline() return instead
    timer = threading.Timer(timeout_s, kill_group)
    timer.daemon = True
    timer.start()
    try:
        with open(os.path.join(out, f"{stage}.log"), "w") as log:
            for line in proc.stdout:
                sys.stdout.write(line)
                sys.stdout.flush()
                log.write(line)
        rc = proc.wait()
        if time.monotonic() >= deadline and rc != 0:
            print(f"chip_smoke: stage {stage} killed at its "
                  f"{timeout_s:.0f}s limit", flush=True)
        return rc
    finally:
        timer.cancel()
        kill_group()            # stragglers of the group, if any
        proc.wait()


def parent(args) -> int:
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    stages = [s for s in args.stages.split(",") if s]
    for s in stages:
        if s not in STAGES:
            raise SystemExit(f"unknown stage {s!r} (stages: {STAGES})")
    t_start = time.monotonic()
    cdir = cache_dir()
    report, failed = [], []
    for stage in stages:
        left = BUDGET_S - (time.monotonic() - t_start)
        if left <= 0:
            print(f"chip_smoke: out of time before stage {stage}")
            failed.append(stage)
            break
        before = count_entries(cdir)
        t0 = time.monotonic()
        print(f"=== chip_smoke stage {stage} "
              f"(cache {cdir}: {before} entries) ===", flush=True)
        result_path = os.path.join(out, f"{stage}.json")
        if os.path.exists(result_path):
            os.remove(result_path)
        rc = run_child(stage, out, left)
        wall = time.monotonic() - t0
        after = count_entries(cdir)
        if rc != 0 or not os.path.exists(result_path):
            # keep going: one call should show every broken stage
            print(f"chip_smoke: stage {stage} FAILED (exit {rc}) after "
                  f"{wall:.0f}s", flush=True)
            failed.append(stage)
            if rc == 2:         # no TPU: the other stages say the same
                break
            continue
        with open(result_path) as f:
            result = json.load(f)
        report.append({
            "stage": stage, "wall_s": round(wall, 1),
            "setup_s": result.get("setup_s"),
            "cache_entries_before": before,
            "cache_new_entries": after - before,
            "warm_start": after == before and before > 0,
            "device": result["device"],
        })
    print("=== chip_smoke report ===")
    for r in report:
        print(f"stage {r['stage']:8s} wall {r['wall_s']:7.1f}s  "
              f"set-up {json.dumps(r['setup_s'])}  compile cache: "
              f"{r['cache_new_entries']} new entries "
              f"({'warm start' if r['warm_start'] else 'cold/partial'}; "
              f"{r['cache_entries_before']} before)")
    total = round(time.monotonic() - t_start, 1)
    print(f"total wall {total:.0f}s", flush=True)
    if failed:
        print(f"chip_smoke: FAILED stages: {failed}", flush=True)
        return 1
    summary = {"ok": True, "device": report[0]["device"]}
    with open(os.path.join(out, "report.json"), "w") as f:
        json.dump({"summary": summary, "stages": report,
                   "cache_dir": cdir, "total_wall_s": total}, f, indent=1)
    print(json.dumps(summary), flush=True)
    return 0


# ---------------------------------------------------------------------
# children: each is the one process that holds the chip


class Checks:
    """Named assertions that print as they go and fail the stage at the
    end, so one run shows every failed check, not the first."""

    def __init__(self, stage: str):
        self.stage = stage
        self.failed: list[str] = []
        self.values: dict = {}
        self.setup_s: dict = {}     # run name -> compile/warm-up seconds

    def check(self, name: str, ok, detail="") -> bool:
        ok = bool(ok)
        print(f"[{self.stage}] {'ok  ' if ok else 'FAIL'} {name}"
              + (f": {detail}" if detail != "" else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return ok

    def note(self, name: str, value) -> None:
        self.values[name] = value
        print(f"[{self.stage}] note {name} = {value}", flush=True)

    def finish(self, out: str, **extra) -> int:
        if self.failed:
            print(f"[{self.stage}] FAILED checks: {self.failed}",
                  flush=True)
            return 1
        with open(os.path.join(out, f"{self.stage}.json"), "w") as f:
            json.dump({"ok": True, "values": self.values,
                       "setup_s": self.setup_s or None, **extra}, f,
                      indent=1, default=str)
        return 0


def require_tpu() -> dict:
    """First thing in every child: the backend is ``tpu`` or the stage
    dies.  Returns the device record the last line reports."""
    import jax

    backend = jax.default_backend()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if backend != "tpu" or device["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend={backend!r}, "
              f"devices={device}); refusing to fall back", flush=True)
        raise SystemExit(2)
    return device


def read_metrics(mdir: str) -> tuple[dict, list[dict]]:
    with open(os.path.join(mdir, "manifest.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(mdir, "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f if line.strip()]
    return manifest, records


def last(records: list[dict], kind: str) -> dict:
    return [r for r in records if r["kind"] == kind][-1]


def custom_call_lines(text: str) -> list[str]:
    return [ln for ln in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


class ProbeSpy:
    """Catches the run's OWN compiled step: the driver's FLOPs probe
    AOT-compiles the very program the run executes (its abstract args
    carry the live state's and batch's committed shardings) and hands
    the handle to ``obs.efficiency.flops_of_compiled``."""

    def __init__(self):
        from tpu_hc_bench.obs import efficiency

        self._mod = efficiency
        self._orig = efficiency.flops_of_compiled
        self.compiled = []
        efficiency.flops_of_compiled = self._spy

    def _spy(self, compiled):
        self.compiled.append(compiled)
        return self._orig(compiled)

    def take(self):
        assert self.compiled, "the run's FLOPs probe never compiled"
        return self.compiled.pop()

    def close(self):
        self._mod.flops_of_compiled = self._orig


# the reference experiment (README quick start at the chip's batch), and
# gpt2 at its published widths: seq 1024, per-chip batch 8
RESNET50 = ["1", "0", "128", "ici", "--model=resnet50", "--use_fp16=True",
            "--num_warmup_batches=5", "--num_batches=20"]
GPT2 = ["1", "0", "8", "ici", "--model=gpt2", "--use_fp16=True",
        "--num_warmup_batches=3", "--num_batches=5", "--display_every=1"]
GPT2_LAYERS = 12


def train_run(c: Checks, name: str, argv: list[str], out: str):
    """One launcher.main run; the common assertions; returns
    ``(summary record, timed-step losses, manifest)``."""
    from tpu_hc_bench import launcher

    mdir = os.path.join(out, name)
    rc = launcher.main(argv + [f"--metrics_dir={mdir}"])
    c.check(f"{name}: exit code 0", rc == 0, rc)
    manifest, records = read_metrics(mdir)
    summary = last(records, "summary")
    losses = [r["loss"] for r in records if r["kind"] == "window"]
    c.check(f"{name}: loss finite", all(math.isfinite(x) for x in losses),
            losses)
    c.check(f"{name}: manifest platform=tpu",
            manifest["platform"] == "tpu", manifest["platform"])
    c.setup_s[name] = (summary.get("goodput_phases") or {}).get("compile")
    c.note(f"{name}: compile phase s", c.setup_s[name])
    c.note(f"{name}: mean step ms", round(summary["mean_step_ms"], 2))
    return summary, losses, manifest


def child_train(out: str) -> int:
    import importlib.metadata as md

    import jax

    device = require_tpu()
    c = Checks("train")
    n = device["count"]
    from tpu_hc_bench.utils import compile_cache

    resolved = compile_cache.resolve(None)
    print(f"[train] platform={device['platform']} "
          f"device_kind={device['kind']} device_count={n} "
          f"jax={jax.__version__} jaxlib={md.version('jaxlib')} "
          f"libtpu={md.version('libtpu')} compile_cache={resolved}",
          flush=True)
    c.check("compile cache is where the parent counts",
            os.path.realpath(resolved) == os.path.realpath(cache_dir()),
            f"{resolved} vs {cache_dir()}")
    spy = ProbeSpy()

    # --- the reference experiment: resnet50 bs 128/chip bf16
    s, losses, manifest = train_run(c, "train_resnet50", RESNET50, out)
    resnet_step = spy.take()
    c.check("resnet50: loss falling", losses[-1] < losses[0], losses)
    c.check("resnet50: step markers resolve single steps",
            s["p50_step_granularity"] == 1, s["p50_step_granularity"])
    c.check("resnet50: mfu_source == measured",
            s["mfu_source"] == "measured", s["mfu_source"])
    c.check("resnet50: memory source is the allocator peak",
            s["mem_source"] == "memory_stats", s["mem_source"])
    c.check("resnet50: all local chips in the mesh",
            s["total_workers"] == n and manifest["device_count"] == n
            and s["global_batch"] == int(RESNET50[2]) * n
            and manifest["mesh_shape"].get("data") == n,
            f"workers={s['total_workers']} global_batch="
            f"{s['global_batch']} mesh={manifest['mesh_shape']}")
    c.note("resnet50: peak HBM MiB",
           round((s["peak_hbm_bytes"] or 0) / 2**20))
    c.note("resnet50: images/sec/chip (single run, not a speed result)",
           round(s["images_per_sec_per_chip"], 1))

    # --- transformer with the flash kernel, against the dense arm
    s_f, loss_f, _ = train_run(
        c, "train_gpt2_flash", GPT2 + ["--attention_impl=flash"], out)
    calls = custom_call_lines(spy.take().as_text())
    bwd = [ln for ln in calls if "transpose(" in ln]
    c.check("gpt2 flash: Mosaic custom calls, forward and backward",
            len(calls) - len(bwd) >= GPT2_LAYERS
            and len(bwd) >= 2 * GPT2_LAYERS,
            f"{len(calls) - len(bwd)} forward, {len(bwd)} backward")
    c.check("gpt2 flash: step markers resolve single steps",
            s_f["p50_step_granularity"] == 1, s_f["p50_step_granularity"])
    _, loss_d, _ = train_run(
        c, "train_gpt2_dense", GPT2 + ["--attention_impl=dense"], out)
    c.check("gpt2 dense: no Mosaic custom call",
            not custom_call_lines(spy.take().as_text()))
    delta = abs(loss_f[0] - loss_d[0])
    c.check("gpt2: flash vs dense loss at the first timed step",
            delta <= FLASH_VS_DENSE_LOSS_ATOL,
            f"flash {loss_f[0]:.5f} dense {loss_d[0]:.5f} |delta| "
            f"{delta:.2e} (atol {FLASH_VS_DENSE_LOSS_ATOL})")
    c.note("gpt2 flash: peak HBM MiB",
           round((s_f["peak_hbm_bytes"] or 0) / 2**20))

    if n > 1:
        multichip(c, spy, resnet_step, out, n)
    spy.close()
    return c.finish(out, device=device)


def multichip(c: Checks, spy: ProbeSpy, resnet_step, out: str,
              n: int) -> None:
    """Several chips are the point of this system: prove the state and
    the batch are placed on all of them, that the loss is the one-chip
    loss at equal global batch, that zero1 steps and that the OSU sweep
    runs on ICI."""
    import jax

    # the run's own step program: (state, batch, rng) shardings
    args, _ = resnet_step.input_shardings
    state_sh, batch_sh, _ = args
    params_sh = jax.tree.leaves(state_sh.params)
    c.check("params replicated on every device",
            all(len(s.device_set) == n and s.is_fully_replicated
                for s in params_sh),
            f"{len(params_sh)} leaves")
    c.check("batch sharded over every device",
            all(len(s.device_set) == n and not s.is_fully_replicated
                for s in jax.tree.leaves(batch_sh)))
    peaks = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()}
    c.check("peak_bytes_in_use > 0 on every local device",
            all(v > 0 for v in peaks.values()),
            {k: f"{v / 2**20:.0f} MiB" for k, v in peaks.items()})
    c.note("per-device peak HBM MiB",
           {k: round(v / 2**20) for k, v in peaks.items()})

    # equal global batch: n chips x batch/n against ONE chip x batch
    flash = ["--attention_impl=flash"]
    gb = int(GPT2[2])
    small = GPT2[:2] + [str(gb // n)] + GPT2[3:]
    s_n, loss_n, _ = train_run(c, f"train_gpt2_{n}chip_equal_batch",
                               small + flash, out)
    spy.take()
    one = ["1", "1"] + GPT2[2:]
    s_1, loss_1, _ = train_run(c, "train_gpt2_1chip_equal_batch",
                               one + flash, out)
    spy.take()
    c.check("equal global batch on n chips and on one",
            s_n["global_batch"] == gb == s_1["global_batch"]
            and s_n["total_workers"] == n and s_1["total_workers"] == 1,
            f"{s_n['total_workers']}x{s_n['global_batch'] // n} vs "
            f"{s_1['total_workers']}x{s_1['global_batch']}")
    delta = abs(loss_n[0] - loss_1[0])
    c.check(f"loss on {n} chips == loss on one chip at global batch {gb}",
            delta <= MULTICHIP_LOSS_ATOL,
            f"{n}-chip {loss_n[0]:.5f} one-chip {loss_1[0]:.5f} |delta| "
            f"{delta:.2e} (atol {MULTICHIP_LOSS_ATOL})")

    s_z, loss_z, _ = train_run(
        c, "train_gpt2_zero1",
        small + flash + ["--variable_update=zero1"], out)
    spy.take()
    c.check("zero1 steps over every chip",
            s_z["total_workers"] == n and len(loss_z) == 5, loss_z)

    from tpu_hc_bench.microbench import osu

    t0 = time.monotonic()
    osu.main(["--op", "allreduce", "--max_bytes", "16777216",
              "--json", os.path.join(out, "osu_allreduce.json")])
    with open(os.path.join(out, "osu_allreduce.json")) as f:
        sweep = json.load(f)
    c.check("OSU allreduce sweep completed on ICI",
            sweep["world_size"] == n
            and sweep["sweeps"]["allreduce"][-1]["message_bytes"]
            == 16777216,
            f"world {sweep['world_size']}, "
            f"{len(sweep['sweeps']['allreduce'])} sizes in "
            f"{time.monotonic() - t0:.0f}s")
    c.note("OSU allreduce busbw GB/s at 16 MiB (single run)",
           round(sweep["sweeps"]["allreduce"][-1]["busbw_gbps"], 1))


# kernel-stage dims: gpt2's train shape (batch, seq, heads, head_dim) and
# the serve stage's pool (layers, kv heads, pages, page size, head_dim,
# rows, table width); llama_1b's hidden for the rmsnorm compare
FLASH_DIMS = (8, 1024, 12, 64)
PAGED_DIMS = (12, 12, 289, 16, 64, 8, 36)
NORM_DIMS = (("layernorm", 768), ("rmsnorm", 2048))


def child_kernels(out: str) -> int:
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    device = require_tpu()
    c = Checks("kernels")
    from tpu_hc_bench.utils import compile_cache

    compile_cache.resolve(None)     # no lane runs here to place it
    from tpu_hc_bench.models.llama import RMSNorm
    from tpu_hc_bench.ops.flash_attention import flash_attention
    from tpu_hc_bench.ops.fused_residual_ln import fused_residual_norm
    from tpu_hc_bench.ops.paged_attention import paged_decode_attention
    from tpu_hc_bench.parallel.sequence import dense_attention
    from tpu_hc_bench.serve.decode import _softmax_attend

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))

    def normal(shape, dtype=jnp.float32):
        return jax.random.normal(next(keys), shape, dtype)

    def mosaic(fn, *a) -> int:
        return len(custom_call_lines(
            jax.jit(fn).lower(*a).compile().as_text()))

    def highest(fn, *a):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*a)

    def max_abs(x) -> float:
        return float(jnp.max(jnp.abs(x)))

    # --- flash attention, forward + custom-VJP backward, bf16
    q, k, v = (normal(FLASH_DIMS, jnp.bfloat16) for _ in range(3))
    w = normal(FLASH_DIMS)          # a fixed cotangent

    def flash_fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def dense_fwd(q, k, v):
        return dense_attention(
            *(x.astype(jnp.float32) for x in (q, k, v)), causal=True)

    def flash_loss(q, k, v, w):
        return jnp.sum(flash_fwd(q, k, v) * w)

    def dense_loss(q, k, v, w):
        return jnp.sum(dense_fwd(q, k, v) * w)

    flash_grad = jax.grad(flash_loss, (0, 1, 2))
    c.check("flash: forward lowers through Mosaic",
            mosaic(flash_fwd, q, k, v) == 1)
    c.check("flash: backward lowers through Mosaic (fwd + dq + dk/dv)",
            mosaic(flash_grad, q, k, v, w) == 3)
    err = max_abs(jax.jit(flash_fwd)(q, k, v).astype(jnp.float32)
                  - highest(dense_fwd, q, k, v))
    c.check("flash: forward == dense reference", err <= FLASH_FWD_ATOL,
            f"max |delta| {err:.2e} (atol {FLASH_FWD_ATOL})")
    g_got = jax.jit(flash_grad)(q, k, v, w)
    g_want = highest(jax.grad(dense_loss, (0, 1, 2)), q, k, v, w)
    for name, a, b in zip(("dq", "dk", "dv"), g_got, g_want):
        rel = max_abs(a.astype(jnp.float32) - b) / max_abs(b)
        c.check(f"flash: {name} == dense reference", rel <= FLASH_BWD_RTOL,
                f"max |delta| / max |ref| {rel:.2e} "
                f"(rtol {FLASH_BWD_RTOL})")

    # --- paged decode attention over the serve stage's pool: the pool's
    # lanes are head_dim padded to the 128-lane tile, pad lanes zero
    L, kvh, pages, ps, d, b, width = PAGED_DIMS
    lanes = -(-d // 128) * 128
    pad = ((0, 0),) * 4 + ((0, lanes - d),)
    kp, vp = (jnp.pad(normal((L, kvh, pages, ps, d)), pad)
              for _ in range(2))
    qd = normal((b, kvh, d))
    tables = jax.random.randint(next(keys), (b, width), 1, pages)
    lengths = jax.random.randint(next(keys), (b,), 1, width * ps + 1)
    layer = L - 5

    def gather_reference(qd, kp, vp, tables, lengths):
        def rows(x):            # [kvh, pages, ps, lanes] -> [b, S, kvh, d]
            r = x[layer][:, tables][..., :d]
            return r.transpose(1, 2, 3, 0, 4).reshape(b, width * ps, kvh, d)

        mask = jnp.arange(width * ps)[None, :] < lengths[:, None]
        return _softmax_attend(qd[:, None], rows(kp), rows(vp), mask)[:, 0]

    def quantize(x):            # one scale per (layer, page)
        amax = jnp.max(jnp.abs(x), axis=(1, 3, 4))
        sc = jnp.maximum(amax / 127.0, 1e-8)
        xq = jnp.clip(jnp.round(x / sc[:, None, :, None, None]),
                      -127, 127).astype(jnp.int8)
        return xq, sc

    def dequantize(xq, sc):
        return xq.astype(jnp.float32) * sc[:, None, :, None, None]

    (kq, ks), (vq, vs) = jax.jit(quantize)(kp), jax.jit(quantize)(vp)
    want = highest(gather_reference, qd, kp, vp, tables, lengths)
    want_q = highest(gather_reference, qd, dequantize(kq, ks),
                     dequantize(vq, vs), tables, lengths)
    for ppb in (1, 4):
        def f32(qd, kp, vp, tables, lengths):
            return paged_decode_attention(
                qd, kp, vp, tables, lengths, pages_per_block=ppb,
                layer=layer)

        def int8(qd, kq, vq, tables, lengths, ks, vs):
            return paged_decode_attention(
                qd, kq, vq, tables, lengths, pages_per_block=ppb,
                layer=layer, k_scales=ks, v_scales=vs)

        for name, fn, a, ref in (
                ("f32", f32, (qd, kp, vp, tables, lengths), want),
                ("int8", int8, (qd, kq, vq, tables, lengths, ks, vs),
                 want_q)):
            c.check(f"paged {name} pages_per_block={ppb}: lowers through "
                    f"Mosaic", mosaic(fn, *a) == 1)
            err = max_abs(jax.jit(fn)(*a) - ref)
            c.check(f"paged {name} pages_per_block={ppb} == gather "
                    f"reference", err <= PAGED_ATOL,
                    f"max |delta| {err:.2e} (atol {PAGED_ATOL})")

    # --- fused residual + norm at the decode step's [rows, 1, hidden]
    for kind, hidden in NORM_DIMS:
        res, x = normal((8, 1, hidden)), normal((8, 1, hidden))
        gamma, beta = normal((hidden,)), normal((hidden,))
        if kind == "layernorm":
            def fn(res, x, gamma, beta):
                return fused_residual_norm(res, x, gamma, beta)

            ref = nn.LayerNorm().apply(
                {"params": {"scale": gamma, "bias": beta}}, res + x)
        else:
            def fn(res, x, gamma, beta):
                del beta
                return fused_residual_norm(res, x, gamma, kind="rmsnorm")

            ref = RMSNorm().apply({"params": {"scale": gamma}}, res + x)
        c.check(f"fused {kind}@{hidden}: lowers through Mosaic",
                mosaic(fn, res, x, gamma, beta) == 1)
        y, o = jax.jit(fn)(res, x, gamma, beta)
        err = max(max_abs(y - (res + x)), max_abs(o - ref))
        c.check(f"fused {kind}@{hidden} == reference", err <= NORM_ATOL,
                f"max |delta| {err:.2e} (atol {NORM_ATOL})")
    return c.finish(out, device=device)


SERVE = ["--model=gpt2", "--max_prompt_len=512", "--max_output_len=64",
         "--kv_page_size=16", "--max_in_flight=8", "--num_requests=32",
         "--arrival_rate=8"]
R25 = ["--decode_attention=paged", "--kv_reserve=lazy",
       "--prefix_cache=on", "--kv_preempt=on"]
# prompt buckets 8..512 (7) + batch buckets 1,2,4,8 (4) + page_copy
SERVE_BUCKETS = 12
# per layer one paged-attention call and two fused norms; the first
# layer's attention norm has no pending residual and stays unfused
PAGED_CALLS = 3 * GPT2_LAYERS - 1
PARITY_PROMPTS = (200, 37)


def serve_run(c: Checks, name: str, argv: list[str], out: str) -> None:
    from tpu_hc_bench.serve import cli as serve_cli

    mdir = os.path.join(out, name)
    lines: list[str] = []

    def tee(msg: str) -> None:
        print(msg, flush=True)
        lines.append(msg)

    rc = serve_cli.main(argv + [f"--metrics_dir={mdir}"], print_fn=tee)
    c.check(f"{name}: exit code 0", rc == 0, rc)
    manifest, records = read_metrics(mdir)
    s = last(records, "serve_summary")
    comp = last(records, "serve_compile")
    c.check(f"{name}: every request completed",
            s["completed"] == s["requests"] > 0,
            f"{s['completed']}/{s['requests']}")
    c.check(f"{name}: post-warmup compiles: 0",
            s["post_warmup_compiles"] == 0
            and any("post-warmup compiles: 0" in ln for ln in lines),
            s["post_warmup_compiles"])
    c.check(f"{name}: every AOT bucket compiled on the TPU",
            comp["buckets"] == SERVE_BUCKETS
            and manifest["platform"] == "tpu",
            f"{comp['buckets']} buckets, platform {manifest['platform']}")
    c.check(f"{name}: banner names the serving device",
            any(ln.startswith("serve device: TPU") for ln in lines),
            next((ln for ln in lines if ln.startswith("serve device")),
                 None))
    c.setup_s[name] = comp["warmup_s"]
    c.note(f"{name}: warmup s / new cache entries",
           (comp["warmup_s"], comp["new_entries"]))
    c.note(f"{name}: tokens/s, p50 ttft ms (single run, not a speed "
           f"result)", (s["tokens_per_s"], s["p50_ttft_ms"]))


def child_serve(out: str) -> int:
    import jax
    import numpy as np

    device = require_tpu()
    c = Checks("serve")
    from tpu_hc_bench import flags
    from tpu_hc_bench.serve.engine import ServeEngine, pick_bucket

    serve_run(c, "serve_gather", SERVE, out)
    serve_run(c, "serve_paged", SERVE + R25, out)
    if device["count"] > 1:
        c.note("serve lane", f"single-device: ran on device 0 of "
                             f"{device['count']}")

    # --- one warmed engine pair, outside any timed window: the ladders
    # above are in the compile cache, so these two constructions load
    quiet = lambda m: None      # noqa: E731
    gather = ServeEngine(flags.parse_flags(SERVE, workload="serve"), quiet)
    paged = ServeEngine(flags.parse_flags(SERVE + R25, workload="serve"),
                        quiet)
    for b in paged.batch_buckets:
        text = paged.compiled[("decode", b)].as_text()
        calls = len(custom_call_lines(text))
        c.check(f"paged decode@{b}: Mosaic custom calls for both kernels",
                calls == PAGED_CALLS and "paged_decode_attention" in text
                and "fused_residual_norm" in text, f"{calls} custom calls")
    c.check("gather decode: no Mosaic custom call", not custom_call_lines(
        gather.compiled[("decode", gather.cap)].as_text()))
    # the pool is read and written where it rests: no program holds a
    # second array of a pool leaf's or a layer's shape (the paged arm
    # shares the write helper)
    from tpu_hc_bench.analysis import hlo

    for eng, arm, keys in (
            (gather, "gather", [("decode", gather.cap),
                                ("prefill", max(gather.prefill_buckets))]),
            (paged, "paged", [("prefill", max(paged.prefill_buckets))])):
        leaf = eng._kv[0].shape
        shapes = [hlo.shape_text(leaf), hlo.shape_text(leaf[1:])]
        for key in keys:
            found = hlo.new_buffers_of_shape(
                eng.compiled[key].as_text(), shapes)
            c.check(f"{arm} {key[0]}@{key[1]}: no new pool-shaped array",
                    not found,
                    ", ".join(f"{i.name} {i.opcode}" for i in found[:6])
                    or f"none of {shapes[0]} / {shapes[1]}")
        ratio = eng.compile_record["kv_pool_temp_ratio"]
        c.check(f"{arm}: kv_pool_temp_ratio < 1",
                ratio is not None and ratio < 1, ratio)

    rng = np.random.default_rng(0)
    vocab, w = gather.spec.vocab_size, gather.table_width
    prompts = [rng.integers(1, vocab, n).astype(np.int32)
               for n in PARITY_PROMPTS]
    steps = 8
    feed = rng.integers(1, vocab, (steps, 2)).astype(np.int32)
    tables = np.arange(1, 1 + 2 * w, dtype=np.int32).reshape(2, w)

    def logits_of(eng) -> np.ndarray:
        kv = eng._kv
        lengths = np.zeros((2,), np.int32)
        rows = []
        for i, prompt in enumerate(prompts):
            s = pick_bucket(eng.prefill_buckets, len(prompt))
            toks = np.zeros((1, s), np.int32)
            toks[0, :len(prompt)] = prompt
            _, logits, kv = eng.compiled[("prefill", s)](
                eng.exec_params, kv, toks, np.int32(len(prompt)),
                tables[i])
            rows.append(np.asarray(logits)[0])
            lengths[i] = len(prompt)
        outs = [np.stack(rows)]
        for t in range(steps):
            _, logits, kv = eng.compiled[("decode", 2)](
                eng.exec_params, kv, feed[t], tables, lengths,
                np.ones((2,), bool))
            outs.append(np.asarray(logits))
            lengths = lengths + 1
        eng._kv = kv
        return np.stack(outs)           # [1 + steps, 2, vocab]

    ref, got = logits_of(gather), logits_of(paged)
    c.check("parity: logits finite, shape [1+8, 2, vocab]",
            np.isfinite(got).all() and got.shape == (1 + steps, 2, vocab),
            got.shape)
    span = float(ref.max() - ref.min())
    err = float(np.abs(got - ref).max())
    c.check("parity: prefill + 8 paged decode steps == gather, logits",
            err <= PAGED_VS_GATHER_LOGIT_FRAC * span,
            f"max |delta| {err:.3e} vs logit range {span:.3f} (bound "
            f"{PAGED_VS_GATHER_LOGIT_FRAC} x range; default matmul "
            f"precision in both arms)")
    c.note("parity: argmax agreement (not asserted: random weights)",
           float((got.argmax(-1) == ref.argmax(-1)).mean()))
    cap = paged.cap
    del gather, paged

    # --- compile only, no traffic: the paged decode bucket under the
    # int8 pool and under 4 pages per kernel block
    for name, extra in (("int8_kv", ["--quant=int8_kv"]),
                        ("block_pages=4", ["--decode_block_pages=4"])):
        t0 = time.monotonic()
        eng = ServeEngine(flags.parse_flags(
            SERVE + R25 + extra + [f"--serve_buckets={cap}"],
            workload="serve"), quiet)
        calls = len(custom_call_lines(
            eng.compiled[("decode", cap)].as_text()))
        c.check(f"paged decode@{cap} {name}: compiles through Mosaic",
                calls == PAGED_CALLS,
                f"{calls} custom calls, ladder in "
                f"{time.monotonic() - t0:.0f}s")
        del eng
    c.note("peak HBM MiB, device 0", round(
        (jax.local_devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use", 0) / 2**20))
    return c.finish(out, device=device)


CHILDREN = {"train": child_train, "kernels": child_kernels,
            "serve": child_serve}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"))
    ap.add_argument("--stages", default=",".join(STAGES),
                    help="comma list, for debugging one stage; the "
                         "contract is the default: all of them")
    ap.add_argument("--child", choices=STAGES, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return CHILDREN[args.child](os.path.abspath(args.out))
    return parent(args)


if __name__ == "__main__":
    raise SystemExit(main())
