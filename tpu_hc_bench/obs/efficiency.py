"""Honest efficiency accounting: measured MFU + fabric-ceiling attribution.

Two dishonesties this module removes from the headline numbers:

- **MFU from a hand-maintained FLOP table.**  ``spec.flops_per_example``
  is a curated constant (2*MACs at the canonical shape) times a 3x
  fwd+bwd multiplier — fine until the table rots or a model variant
  (seq-len override, MoE capacity, remat recompute) drifts from it.
  ``measured_step_flops`` asks XLA instead: the already-built step
  function is AOT-lowered and compiled, and ``compiled.cost_analysis()``
  returns the per-device FLOPs of the *exact program the run executes*.
  The driver reports MFU from the measured figure when available,
  labels the source, and prints both when they disagree by >10% —
  the table cross-check that keeps the registry honest.

- **Collective bandwidth judged against datasheet numbers.**  The only
  ceiling that matters is the one THIS fabric measured:
  ``python -m tpu_hc_bench.microbench.osu --op all --json sweep.json``
  saves the OSU-style sweep, and ``--fabric_ceiling=sweep.json`` lets
  the driver/``summarize`` compare the achieved gradient-allreduce bus
  bandwidth against the sweep's peak — "all_reduce at 61% of measured
  ceiling" instead of a context-free GB/s.

Achieved bandwidth derivation (documented because every term matters):
collective seconds/step = (trace collective bucket / trace total,
including idle) x the *wall-measured* mean step time — the traced
window is a few steps, so it supplies the RATIO and the untraced run
supplies the step time;
bytes/step for the gradient allreduce = the gradient tree's bytes at
the wire dtype (bf16 when ``--accum_dtype=bf16`` keeps the tree bf16
through the allreduce); busbw = algbw * 2*(n-1)/n, the same ring
convention as ``microbench.osu``, so achieved and ceiling are
comparable by construction.
"""

from __future__ import annotations

import json
import os

# trace collective-leaf substrings -> microbench.osu sweep op names
KIND_TO_SWEEP_OP = (
    ("all-reduce", "allreduce"),
    ("allreduce", "allreduce"),
    ("reduce-scatter", "reduce_scatter"),
    ("all-gather", "all_gather"),
    ("allgather", "all_gather"),
    ("all-to-all", "all_to_all"),
    ("permute", "ppermute"),
)


# ---------------------------------------------------------------------
# measured FLOPs (needs jax; driver-side only)


def _abstractify(x):
    import jax

    if hasattr(x, "shape") and hasattr(x, "dtype"):
        # carry the COMMITTED sharding where one exists (the GSPMD TP
        # arm follows input shardings — an unsharded abstract value
        # would lower a different program than the run executes).  An
        # uncommitted array (the step's PRNG key) must stay unplaced:
        # pinning it to its incidental device 0 makes the lowering
        # refuse it beside a state committed to the whole mesh
        sharding = (x.sharding if getattr(x, "committed", False)
                    else None)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
    return x


def flops_of_compiled(compiled) -> float | None:
    """The ``flops`` entry of ``compiled.cost_analysis()`` (None where
    the backend has no analysis)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops")
    if flops is None or float(flops) <= 0:
        return None
    return float(flops)


def _probe_handles(step_fn, example_args):
    """``(jitted, abstract_args)`` for the FLOPs probe, or None.

    ``step_fn`` must expose its underlying jitted callable as
    ``_jitted`` (``train.step`` builders attach it); the example args
    are abstracted to ShapeDtypeStructs so donated or already-consumed
    buffers are never touched and nothing executes."""
    import jax

    jitted = getattr(step_fn, "_jitted", None)
    if jitted is None:
        return None
    try:
        return jitted, jax.tree.map(_abstractify, example_args)
    except Exception:
        return None


def aot_compile(jitted, *example_args):
    """AOT lower+compile a jitted callable at abstracted argument shapes.

    The ONE lowering path shared by the FLOPs/memory probe and the
    serving engine's bucket warmup (``tpu_hc_bench.serve.engine``): the
    example args are abstracted to ShapeDtypeStructs (committed
    shardings carried, donated/consumed buffers never touched, nothing
    executes), then ``jitted.lower(...).compile()`` produces the
    executable.  Because the result is an AOT ``Compiled`` handle, a
    call at any OTHER shape raises instead of silently recompiling —
    the property the serving lane's zero-recompile-after-warmup
    contract is built on.  Raises on lowering failure (callers that
    want the probe's None-degradation use ``_lowered_compiled``).
    """
    import jax

    abstract = jax.tree.map(_abstractify, example_args)
    return jitted.lower(*abstract).compile()


def _lowered_compiled(jitted, abstract):
    """The probe's AOT compile.  On the CPU test mesh a failed probe
    degrades to None (MFU falls back to the analytic table); on a TPU
    backend the probe compiles the very program the run executes, so a
    failure there is real and raises instead of silently relabeling
    the MFU ``analytic``."""
    import jax

    try:
        return jitted.lower(*abstract).compile()
    except Exception:
        if jax.default_backend() == "tpu":
            raise
        return None


def _lowered_flops(jitted, abstract) -> float | None:
    compiled = _lowered_compiled(jitted, abstract)
    if compiled is None:
        return None
    return flops_of_compiled(compiled)


def measured_step_flops(step_fn, *example_args) -> float | None:
    """Per-device per-step FLOPs of the compiled step, or None.

    Cost: one extra (cached where the stack supports it) compile —
    which is why the driver only probes on observability-enabled runs
    (and there through the background ``StepFlopsProbe``).
    """
    handles = _probe_handles(step_fn, example_args)
    if handles is None:
        return None
    return _lowered_flops(*handles)


class StepFlopsProbe:
    """``measured_step_flops`` (+ the AOT memory analysis) on a
    background thread.

    The probe's AOT lower+compile is pure telemetry — nothing the step
    loop depends on — so billing it to the ledger's compile phase was
    pure latency (round 10).  The example args are abstracted to
    ShapeDtypeStructs on the CALLING thread (so no device buffer
    outlives the handoff and donated args are never touched), then the
    lower+compile+cost_analysis runs on a daemon thread, overlapped
    with the timed loop; ``result()`` joins and returns the per-device
    FLOPs (None where the probe degraded — same contract as the
    synchronous probe; a compile failure on a TPU backend is re-raised
    from the join instead, see ``_lowered_compiled``).

    The SAME compiled handle also answers ``memory_analysis()`` —
    the argument/output/temp bytes of the step program (round 15,
    ``obs.memory``): one compile serves both probes.

    ``background=False`` runs the compile on the calling thread
    instead: ``--hbm_budget`` needs the memory report BEFORE the
    warmup pays for the full run's compile, and a budget check that
    joins after the timed loop would defeat its purpose.
    """

    def __init__(self, step_fn, *example_args, background: bool = True):
        self._flops: float | None = None
        self._memory: dict | None = None
        self._error: Exception | None = None
        self._thread = None
        handles = _probe_handles(step_fn, example_args)
        if handles is None:
            return

        def _run():
            from tpu_hc_bench.obs import memory as memory_mod

            try:
                compiled = _lowered_compiled(*handles)
            except Exception as e:      # surfaced by _join, on the caller
                self._error = e
                return
            if compiled is None:
                return
            self._flops = flops_of_compiled(compiled)
            self._memory = memory_mod.memory_analysis_of_compiled(compiled)

        if not background:
            _run()
            return
        import threading

        self._thread = threading.Thread(
            target=_run, name="tpu-hc-bench-flops-probe", daemon=True)
        self._thread.start()

    def _join(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            error, self._error = self._error, None
            raise error

    def result(self) -> float | None:
        self._join()
        return self._flops

    def memory_analysis(self) -> dict | None:
        """The step program's AOT byte accounting (obs.memory record
        shape), or None where the backend has no analysis."""
        self._join()
        return self._memory


def grad_allreduce_bytes(params, accum_dtype: str = "f32") -> int:
    """Per-device message bytes of the gradient allreduce: the gradient
    tree matches the param tree leaf-for-leaf; ``--accum_dtype=bf16``
    keeps the tree bf16 through the allreduce (train.step), halving the
    wire bytes."""
    import jax

    total = 0
    for leaf in jax.tree.leaves(params):
        if not hasattr(leaf, "size"):
            continue
        itemsize = 2 if accum_dtype == "bf16" else getattr(
            leaf.dtype, "itemsize", 4)
        total += int(leaf.size) * itemsize
    return total


# ---------------------------------------------------------------------
# MFU bookkeeping (pure)


def mfu_report(measured_flops_per_step: float | None,
               analytic_flops_per_step: float,
               mean_step_s: float, peak_flops: float) -> dict:
    """The honest MFU record: value, source label, both FLOP figures,
    and the disagreement flag (>10% — the table-rot tripwire)."""
    denom = mean_step_s * peak_flops
    mfu_analytic = analytic_flops_per_step / denom if denom > 0 else 0.0
    out = {
        "mfu": mfu_analytic,
        "mfu_source": "analytic",
        "mfu_analytic": mfu_analytic,
        "analytic_flops_per_step": analytic_flops_per_step,
    }
    if measured_flops_per_step is not None and denom > 0:
        mfu_measured = measured_flops_per_step / denom
        out.update(mfu=mfu_measured, mfu_source="measured",
                   mfu_measured=mfu_measured,
                   measured_flops_per_step=measured_flops_per_step)
        if analytic_flops_per_step > 0:
            rel = abs(measured_flops_per_step - analytic_flops_per_step) \
                / analytic_flops_per_step
            out["flops_disagreement"] = rel
            out["flops_disagree"] = rel > 0.10
    return out


def mfu_lines(summary: dict) -> list[str]:
    """Render the MFU-source attribution from a summary record (shared
    by the driver's final print and ``obs summarize``)."""
    src = summary.get("mfu_source")
    if not src:
        return []
    lines = [f"  MFU {100 * summary.get('mfu', 0.0):.1f}% "
             f"(flops source: {src})"]
    if summary.get("flops_disagree"):
        lines.append(
            f"  WARNING: measured vs analytic FLOPs disagree "
            f"{summary.get('flops_disagreement', 0.0):.0%}: measured "
            f"{summary.get('measured_flops_per_step', 0.0):.3g} vs "
            f"analytic {summary.get('analytic_flops_per_step', 0.0):.3g} "
            f"flops/step — spec.flops_per_example may have rotted")
    return lines


# ---------------------------------------------------------------------
# fabric ceiling (pure file ops; the sweep json is written by
# `python -m tpu_hc_bench.microbench.osu --json`)


def load_fabric_ceiling(path: str) -> dict:
    """Load an osu sweep export; returns ``{"world_size", "device_kind",
    "ceilings": {op: {"busbw_gbps", "message_bytes"}}}`` where each
    op's ceiling is its best measured busbw over the swept sizes."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"--fabric_ceiling: no such file: {path}")
    with open(path) as f:
        data = json.load(f)
    sweeps = data.get("sweeps")
    if not isinstance(sweeps, dict) or not sweeps:
        raise ValueError(
            f"--fabric_ceiling: {path} is not an osu sweep export "
            f"(write one with `python -m tpu_hc_bench.microbench.osu "
            f"--op all --json {path}`)")
    ceilings = {}
    for op, rows in sweeps.items():
        best = max(rows, key=lambda r: r.get("busbw_gbps", 0.0),
                   default=None)
        if best:
            ceilings[op] = {"busbw_gbps": float(best["busbw_gbps"]),
                            "message_bytes": int(best["message_bytes"])}
    return {"world_size": data.get("world_size"),
            "device_kind": data.get("device_kind"),
            "ceilings": ceilings}


def _merge_intervals(
    intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted disjoint union of [start, end) intervals."""
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _intersection_len(a: list[tuple[float, float]],
                      b: list[tuple[float, float]]) -> float:
    """Total overlap length of two sorted disjoint interval lists."""
    total = 0.0
    i = j = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def collective_overlap(
        intervals: list[tuple[str, float, float]]) -> dict | None:
    """Overlapped-vs-exposed collective attribution from trace intervals.

    ``intervals`` is ``obs.trace.leaf_intervals``'s output.  *Exposed*
    collective wall is the part of the collective-busy span no
    compute/host-transfer op covers concurrently (a sibling track's DMA
    or MXU work hides a collective; a collective running alone is pure
    step-time cost).  This is the measurement behind
    ``--overlap_grad_comm``: the flag's win is exposed fraction going
    DOWN while total collective time stays ~flat.  Same ratio-only
    trust contract as every trace consumer (obs.trace docstring).
    Returns None when the trace has no collective ops.
    """
    from tpu_hc_bench.obs import trace as trace_mod

    coll: list[tuple[float, float]] = []
    comp: list[tuple[float, float]] = []
    for name, s, e in intervals:
        if e <= s:
            continue
        if trace_mod.bucket_of(name) == "collective":
            coll.append((s, e))
        else:
            comp.append((s, e))
    if not coll:
        return None
    coll_u = _merge_intervals(coll)
    comp_u = _merge_intervals(comp)
    total = sum(e - s for s, e in coll_u)
    covered = _intersection_len(coll_u, comp_u)
    exposed = max(0.0, total - covered)
    frac = exposed / total if total > 0 else 0.0
    return {
        "collective_us": total,
        "exposed_us": exposed,
        "exposed_frac": frac,
        "overlapped_frac": 1.0 - frac,
    }


def overlap_lines(rec: dict) -> list[str]:
    """Render a ``collective_overlap`` record (driver + summarize)."""
    return [
        f"  collective exposure: {rec.get('exposed_frac', 0.0):.1%} of "
        f"collective wall exposed, {rec.get('overlapped_frac', 0.0):.1%} "
        f"overlapped with compute"
    ]


def collective_busbw_lines(summary: dict,
                           trace_rec: dict | None) -> list[str]:
    """Absolute achieved gradient-collective bus bandwidth (GB/s).

    The ceiling-free companion of ``ceiling_utilization_lines``: the
    same trace-ratio x wall-step-time x wire-bytes derivation, printed
    in absolute GB/s so a run WITHOUT a ``--fabric_ceiling`` sweep still
    reports what the fabric achieved instead of gating the number on an
    artifact the operator may not have.  The zero1 arm's reduce-scatter
    + all-gather pair is folded into the same figure (together they move
    the allreduce's ring volume over the same gradient bytes).
    """
    if not trace_rec or not trace_rec.get("buckets"):
        return []
    buckets = trace_rec["buckets"]
    total_us = sum(buckets.values())
    if total_us <= 0 or buckets.get("collective", 0.0) <= 0:
        return []
    mean_step_s = summary.get("mean_step_ms", 0.0) / 1e3
    world = int(summary.get("total_workers") or 0)
    bytes_per_step = summary.get("allreduce_bytes_per_step")
    if mean_step_s <= 0 or world <= 1 or not bytes_per_step:
        return []
    coll_ops = trace_rec.get("collective_ops") or {
        "allreduce": buckets["collective"]}
    # every gradient-carrying kind, summed: the psum arm's all-reduce
    # buckets, the zero1 arm's reduce-scatter + all-gather pair (a zero1
    # trace ALSO has a small all-reduce — the loss pmean/BN-stat sync —
    # which must not become the denominator on its own)
    grad_us = (coll_ops.get("allreduce", 0.0)
               + coll_ops.get("reduce_scatter", 0.0)
               + coll_ops.get("all_gather", 0.0))
    if grad_us <= 0:
        return []
    frac = grad_us / total_us
    sec_per_step = frac * mean_step_s
    algbw = bytes_per_step / sec_per_step / 1e9
    busbw = algbw * 2.0 * (world - 1) / world
    return [
        f"  fabric: gradient collectives {busbw:.2f} GB/s busbw "
        f"({algbw:.2f} GB/s algbw, {frac:.1%} of step time, "
        f"{bytes_per_step / 2**20:.1f} MiB/step; absolute — pass "
        f"--fabric_ceiling for %-of-measured-ceiling)"
    ]


def collective_kind_times(op_times: dict[str, float]) -> dict[str, float]:
    """Fold leaf-op durations into sweep-op kinds (all-reduce leaves of
    any fusion spelling -> "allreduce", ...)."""
    from tpu_hc_bench.obs import trace as trace_mod

    out: dict[str, float] = {}
    for name, us in op_times.items():
        if trace_mod.classify(name) != "collective":
            continue
        n = name.lower()
        for sub, op in KIND_TO_SWEEP_OP:
            if sub in n:
                out[op] = out.get(op, 0.0) + us
                break
        else:
            out["allreduce"] = out.get("allreduce", 0.0) + us
    return out


def ceiling_utilization_lines(summary: dict, trace_rec: dict | None,
                              ceiling: dict) -> list[str]:
    """Per-collective %-of-ceiling lines from run artifacts.

    ``summary``: the metrics ``summary`` record (mean_step_ms,
    total_workers, allreduce_bytes_per_step); ``trace_rec``: the
    ``trace_buckets`` record (buckets + optional ``collective_ops``
    per-kind split).  Degrades to an explanatory line when a term is
    missing rather than silently printing nothing.
    """
    if not trace_rec or not trace_rec.get("buckets"):
        return ["  fabric ceiling: no trace buckets in this run — rerun "
                "with --trace_dir/--profile_steps to attribute "
                "collective time"]
    buckets = trace_rec["buckets"]
    total_us = sum(buckets.values())
    if total_us <= 0 or buckets.get("collective", 0.0) <= 0:
        return ["  fabric ceiling: trace shows no collective time"]
    mean_step_s = summary.get("mean_step_ms", 0.0) / 1e3
    world = int(summary.get("total_workers") or 0)
    if mean_step_s <= 0 or world <= 1:
        return ["  fabric ceiling: needs a timed multi-worker summary "
                "record"]
    coll_ops = trace_rec.get("collective_ops") or {
        "allreduce": buckets["collective"]}
    bytes_per_step = summary.get("allreduce_bytes_per_step")
    cworld = ceiling.get("world_size")
    lines = []
    if cworld and cworld != world:
        lines.append(
            f"  fabric ceiling: sweep world={cworld} != run world="
            f"{world} — %-of-ceiling is indicative only")
    for op, us in sorted(coll_ops.items(), key=lambda kv: -kv[1]):
        frac = us / total_us
        sec_per_step = frac * mean_step_s
        ceil = ceiling.get("ceilings", {}).get(op)
        if ceil is None:
            lines.append(f"  fabric: {op} {frac:.1%} of step time "
                         f"(no {op} sweep in the ceiling file)")
            continue
        if op == "allreduce" and bytes_per_step and sec_per_step > 0:
            algbw = bytes_per_step / sec_per_step / 1e9
            busbw = algbw * 2.0 * (world - 1) / world
            util = busbw / ceil["busbw_gbps"] if ceil["busbw_gbps"] else 0.0
            lines.append(
                f"  fabric: {op} {busbw:.2f} GB/s busbw = {util:.0%} of "
                f"measured ceiling {ceil['busbw_gbps']:.2f} GB/s "
                f"({frac:.1%} of step time, "
                f"{bytes_per_step / 2**20:.1f} MiB/step)")
        else:
            lines.append(
                f"  fabric: {op} {frac:.1%} of step time "
                f"(ceiling {ceil['busbw_gbps']:.2f} GB/s; no byte "
                f"accounting for this collective)")
    return lines
