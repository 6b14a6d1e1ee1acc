"""The benchmark driver: tf_cnn_benchmarks' measurement protocol on TPU.

Reproduces the reference's experiment shape exactly
(``run-tf-sing-ucx-openmpi.sh:32-35,71``): ``num_warmup_batches`` untimed
steps (covering compile — the analog of the reference's warmup absorbing
graph build + MKL priming), then ``num_batches`` timed steps, throughput
printed every ``display_every`` steps, and a final ``total images/sec``
line — the metric the operator greps from the teed log (SURVEY.md §5
observability row).  Adds what the reference lacks: per-chip throughput,
step-time stats, and MFU against the chip's peak (BASELINE.md targets).
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable

import jax
import numpy as np

from tpu_hc_bench import flags as flags_mod
from tpu_hc_bench.flags import BenchmarkConfig
from tpu_hc_bench.obs import efficiency as obs_efficiency
from tpu_hc_bench.obs import fleet as obs_fleet
from tpu_hc_bench.obs import goodput as obs_goodput
from tpu_hc_bench.obs import memory as obs_memory
from tpu_hc_bench.obs import metrics as obs_metrics
from tpu_hc_bench.obs import timeline as timeline_mod
from tpu_hc_bench.models import create_model
from tpu_hc_bench.data.synthetic import SyntheticImages, SyntheticTokens
from tpu_hc_bench.parallel import fabric as fabric_mod
from tpu_hc_bench.resilience import (
    guards as guards_mod, inject as inject_mod, preempt as preempt_mod,
    watchdog as watchdog_mod,
)
from tpu_hc_bench.resilience.retry import retry_io
from tpu_hc_bench.topology import (
    DATA_AXIS, Layout, SEQ_AXIS, build_mesh, discover_layout,
    topology_record,
)
from tpu_hc_bench.train import step as step_mod
from tpu_hc_bench.utils import compile_cache, hw
from tpu_hc_bench.utils.sync import drain


@dataclasses.dataclass
class BenchmarkResult:
    model: str
    total_workers: int
    global_batch: int
    total_images_per_sec: float      # "total images/sec" (tf_cnn final line)
    images_per_sec_per_chip: float
    mean_step_ms: float
    # weighted median of per-step times at COMPLETION-MARKER granularity:
    # every step enqueues a marker and the fetch thread coalesces under
    # backlog; p50_step_granularity is the width (in steps) of the
    # interval the median came from — 1 means the reported p50 is a true
    # per-step time, N > 1 means it was measured over an N-step window
    # (the fetch thread fell behind the device) and the label admits it
    # instead of passing window medians off as per-step
    p50_step_ms: float
    p50_step_granularity: int
    mfu: float
    final_loss: float
    fabric: str
    # wall-clock goodput fraction (obs.goodput ledger): productive step
    # seconds / wall seconds; NaN where no ledger ran (eval, PP arms)
    goodput: float = float("nan")
    # the ledger's phase breakdown (phase -> wall seconds, zero phases
    # omitted): how the non-productive wall was spent — compile,
    # checkpoint blocking, data waits.  None where no ledger ran.
    goodput_phases: dict | None = None
    # fraction of wall spent blocked on the input pipeline (the
    # ledger's data_wait phase / wall seconds); NaN where no ledger ran.
    # THE input-service success metric: ~0 as workers-per-host scale
    data_wait_frac: float = float("nan")
    # which input arm actually fed the run: True = shared host service,
    # False = per-process pipeline, None = no real-image input plane.
    # --input_service=auto resolves inside the driver, so the flag
    # string alone cannot distinguish the arms in a run record
    input_service: bool | None = None
    # where the MFU's FLOP figure came from: "measured" =
    # compiled.cost_analysis() of the actual step program, "analytic" =
    # the hand-maintained spec.flops_per_example table (obs.efficiency)
    mfu_source: str = "analytic"
    # share of the score rectangle's sub-tiles the flash kernel computes
    # at this run's attention shape (ops.flash_attention.tile_plan, the
    # plan its loops are built from); None where no flash kernel runs
    flash_tile_share: float | None = None
    # resume identity when this run restored a checkpoint (None for a
    # fresh run): restored_step, saved_world -> live_world, arm, and
    # whether the elastic reshard ran — so `obs diff`/BENCH json can
    # attribute a post-resume throughput shift to the topology change
    resume: dict | None = None
    # measured device memory (obs.memory): the run's HBM high-water mark
    # (allocator peak where the backend exposes one, the live-array
    # byte-sum high water otherwise — mem_source says which), the device
    # limit, and the step program's AOT memory_analysis() byte account
    # (None on runs where the probe didn't run)
    peak_hbm_bytes: int | None = None
    hbm_bytes_limit: int | None = None
    mem_source: str | None = None
    memory_analysis: dict | None = None

    def json_line(self) -> dict:
        return dataclasses.asdict(self)


def log_name(
    num_hosts: int, batch: int, data: str, fabric: str, run: int = 1
) -> str:
    """Log naming convention, after the reference's
    ``tfmn-<n>n-<b>b-<data>-<fabric>-r<run>.log`` (run-tf-sing-ucx-openmpi.sh:9-12)."""
    return f"tpubench-{num_hosts}n-{batch}b-{data}-{fabric}-r{run}.log"


def _flash_tile_plan(cfg: BenchmarkConfig, model, spec):
    """The flash kernel's tile plan at this run's attention shape, or
    None where the attention implementation runs no flash kernel (or the
    model does not say its sequence and head widths)."""
    heads, hidden = getattr(model, "heads", 0), getattr(model, "hidden", 0)
    if (cfg.attention_impl not in ("flash", "ulysses_flash")
            or not spec.is_text or not heads):
        return None
    from tpu_hc_bench.ops.flash_attention import tile_plan

    seq = spec.input_shape[0]
    return tile_plan(seq, seq, causal=spec.causal_lm,
                     head_dim=hidden // heads)


def _example_units(cfg: BenchmarkConfig, spec) -> str:
    if (spec.is_text or getattr(spec, "ctc", False)
            or getattr(spec, "integer_input", False)):
        return "examples"
    return "images"


def _prefetch(gen, lookahead: int = 2):
    """Keep `lookahead` device batches in flight (``--prefetch_depth``).

    jax.device_put is asynchronous, so pulling the generator ahead of the
    consumer overlaps host decode + host->device DMA with the running step
    (the tf.data prefetch-to-device role in the reference's pipeline).
    """
    import collections

    q = collections.deque()
    for item in gen:
        q.append(item)
        if len(q) >= lookahead:
            yield q.popleft()
    while q:
        yield q.popleft()


def _input_service_on(cfg: BenchmarkConfig, layout) -> bool:
    """Resolve ``--input_service`` against the world shape.

    ``auto`` turns the service on exactly when >1 worker shares one
    host (the oversubscription case it exists for); ``on`` with workers
    spread over several hosts is refused loudly — per-host worker
    grouping is not derivable here, and a cross-host shm ring is
    nonsense.  flags.resolve already translated the config-level
    exclusions (synthetic input, repeat_cached_sample, eval) to off.
    """
    if cfg.input_service == "off":
        return False
    if cfg.datasets_repeat_cached_sample or cfg.eval:
        # auto never engages for these (resolve() already translated an
        # explicit on to off with a note): repeat_cached shuts the
        # pipeline down after a handful of batches, and eval reads the
        # validation split per-process
        return False
    world = jax.process_count()
    if cfg.input_service == "on":
        if world > 1 and layout.num_hosts > 1:
            raise ValueError(
                "--input_service=on requires all workers on one host "
                "(one shared-memory ring set per host); multi-host runs "
                "start one service per host via their own local launch")
        return True
    return world > 1 and layout.num_hosts == 1


def _completed(handle) -> bool:
    """Has this marker's step finished?  Device arrays know
    (``is_ready``); a host value is complete by construction."""
    is_ready = getattr(handle, "is_ready", None)
    return True if is_ready is None else is_ready()


class _ArrivalFetcher:
    """Background thread that serially fetches result handles and stamps
    their arrival wall time.

    The step-timing mechanism: the dispatch loop never syncs, so the
    device always has the next steps queued; this thread blocks on each
    step's loss scalar instead (a device-to-host fetch of 4 bytes) and
    stamps when it lands.  Every arrival is late by the same small fetch
    latency, so arrival-time *deltas* measure device progress, and the
    host sync stays off the dispatch path.

    If markers ever complete faster than the thread fetches them the
    queue would back up and the deltas would measure fetch serialization
    instead, so the thread *coalesces*: it skips over queued markers
    whose step has ALREADY completed (``is_ready``, truthful on a local
    chip), timing-fetches the newest of those and parks the rest in
    ``skipped`` (values still wanted after the run are fetched then).
    A marker whose step is still running is never skipped — the loop
    dispatches up to ``max_inflight`` steps ahead of the device, so most
    of the queue is pending work, not backlog.  On a local chip a fetch
    is far shorter than a step, so every step is resolved
    (granularity 1).  The enqueue loop uses ``fetched_step`` for flow
    control (bounding in-flight steps).

    ``keep_value``: which parked steps' VALUES matter later (the display
    steps).  With every-step markers a long run coalesces over most of
    them; holding O(num_batches) device scalars alive for the whole run
    — and bulk-fetching them at the end — for values nobody reads would
    be allocator pressure for nothing, so coalesced-over markers outside
    the predicate park as ``(step, None)``.
    """

    def __init__(self, keep_value=None):
        import queue
        import threading

        self._q: queue.Queue = queue.Queue()
        self.arrivals: list[tuple[int, float, object]] = []
        self.skipped: list[tuple[int, object]] = []   # coalesced-over markers
        self._keep_value = keep_value or (lambda i: True)
        self.fetched_step = 0
        self.last_arrival_t: float | None = None   # watchdog progress oracle
        self._last_mono: float | None = None       # device_step span anchor
        self.error: BaseException | None = None
        self._error_tb = None
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def put(self, step_idx: int, handle) -> None:
        self.check()
        self._q.put((step_idx, handle))

    def check(self) -> None:
        """Re-raise a fetch error (XlaRuntimeError, OOM…) in the caller,
        with the ORIGINAL fetch-thread traceback attached — the step loop
        fails with the real error, not a context-free re-raise."""
        if self.error is not None:
            exc = self.error
            if hasattr(exc, "add_note") and not getattr(
                    exc, "_tpu_hc_noted", False):
                exc.add_note(
                    "raised in the arrival-fetch thread; re-raised in the "
                    "step loop (tpu_hc_bench.train.driver._ArrivalFetcher)")
                exc._tpu_hc_noted = True
            raise exc.with_traceback(self._error_tb)

    def _run(self) -> None:
        import queue as queue_mod

        held = []               # at most one item, popped looking ahead
        while True:
            item = held.pop() if held else self._q.get()
            if item is None:
                return
            while True:         # coalesce markers that ALREADY completed
                try:
                    nxt = self._q.get_nowait()
                except queue_mod.Empty:
                    break
                if nxt is None or not _completed(nxt[1]):
                    # the sentinel, or a step still running: the loop is
                    # dispatched far ahead of the device, so queued does
                    # not mean done — its turn comes next
                    held.append(nxt)
                    break
                i0, h0 = item
                self.skipped.append(
                    (i0, h0 if self._keep_value(i0) else None))
                item = nxt
            i, h = item
            try:
                v = jax.device_get(h)
            except BaseException as e:   # surface in main thread, don't hang
                self.error = e
                self._error_tb = e.__traceback__
                self.fetched_step = 1 << 60   # unblock flow-control spins
                return
            self.arrivals.append((i, time.perf_counter(), v))
            self.last_arrival_t = time.perf_counter()
            # flight recorder (obs.timeline): the interval between
            # consecutive completion markers IS the device's view of the
            # step — recorded from this thread so the dispatch path pays
            # nothing
            m_now = time.monotonic()
            if self._last_mono is not None:
                timeline_mod.record_span("device_step", self._last_mono,
                                         m_now, step=i)
            self._last_mono = m_now
            self.fetched_step = i

    def finish(self) -> list[tuple[int, float, object]]:
        self._q.put(None)
        self._thread.join()
        self.check()
        return self.arrivals


class _AsyncTimeline:
    """The measurement protocol shared by the train and eval loops.

    Wraps an _ArrivalFetcher with the marker cadence (sync/display
    points), HBM flow control, and the post-run reconstruction of the
    windowed timeline.  Display steps that were coalesced over inherit
    the mean rate of the enclosing timed span; the final step is always
    timed (it is the newest marker when the queue drains), so the total
    is exact.
    """

    def __init__(self, num_batches: int, display_every: int,
                 global_batch: int):
        self.num_batches = num_batches
        self.display_every = display_every
        self.global_batch = global_batch
        # only display steps' VALUES are ever read back (the loss column);
        # coalesced-over markers elsewhere may drop their handles
        self.fetcher = _ArrivalFetcher(
            keep_value=lambda i: (i % display_every == 0
                                  or i == num_batches or i == 0))
        self.sync_every = max(1, min(display_every, 16))
        # flow-control bound on in-flight steps, so real-data runs don't
        # stack an unbounded queue of host->device batch transfers in HBM
        self.max_inflight = max(32, 2 * self.sync_every)
        # populated by finish(): timed per-step intervals + their width
        self.per_step_times: list[tuple[float, int]] = []
        self.p50_granularity = 1

    def start(self, handle) -> None:
        """Stamp t=0 with an already-fetched (cheap) marker handle.

        Blocks until the marker's arrival is recorded — otherwise a fast
        first window could coalesce over it and the timeline would lose
        its origin."""
        self.fetcher.put(0, handle)
        while not self.fetcher.arrivals:
            self.fetcher.check()
            time.sleep(1e-4)

    def record(self, i: int, handle) -> None:
        """Per-iteration bookkeeping: marker puts + flow control.

        EVERY step enqueues a marker: the fetch thread coalesces
        whenever it falls behind, so per-step completion times are
        recorded exactly as finely as they were observed — on a local
        device that is every single step (true per-step p50); should the
        arrivals ever thin out to multi-step intervals,
        ``p50_granularity`` reports the width honestly.
        """
        self.fetcher.put(i, handle)
        while i - self.fetcher.fetched_step > self.max_inflight:
            time.sleep(2e-3)
        self.fetcher.check()

    def finish(self, line_fn) -> float:
        """Drain; call ``line_fn(step, rate, value)`` per display step in
        order; return the total timed-span seconds.

        Also populates ``per_step_times`` (list of ``(dt_seconds,
        steps_spanned)`` per timed interval) and ``p50_granularity``
        (the width of the weighted-median interval; 1 = the reported
        p50 is a true per-step time) — see ``p50_step_ms``.
        """
        arrivals = self.fetcher.finish()
        values = {i: v for i, _, v in arrivals}
        # coalesced-over display markers: everything is complete now, so
        # the value fetches are cheap (non-display parks carry no handle)
        kept = [(i, h) for i, h in self.fetcher.skipped if h is not None]
        if kept:
            got = jax.device_get([h for _, h in kept])
            values.update({i: v for (i, _), v in zip(kept, got)})
        timed = {i: t for i, t, _ in arrivals}
        t0 = arrivals[0][1]
        total_time = arrivals[-1][1] - t0
        pts = sorted(timed.items())
        self.per_step_times = [
            (max((t1 - t0_) / (i1 - i0), 1e-9), i1 - i0)
            for (i0, t0_), (i1, t1) in zip(pts, pts[1:])
        ]
        # granularity = the width of the interval the reported median
        # comes from (NOT the max width: one transient coalesce in an
        # otherwise per-step run must not relabel the whole measurement)
        med = self._median_interval()
        self.p50_granularity = med[1] if med else 1
        prev_i, prev_t = 0, t0
        pending: list[int] = []
        for i in range(1, self.num_batches + 1):
            if not (i % self.display_every == 0 or i == self.num_batches):
                continue
            pending.append(i)
            if i in timed:
                dt = max((timed[i] - prev_t) / (i - prev_i), 1e-9)
                for j in pending:
                    line_fn(j, self.global_batch / dt, values.get(j))
                prev_i, prev_t = i, timed[i]
                pending = []
        return total_time

    def _median_interval(self) -> tuple[float, int] | None:
        """The weighted-median ``(dt_seconds, width)`` interval — each
        interval's per-step time weighted by the steps it spans, so a
        coalesced-over stretch counts as many steps, not one sample.
        The ONE home of the median rule: the reported p50 value and its
        granularity label both come from this pair."""
        samples = sorted(self.per_step_times)
        total = sum(w for _, w in samples)
        acc = 0
        for dt, w in samples:
            acc += w
            if 2 * acc >= total:
                return dt, w
        return None

    def step_sketch(self):
        """The timed intervals as a weighted quantile sketch (ms per
        step, weighted by steps spanned) — mergeable across ranks and
        the round-24 home of the reported p50.  None before finish()."""
        from tpu_hc_bench.obs import sketch as sketch_mod

        if not self.per_step_times:
            return None
        sk = sketch_mod.QuantileSketch()
        for dt, w in self.per_step_times:
            sk.add(1e3 * dt, w)
        return sk

    def p50_step_ms(self) -> float:
        sk = self.step_sketch()
        return sk.quantile(50) if sk is not None else float("nan")


class _TraceWindow:
    """Flag-driven windowed ``jax.profiler`` tracing with ONE stop path.

    ``--profile_steps=a:b`` selects the timed steps to profile into
    ``--trace_dir``; without it, ``--trace_dir`` keeps its legacy
    first-sync-window behavior (expressed as the window
    ``1:sync_every``).  The window is observed through the timeline's
    completion markers: the trace starts once every step before ``a``
    has *completed* (so the window isn't polluted by the in-flight tail
    of earlier steps) and stops once step ``b`` has completed.

    ``stop()`` is idempotent and is the only place the profiler is ever
    stopped — previously the timed loop's early exit and the post-loop
    cleanup each called ``jax.profiler.stop_trace`` behind their own
    flag, and a run ending inside the profiled window could stop twice.
    """

    def __init__(self, cfg: BenchmarkConfig, print_fn, sync_every: int):
        self.trace_dir = cfg.trace_dir
        self.print_fn = print_fn
        self.active = False
        self.started = False
        if cfg.profile_steps:
            self.start_step, self.stop_after = flags_mod.parse_profile_steps(
                cfg.profile_steps)
        else:
            self.start_step, self.stop_after = 1, sync_every

    def maybe_start(self, next_step: int, fetcher: _ArrivalFetcher) -> None:
        """Start the trace when the loop is about to dispatch
        ``next_step == a``; for a > 1, first wait for step a-1's
        completion marker so the window starts quiesced."""
        if (self.trace_dir is None or self.started
                or next_step < self.start_step):
            return
        if self.start_step > 1:
            while fetcher.fetched_step < self.start_step - 1:
                fetcher.check()
                time.sleep(1e-3)
        jax.profiler.start_trace(self.trace_dir)
        self.active = True
        self.started = True

    def poll(self, fetched_step: int) -> None:
        if self.active and fetched_step >= self.stop_after:
            self.stop()

    def stop(self) -> None:
        if not self.active:
            return
        jax.profiler.stop_trace()
        self.active = False
        self.print_fn(f"profiler trace written to {self.trace_dir}")

    def post_summary(self):
        """Print the bucket attribution of the trace just written
        (through the shared ``obs.trace`` formatter) and return the
        ``TraceSummary``, or None when no usable trace exists (e.g. a
        CPU run: the profiler writes host tracks only)."""
        if self.trace_dir is not None and not self.started:
            # the user asked for a trace and never got one — say so
            # instead of silently writing nothing (a --profile_steps
            # window starting past the run's end)
            self.print_fn(
                f"WARNING: profile window {self.start_step}:"
                f"{self.stop_after} never started (run ended first); "
                f"no trace written to {self.trace_dir}")
        if not self.started:
            return None
        from tpu_hc_bench.obs import trace as obs_trace

        try:
            summary = obs_trace.summarize_trace_dir(self.trace_dir)
        except Exception as e:
            if jax.default_backend() == "tpu":
                # a trace was asked for on the chip and cannot be
                # reduced: that is the run's failure, not a footnote
                raise
            # CPU runs write host tracks only; nothing to attribute
            self.print_fn(f"trace summary unavailable: {e}")
            return None
        for line in obs_trace.format_summary(summary):
            self.print_fn(line)
        return summary


def _fingerprint_line(params, print_fn) -> None:
    """Best-effort params digest: emergency save and resume restore both
    print it, so kill/resume tests assert bitwise identity from the log.
    Silent when the state is not fully addressable (multi-host sharded)."""
    from tpu_hc_bench.utils import checkpoint as ckpt

    try:
        print_fn(f"params fingerprint: {ckpt.fingerprint(params)}")
    except Exception:
        pass


def _maybe_restore(state, cfg, print_fn, sharded=False, topo=None,
                   obs_writer=None):
    """--train_dir resume: restore the latest COMPLETE checkpoint, per
    the ``--resume`` policy (auto = restore if one exists, never = fresh
    init, must/elastic = error when none — a crash-looping relaunch must
    not silently restart from step 0).

    Returns ``(state, restored?, resume_record)``.  Default mode
    restores host arrays (the caller re-places them on the mesh);
    ``sharded=True`` takes an already-PLACED template and restores each
    array with its committed sharding, every process reading only its
    addressable shards (the multi-host model-sharded path).

    ``topo``: the LIVE topology record.  A checkpoint whose sidecar
    disagrees is validated through ``topology.elastic_plan`` — a loud
    :class:`utils.checkpoint.TopologyMismatchError` replaces the old
    opaque Orbax sharding error, ``--resume=elastic`` routes zero1
    states through the resplit path, and a one-line plan of what is
    being reshaped is printed.  The resume record (restored step, saved
    vs live world, arm) is also emitted into the metrics stream.
    """
    if not cfg.train_dir or cfg.resume == "never":
        return state, False, None
    from pathlib import Path

    from tpu_hc_bench.utils import checkpoint as ckpt

    if ckpt.latest_step(cfg.train_dir) is None:
        orphans = [p.name for p in Path(cfg.train_dir).glob("step_*")
                   if p.is_dir() and not p.name.endswith(".tmp")]
        if orphans:
            # crashed saves — or checkpoints from before the commit-
            # sentinel scheme.  Never restore them silently, but never
            # silently restart from step 0 over them either.
            print_fn(
                f"WARNING: {cfg.train_dir} has step dir(s) without a "
                f"commit sentinel ({', '.join(sorted(orphans)[:4])}"
                f"{'...' if len(orphans) > 4 else ''}): crashed saves, "
                f"or pre-sentinel checkpoints — verify and `touch "
                f"<dir>/step_NNNNNNNN.complete` to adopt; starting "
                f"fresh")
        if cfg.resume in ("must", "elastic"):
            raise FileNotFoundError(
                f"--resume={cfg.resume}: no complete checkpoint under "
                f"{cfg.train_dir}")
        return state, False, None
    saved_topo = ckpt.read_topology(cfg.train_dir)
    action, plan = "ok", ""
    if topo is not None and saved_topo is not None:
        # one loud line + error instead of an opaque Orbax shape error:
        # check_topology raises unless the transition is a no-op or an
        # elastic reshard the operator asked for
        action, plan = ckpt.check_topology(
            saved_topo, topo, cfg.train_dir,
            elastic=cfg.resume == "elastic")
        if plan:
            print_fn(f"elastic resume: {plan}")
    elif cfg.resume == "elastic" and saved_topo is None:
        print_fn("elastic resume: checkpoint has no topology sidecar "
                 "(pre-elastic save); assuming the saved topology "
                 "matches the live one")
    if action == "reshard" and not sharded:
        state = ckpt.restore_elastic(state, cfg.train_dir, saved_topo,
                                     topo["world"])
    else:
        state = ckpt.restore(state, cfg.train_dir, sharded=sharded)
    restored_step = int(jax.device_get(state.step))
    print_fn(f"restored checkpoint step {restored_step} from "
             f"{cfg.train_dir}")
    if not sharded:
        _fingerprint_line(state.params, print_fn)
    rec = None
    if saved_topo is not None or topo is not None:
        rec = {"restored_step": restored_step,
               "saved_world": (saved_topo or {}).get("world"),
               "live_world": (topo or {}).get("world"),
               "arm": (saved_topo or {}).get("variable_update"),
               "elastic": action == "reshard"}
        if obs_writer is not None:
            obs_writer.event("resume", **rec, saved_topology=saved_topo,
                             live_topology=topo, plan=plan or None)
    return state, True, rec


def _save_state(state, cfg, print_fn, pp_ctx=None, sharded=False,
                topology=None):
    """Save to --train_dir.  ``state`` is a TrainState, or the PP
    ``(params, opt_state)`` tuple when ``pp_ctx=(model, template)`` — the
    DP<->DPxPP checkpoint interchange: PP runs restack into the DP layout
    so the checkpoint restores under either strategy.  ``topology`` is
    the run's sidecar record (``topology.topology_record``), committed
    next to the step sentinel for elastic resume.

    Multi-process: ALL processes call (Orbax synchronizes internally and
    the primary host writes the replicated arrays); the driver guard has
    already ensured the state is replicated, not model-sharded."""
    if not cfg.train_dir:
        return
    from tpu_hc_bench.utils import checkpoint as ckpt

    if pp_ctx is not None:
        from tpu_hc_bench.parallel import pipeline as pipe_mod

        model, template, steps_done = pp_ctx
        params, opt_state = state
        state = pipe_mod.train_state_from_pp(
            params, opt_state, template, model.num_layers)
        state = state.replace(
            step=jax.numpy.asarray(steps_done, jax.numpy.int32))
    path = ckpt.save(state, cfg.train_dir, sharded=sharded,
                     topology=topology)
    print_fn(f"checkpoint saved: {path}")


_RANDOM_INIT_EVAL_WARNING = (
    "WARNING: --eval without --train_dir measures RANDOMLY INITIALIZED "
    "params — the accuracy line is meaningless; train with --train_dir "
    "first and pass it here")


def _require_checkpoint_for_eval(cfg, restored: bool, print_fn) -> None:
    """The one home of the eval-restore policy (all eval arms): a named
    --train_dir with no checkpoint is an error; no --train_dir at all
    warns that random init is being measured."""
    if restored:
        return
    if cfg.train_dir:
        raise FileNotFoundError(
            f"--eval: no checkpoint found under {cfg.train_dir}")
    print_fn(_RANDOM_INIT_EVAL_WARNING)


def _run_eval(cfg, spec, layout, mesh, state, batch_iter, global_batch,
              fab, print_fn, follow_inputs=False, eval_step=None,
              sp=False, dcn=False, tp=False, obs_writer=None):
    """tf_cnn_benchmarks --eval: timed forward passes + top-1 accuracy.

    ``follow_inputs=True``: TP/EP eval — the state enters model-sharded
    and the GSPMD eval step follows its committed shardings.
    ``sp=True``: the (data, seq) shard_map eval arm (``tp=True`` for the
    DP x SP x TP hybrid's partial-manual variant).
    ``dcn=True``: the multislice (dcn, data) eval arm.
    ``eval_step``: pre-built override (the PP eval step) with the same
    ``(state, batch) -> (loss, correct)`` contract."""
    from tpu_hc_bench.train import step as step_mod

    if eval_step is None:
        eval_step = step_mod.build_eval_step(mesh, cfg, spec,
                                             follow_inputs=follow_inputs,
                                             sp=sp, dcn=dcn, tp=tp)
    units = _example_units(cfg, spec)
    for _ in range(max(1, min(cfg.num_warmup_batches, 5))):
        loss, correct = eval_step(state, next(batch_iter))
    drain(loss)

    # async dispatch with the shared timing protocol (_AsyncTimeline);
    # per-step correct counts are fetched in one transfer at the end
    corrects = []
    timeline = _AsyncTimeline(cfg.num_batches, cfg.display_every,
                              global_batch)
    timeline.start(loss)        # drained above: arrival stamps t=0
    for i in range(1, cfg.num_batches + 1):
        with timeline_mod.span("input_wait", step=i):
            batch = next(batch_iter)
        with timeline_mod.span("eval_dispatch", step=i):
            loss, correct = eval_step(state, batch)
        corrects.append(correct)
        timeline.record(i, loss)
    display_recs: list[tuple[int, float, object]] = []
    total_time = timeline.finish(
        lambda i, rate, v: display_recs.append((i, rate, v)))
    obs_writer = obs_writer or obs_metrics.MetricsWriter(None)
    correct_np = np.asarray(jax.device_get(corrects))
    loss_vals = []
    for i, rate, v in display_recs:
        top1 = float(correct_np[:i].sum()) / (i * global_batch)
        loss_vals.append(float(np.asarray(v)))
        print_fn(f"{i}\ttop_1: {top1:.4f}\tloss: {loss_vals[-1]:.3f}")
        obs_writer.event("window", step=i, rate=rate,
                         step_ms=1e3 * global_batch / rate, top_1=top1,
                         loss=loss_vals[-1])
    correct_total = float(correct_np.sum())
    seen = cfg.num_batches * global_batch
    total_rate = cfg.num_batches * global_batch / total_time
    per_chip = total_rate / layout.total_workers
    peak = hw.peak_flops(dtype=cfg.compute_dtype)
    result = BenchmarkResult(
        model=cfg.model,
        total_workers=layout.total_workers,
        global_batch=global_batch,
        total_images_per_sec=total_rate,
        images_per_sec_per_chip=per_chip,
        mean_step_ms=1e3 * total_time / cfg.num_batches,
        p50_step_ms=timeline.p50_step_ms(),
        p50_step_granularity=timeline.p50_granularity,
        mfu=(spec.flops_per_example * per_chip) / peak,
        final_loss=float(loss_vals[-1]),
        fabric=fab.value,
    )
    print_fn("-" * 40)
    print_fn(f"eval top_1 accuracy: {correct_total / seen:.4f}")
    print_fn(f"total {units}/sec: {total_rate:.2f}")
    # one end-of-run memory sample (cheap, post-timing): the forward
    # pass's high water, capability-gated with the live-arrays fallback
    mem_ledger = obs_memory.MemoryLedger()
    obs_writer.event("memory",
                     **mem_ledger.sample("step", step=cfg.num_batches))
    result.peak_hbm_bytes = mem_ledger.peak_bytes or None
    result.hbm_bytes_limit = mem_ledger.bytes_limit
    result.mem_source = mem_ledger.source
    step_sk = timeline.step_sketch()
    if step_sk is not None:
        obs_writer.event("latency_sketch", window=0,
                         fields={"step_ms": step_sk.to_record()})
    obs_writer.event("summary", eval_top_1=correct_total / seen,
                     **result.json_line())
    obs_writer.close()
    timeline_mod.detach()
    return result


def run_benchmark(
    cfg: BenchmarkConfig,
    layout: Layout | None = None,
    fabric_name: str = "ici",
    print_fn: Callable[[str], None] = print,
    model_dtype=None,
) -> BenchmarkResult:
    """Run the full benchmark protocol; returns the measured result."""
    import jax.numpy as jnp

    hw.require_accelerator(cfg.virtual_devices)
    fab = fabric_mod.resolve_fabric(fabric_name)
    # load the fabric-ceiling sweep NOW, loudly: a typo'd path must die
    # before warmup, not after the full run when the summary needs it
    fabric_ceiling = (obs_efficiency.load_fabric_ceiling(cfg.fabric_ceiling)
                      if cfg.fabric_ceiling else None)
    # --hbm_budget: parse loudly now; "auto" resolves to the device's
    # measured bytes_limit right before the pre-warmup AOT check
    hbm_budget = obs_memory.parse_hbm_budget(cfg.hbm_budget)
    # persistent compile cache (utils.compile_cache): activated before
    # anything lowers, so the warmup's compiles hit (warm start) or
    # populate (cold start) it; hit/miss is measured over the warmup
    # and recorded in the manifest
    compile_cache_dir = compile_cache.resolve(cfg.compile_cache)
    cache_entries_before = (compile_cache.entry_count(compile_cache_dir)
                            if compile_cache_dir else 0)
    layout = layout or discover_layout()
    # TP/EP claim the mesh's "model" axis, PP "pipe", SP "seq".  Round 2:
    # minor axes COMPOSE — DPxPPxTP and DPxSPxTP are the supported 3-D
    # hybrids (PP/SP manual shard_map axes, model auto/GSPMD); the other
    # pairings are rejected explicitly.
    pp = max(1, getattr(cfg, "pipeline_parallel", 1))
    sp = max(1, getattr(cfg, "sequence_parallel", 1))
    # degenerate SP (round 3): a seq-sharded attention impl at
    # sequence_parallel=1 runs on a size-1 seq axis (world-1 collectives)
    sp_active = sp > 1 or cfg.attention_impl in (
        "ring", "ulysses", "ulysses_flash")
    tp = max(1, cfg.model_parallel)
    ep = max(1, getattr(cfg, "expert_parallel", 1))
    if tp > 1 and ep > 1:
        raise ValueError(
            "--model_parallel and --expert_parallel share the mesh's "
            "model axis; pick one")
    if getattr(cfg, "scan_layers", False) and (pp > 1 or tp > 1 or ep > 1):
        raise ValueError(
            "--scan_layers stacks the trunk params [L, ...] (one compiled "
            "layer body), which the layer_i-based PP interface and the "
            "per-tensor TP/EP sharding rules do not address yet; drop "
            "--scan_layers or the model/pipe axes")
    if pp > 1 and sp > 1:
        raise ValueError(
            "--pipeline_parallel x --sequence_parallel is not a supported "
            "composition (supported hybrids: DPxPPxTP, DPxSPxTP)")
    if ep > 1 and (pp > 1 or sp > 1):
        raise ValueError(
            "--expert_parallel composes with data parallelism only")
    mp = max(tp, ep) * pp * sp      # minor product = DP-degree divisor
    sharded_ckpt = False
    pp_native_ckpt = False
    if cfg.train_dir and jax.process_count() > 1:
        # Plain-DP/SP state is REPLICATED (every host holds full copies:
        # process 0's device_get-and-save works, every process restores
        # from the shared filesystem).  TP/EP states — including the
        # DP x SP x TP hybrid's — are model-SHARDED: they save/restore
        # through Orbax's per-shard jax.Array I/O with every process
        # participating (utils.checkpoint sharded=True, restore AFTER
        # placement).  Multi-host PP (round 4) saves the PP-NATIVE
        # stacked layout (utils.checkpoint.save_pp): the DP-layout
        # interchange needs full addressability, which a pipe-sharded
        # trunk is not, so the checkpoint keeps the [L, ...] layout and
        # every process writes its shards.
        if pp > 1:
            pp_native_ckpt = True
            print_fn(
                "--train_dir multi-process PP: PP-native sharded Orbax "
                "(stacked [L,...] trunk; not interchangeable with "
                "DP-layout checkpoints); restore requires a filesystem "
                "shared by all hosts")
        else:
            # zero1's optimizer state is sharded over the data axis: at
            # world > 1 the shards span hosts and the host-gather save
            # path cannot address them — the sharded Orbax path (restore
            # AFTER placement) handles it like the TP/EP states
            sharded_ckpt = (max(tp, ep) > 1
                            or cfg.variable_update == "zero1")
            print_fn(
                "--train_dir multi-process: "
                + ("sharded Orbax I/O, every process writes its shards"
                   if sharded_ckpt else "process 0 writes")
                + "; restore requires a filesystem shared by all hosts")
    if layout.total_workers % mp:
        raise ValueError(
            f"--model_parallel/--expert_parallel/--pipeline_parallel/"
            f"--sequence_parallel product {mp} does not divide "
            f"{layout.total_workers} workers"
        )
    if (mp > 1 or sp_active) and fab is fabric_mod.Fabric.HOST:
        raise ValueError(
            "--model_parallel/--expert_parallel/--pipeline_parallel/"
            "--sequence_parallel (incl. the degenerate seq axis of the "
            "seq-sharded attention impls) requires a device fabric "
            "(ici/dcn): the host path's shard_map binds no seq axis and "
            "would silently re-replicate the shards"
        )
    if (cfg.on_nonfinite in ("skip", "rewind")
            and fab is fabric_mod.Fabric.HOST):
        # flags.resolve rejects the other unsupported arms; the fabric is
        # only known here
        raise ValueError(
            "--on_nonfinite=skip/rewind needs a compiled step (fabric "
            "ici/dcn): the host-fabric numpy step carries no in-step "
            "guard")
    # fabric=dcn selects the MULTISLICE layout: slices x hosts/slice x
    # chips, a leading `dcn` mesh axis splitting the data dimension so the
    # gradient allreduce's cross-slice phase is explicit (the reference's
    # second-transport-stack role, run-tf-sing-libfabric-intelmpi.sh:86-105).
    # Default: one slice per host (hosts without shared ICI); override
    # with --num_slices for multi-host slices.
    num_slices = 1
    if fab is fabric_mod.Fabric.DCN:
        num_slices = getattr(cfg, "num_slices", 0) or layout.num_hosts
        if num_slices > 1 and mp > 1:
            raise ValueError(
                "fabric=dcn multislice currently composes with data "
                "parallelism only")
    elif getattr(cfg, "num_slices", 0) > 1:
        raise ValueError("--num_slices requires fabric=dcn")
    mesh = build_mesh(layout, model_parallel=max(tp, ep),
                      pipeline_parallel=pp, sequence_parallel=sp,
                      num_slices=num_slices, force_seq_axis=sp_active)
    # with TP/EP/PP/SP, the data-parallel degree (and so the global batch
    # at fixed per-worker batch) shrinks by the minor-axis product
    global_batch = layout.global_batch(cfg.batch_size) // mp

    # elastic-resume topology record (round 12): world/mesh/arm/layout/
    # dtype identity, written as a sidecar next to every checkpoint's
    # commit sentinel and validated at restore — the thing that lets a
    # preempted 8-way run continue on the 4 chips that survive
    topo_rec = topology_record(
        layout=layout, mesh=mesh, cfg=cfg,
        layout_kind=("pp-native" if pp_native_ckpt
                     else "sharded" if sharded_ckpt else "host"))
    resume_rec: dict | None = None

    dtype = model_dtype or jnp.dtype(cfg.compute_dtype)
    model, spec = create_model(cfg.model, num_classes=cfg.num_classes,
                               dtype=dtype, attention_impl=cfg.attention_impl,
                               space_to_depth=cfg.use_space_to_depth,
                               fused_conv=getattr(cfg, "fused_conv", False),
                               seq_len=cfg.seq_len,
                               gradient_checkpointing=cfg.gradient_checkpointing,
                               moe_impl=getattr(cfg, "moe_impl", "einsum"),
                               rnn_impl=getattr(cfg, "rnn_impl", "hoisted"),
                               scan_layers=getattr(cfg, "scan_layers", False),
                               moe_capacity_factor=getattr(
                                   cfg, "moe_capacity_factor", 1.25),
                               moe_f_chunk=getattr(cfg, "moe_f_chunk", 0),
                               seq_axis=SEQ_AXIS if sp_active else None)
    if sp_active:
        seq_len = spec.input_shape[0]
        if seq_len % sp:
            raise ValueError(
                f"sequence length {seq_len} not divisible by "
                f"sequence_parallel={sp}")

    # real-data split, resolved ONCE: both the --num_epochs sizing and
    # the dataset construction below must read the same shards (eval
    # prefers a validation split when present, else falls back to train)
    if cfg.datasets_repeat_cached_sample and (
            cfg.data_dir is None or spec.is_text):
        # the flag isolates the DEVICE-side real-IMAGE step cost; synthetic
        # input is already host-free and the token path is ~wire-free
        # (16 KB/step — BASELINE.md real-text table), so accepting the flag
        # there would print a banner claiming an isolation that never ran
        raise ValueError(
            "--datasets_repeat_cached_sample needs a real image dataset "
            "(--data_dir with TFRecord shards); it is meaningless for "
            "synthetic input and unsupported for text corpora")
    if cfg.datasets_repeat_cached_sample and (cfg.eval or cfg.num_epochs):
        # same loud-error principle: an "epoch" sized for the full dataset
        # or a "validation accuracy" computed over 8 cycled batches would
        # wear a banner describing a measurement that never happened
        raise ValueError(
            "--datasets_repeat_cached_sample is a throughput-isolation "
            "mode (a handful of batches cycled forever); it cannot define "
            "an epoch (--num_epochs) or a split-wide metric (--eval)")

    data_split = None
    if cfg.data_dir is not None and not spec.is_text:
        from tpu_hc_bench.data.imagenet import find_shards

        data_split = "train"
        if cfg.eval:
            try:
                find_shards(cfg.data_dir, "validation")
                data_split = "validation"
            except FileNotFoundError:
                pass

    if cfg.num_epochs:
        # tf_cnn_benchmarks --num_epochs: duration in dataset passes,
        # resolvable only here (needs the global batch and the ACTUAL
        # dataset — synthetic/text streams have no epoch size, so they
        # reject rather than silently assume ilsvrc2012 splits).
        # num_epochs is cleared after derivation so cfg stays
        # re-resolvable.
        import math

        if data_split is None:
            raise ValueError(
                "--num_epochs needs a real image dataset (--data_dir): "
                "synthetic and text inputs are endless streams with no "
                "epoch size; use --num_batches")
        from tpu_hc_bench.data.imagenet import count_examples

        examples = count_examples(cfg.data_dir, data_split)
        cfg.num_batches = math.ceil(
            cfg.num_epochs * examples / global_batch)
        print_fn(f"num_epochs={cfg.num_epochs} ({examples} examples) -> "
                 f"num_batches={cfg.num_batches} "
                 f"(global_batch={global_batch})")
        cfg.num_epochs = 0.0

    # --- banner (reference :52-58 config echo) ---
    for line in layout.summary_lines(fabric=fab.value):
        print_fn(line)
    for line in cfg.summary_lines():
        print_fn(line)
    fcfg = fabric_mod.FabricConfig(fab, cfg.fusion_threshold_bytes)
    print_fn(fcfg.summary())
    if num_slices > 1:
        per_slice = (f"{layout.num_hosts // num_slices} host(s)/slice"
                     if num_slices <= layout.num_hosts
                     else f"virtual slices on {layout.num_hosts} host(s)")
        print_fn(
            f"multislice: {num_slices} slices x {per_slice} — data axis = "
            f"dcn({num_slices}) x data({layout.total_workers // num_slices})")
    print_fn(f"device_kind={hw.device_kind()} global_batch={global_batch}")
    flash_plan = _flash_tile_plan(cfg, model, spec)
    if flash_plan is not None:
        print_fn(f"flash tiles: {flash_plan.fwd[0]}/{flash_plan.rect} "
                 f"computed, {flash_plan.fwd[1]} masked "
                 f"({flash_plan.sub_q}x{flash_plan.sub_k} in blocks of "
                 f"{flash_plan.block_q}x{flash_plan.block_k})")
    if compile_cache_dir:
        print_fn(f"compile cache: {compile_cache_dir} "
                 f"({cache_entries_before} entries at start)")
    for line in hw.ici_topology_lines():
        print_fn(line)

    # --- run observability (obs.metrics): manifest eagerly, so even a
    # crashed run leaves its identity behind; worker 0 writes and is the
    # only one that even BUILDS the manifest (git subprocess + version
    # probes are wasted work on the N-1 processes whose writer no-ops) —
    # records are already globally aggregated (psum'd loss, global-batch
    # rates), so its view is the merged record
    if cfg.metrics_dir and jax.process_index() == 0:
        # checkpoint topology identity rides the manifest too, so `obs
        # diff` can name a world-size change across a resume boundary
        manifest_extra: dict = {"topology": topo_rec}
        if compile_cache_dir:
            manifest_extra["compile_cache"] = {
                "dir": compile_cache_dir,
                "entries_before": cache_entries_before}
        if cfg.variable_update == "zero1":
            # manifest-noted checkpoint policy: single-process zero1
            # saves gather the sharded optimizer state to host
            # (gather-on-save); multi-process uses sharded Orbax I/O.
            # No --train_dir = no checkpoints = no policy to note.
            zrec: dict = {"opt_state_sharded": True,
                          "opt_shards": layout.total_workers}
            if cfg.train_dir:
                zrec["checkpoint"] = ("sharded" if sharded_ckpt
                                      else "gather-on-save")
            manifest_extra["zero1"] = zrec
        obs_writer = obs_metrics.MetricsWriter(
            cfg.metrics_dir,
            obs_metrics.run_manifest(
                cfg=cfg, layout=layout, mesh=mesh, fabric=fab.value,
                extra=manifest_extra or None),
            primary=True)
        print_fn(f"metrics: {cfg.metrics_dir}/{obs_metrics.METRICS_NAME} "
                 f"(+ {obs_metrics.MANIFEST_NAME}); live view: "
                 f"python -m tpu_hc_bench.obs watch {cfg.metrics_dir}")
    else:
        obs_writer = obs_metrics.MetricsWriter(None)
    # flight recorder (obs.timeline): always-on bounded span ring; with
    # --metrics_dir EVERY rank persists its spans.<k>.jsonl beside the
    # heartbeats (per-rank visibility, like FleetWriter).  Configured
    # BEFORE the phase tracker so the init transition lands in the ring.
    timeline_mod.configure(enabled=cfg.flight_recorder != "off",
                           run_dir=cfg.metrics_dir,
                           rank=jax.process_index())
    # goodput ledger (obs.goodput): phase transitions into the metrics
    # stream + a local mirror so the final account never re-reads the
    # file; enters "init" now
    phases = obs_goodput.PhaseTracker(obs_writer)

    # --- data ---
    input_svc = None        # rank-0's InputService (stats + shutdown)
    svc_client = None       # this worker's ring consumer
    if cfg.data_dir is not None and not spec.is_text:
        # real ImageNet TFRecords, per-host shard split (reference :19,80-81)
        from tpu_hc_bench.data.imagenet import ImageNetDataset

        image_size = spec.default_image_size
        # round 14: sliced input — each worker decodes and ships ONLY
        # its own rows of the global batch (the service rings carry the
        # slice, the per-process pipeline decodes just the consumed
        # rows), and jax.make_array_from_process_local_data assembles
        # the global array.  The pre-round-14 arm (every process builds
        # the FULL global batch, device_put keeps the local slice) is
        # the bitwise A/B control, kept as --full_batch_identity.
        # Delivered pixels are
        # identical either way (per-row RNG keying); only the W-fold
        # redundant host decode/copy disappears.
        in_world = jax.process_count()
        sliced_input = False
        if in_world > 1 and not cfg.full_batch_identity:
            if global_batch % in_world:
                print_fn(f"sliced input: global batch {global_batch} "
                         f"not divisible by {in_world} worker(s) — "
                         "full-batch identity fallback")
            else:
                sliced_input = True
        _rows = None
        if sliced_input:
            per_w = global_batch // in_world
            _rows = (jax.process_index() * per_w,
                     (jax.process_index() + 1) * per_w)
        if _input_service_on(cfg, layout):
            # host-level shared input service (round 13): the lowest
            # local rank owns ONE decode pool and feeds every local
            # worker's shm ring; each worker's delivered stream is
            # bitwise-identical to the per-process pipeline it replaces
            from tpu_hc_bench.data import service as service_mod

            world = jax.process_count()
            ring_depth = max(2, cfg.prefetch_depth)
            # every rank must derive the SAME name; a per-launch nonce
            # broadcast from rank 0 keeps (a) a relaunch from attaching
            # to a crashed run's stale segment before rank 0 reclaims
            # it and (b) concurrent same-config runs on one host apart.
            # Falls back to a config-only name if the collective is
            # unavailable (then the config-hash + stale-reclaim in
            # ShmRing.create is the only guard).
            nonce = os.getpid()
            if world > 1:
                try:
                    from jax.experimental import multihost_utils

                    nonce = int(multihost_utils.broadcast_one_to_all(
                        np.int64(os.getpid() * 1000
                                 + (time.monotonic_ns() // 1000) % 1000)))
                except Exception:
                    nonce = 0
            svc_name = service_mod.service_name(
                cfg.data_dir, data_split, cfg.seed, global_batch,
                image_size, cfg.wire_dtype, cfg.model,
                cfg.metrics_dir or "", cfg.train_dir or "",
                "sliced" if sliced_input else "full", nonce)
            if jax.process_index() == 0:
                input_svc = service_mod.make_image_service(
                    [cfg.data_dir], num_workers=world,
                    global_batch=global_batch, image_size=image_size,
                    split=data_split, train=not cfg.eval, seed=cfg.seed,
                    wire_dtype=cfg.wire_dtype,
                    decode_workers=cfg.service_decode_workers,
                    depth=ring_depth, name=svc_name,
                    slice_per_worker=sliced_input,
                ).start()
                print_fn(
                    f"input service: host decode pool "
                    f"{input_svc.decode_workers} thread(s) serving "
                    f"{world} worker(s) over shared-memory rings "
                    f"(depth {ring_depth}"
                    + (", sliced rings: each worker's ring carries "
                       f"only its {global_batch // world} rows"
                       if sliced_input else "") + ")")
            # copy=True: the batch feeds an ASYNC jax.device_put (which
            # on CPU may even alias the aligned buffer) while _prefetch
            # pulls ahead — a zero-copy view's slot could be recycled
            # mid-transfer, so the client takes an owned copy per batch
            svc_client = service_mod.ServiceClient(
                svc_name,
                service_mod.image_batch_layout(
                    global_batch // world if sliced_input else global_batch,
                    image_size, cfg.wire_dtype),
                worker=jax.process_index(), depth=ring_depth, copy=True,
                # a dead service host must surface as an error, not an
                # eternal data wait (10 min covers any sane decode)
                stall_timeout_s=600.0)
            ds = svc_client
            host_iter = iter(svc_client)
        else:
            # ceil-divide on ragged layouts: over-dividing the pool is
            # safe, while a fall-back to 1 would reinstate the full-
            # width-per-process oversubscription this exists to fix
            local_workers = -(-jax.process_count() // layout.num_hosts)
            ds = ImageNetDataset(
                cfg.data_dir,
                global_batch=global_batch,
                image_size=image_size,
                split=data_split,
                train=not cfg.eval,
                worker=jax.process_index(),
                num_workers=jax.process_count(),
                seed=cfg.seed,
                # uint8 ships 4x less host->device traffic; the
                # cast+normalize runs inside the compiled step
                # (train.step.prep_inputs)
                wire_dtype=cfg.wire_dtype,
                # 0 = auto-size the decode pool to this worker's SHARE
                # of the host's cores (divided by local worker count —
                # N private pools must not claim N*(cpu-1) threads)
                decode_workers=cfg.datasets_num_private_threads,
                local_workers=local_workers,
                prefetch=cfg.prefetch_depth,
                # sliced mode: decode only the rows this process's
                # devices hold; the per-row RNG still advances over all
                # rows, so the delivered pixels are bitwise-identical
                # to the full pipeline's same rows
                decode_rows=_rows,
            )
            print_fn(f"decode pool: {ds.decode_workers} thread(s)/worker "
                     f"({local_workers} local worker(s) share "
                     f"{os.cpu_count()} host CPUs; per-process pipeline"
                     + (f"; sliced: decoding rows [{_rows[0]}, {_rows[1]})"
                        if _rows is not None else "") + ")")
            host_iter = iter(ds)
            if sliced_input:
                # decode_rows yields full-shaped batches with only the
                # local rows decoded — hand downstream just the rows.
                # close() must reach the dataset iterator (the
                # repeat_cached path stops the decode pool through it)
                def _local_rows(it, lo=_rows[0], hi=_rows[1]):
                    try:
                        for b in it:
                            yield tuple(a[lo:hi] for a in b)
                    finally:
                        it.close()
                host_iter = _local_rows(host_iter)
        batch = next(host_iter)
        # sliced mode ships local rows through make_array_from_process_
        # local_data; the identity arm ships the global batch through
        # device_put (which keeps the local slice)
        place_batch = (
            (lambda b: step_mod.shard_batch_local(b, mesh)) if sliced_input
            else (lambda b: step_mod.shard_batch(b, mesh)))

        if cfg.datasets_repeat_cached_sample:
            # --datasets_repeat_cached_sample: decode a handful of REAL
            # batches once, park them on device, cycle.  This takes the
            # host decode + host->device transfer wall out of the loop so the
            # number measures the device-side real-data step (uint8 wire
            # cast + normalize run inside the compiled step —
            # train/step.py::prep_inputs), augmentation baked in at decode.
            # Stricter isolation than tf_cnn's mechanics (which repeat one
            # cached record through the LIVE pipeline and still pay the
            # per-step transfer) — see the deviation note in flags.py.
            # 8 distinct batches keep XLA from seeing a constant input
            # while staying far under HBM pressure at bench batch sizes.
            import itertools

            cached = [
                place_batch(b)
                for b in itertools.chain(
                    [batch], itertools.islice(host_iter, 7))
            ]
            # stop the decode pool NOW: a live producer thread polling the
            # prefetch queue is exactly the host work this flag exists to
            # take out of the measurement
            host_iter.close()
            print_fn(f"repeat_cached_sample: {len(cached)} real batches "
                     "decoded once, device-resident, cycled per step")

            def batches():
                yield from itertools.cycle(cached)
        else:
            def batches():
                def raw():
                    import itertools

                    for b in itertools.chain([batch], host_iter):
                        yield place_batch(b)
                yield from _prefetch(raw(), cfg.prefetch_depth)
    elif spec.is_text and cfg.data_dir is not None:
        # real pre-tokenized corpus (<data_dir>/<split>.bin memmap) — the
        # reference's real-data axis for the text members (round 3)
        from tpu_hc_bench.data.tokens import TokenDataset, _resolve
        from jax.sharding import PartitionSpec as P

        seq_len = spec.input_shape[0]
        split = "train"
        if cfg.eval:
            try:
                _resolve(cfg.data_dir, "validation")
                split = "validation"
            except FileNotFoundError:
                pass
        ds = TokenDataset(
            cfg.data_dir, global_batch, seq_len, split=split,
            causal_lm=spec.causal_lm,
            worker=jax.process_index(), num_workers=jax.process_count(),
            seed=cfg.seed, vocab_size=spec.vocab_size,
        )
        host_iter = iter(ds)
        batch = next(host_iter)
        batch_spec = P(DATA_AXIS, SEQ_AXIS) if sp_active else None

        def batches():
            def raw():
                import itertools

                for b in itertools.chain([batch], host_iter):
                    yield step_mod.shard_batch(b, mesh, batch_spec)
            yield from _prefetch(raw(), cfg.prefetch_depth)
    elif spec.is_text:
        seq_len = spec.input_shape[0]
        ds = SyntheticTokens(global_batch, seq_len, seed=cfg.seed,
                             vocab_size=spec.vocab_size,
                             causal_lm=spec.causal_lm)
        batch = ds.batch()
        from jax.sharding import PartitionSpec as P

        # under SP the [B, S] token batch shards over BOTH mesh axes
        batch_spec = P(DATA_AXIS, SEQ_AXIS) if sp_active else None

        def batches():
            dev_batch = step_mod.shard_batch(batch, mesh, batch_spec)
            while True:
                yield dev_batch
    elif getattr(spec, "ctc", False):
        # deepspeech2: spectrogram frames + padded CTC transcripts
        from tpu_hc_bench.data.synthetic import SyntheticSpeech
        from tpu_hc_bench.models.deepspeech import max_label_for

        if cfg.data_dir is not None:
            raise ValueError(
                f"--data_dir is not supported for {cfg.model} "
                "(synthetic spectrograms only)")
        if cfg.eval:
            raise ValueError(
                "--eval is not supported for the CTC member (decode/CER "
                "is outside the benchmark protocol)")
        frames, freq = spec.input_shape
        # CTC validity: label length bounded by the post-conv frame count
        ds = SyntheticSpeech(global_batch, frames, freq,
                             max_label_for(frames), seed=cfg.seed)
        batch = ds.batch()

        def batches():
            dev_batch = step_mod.shard_batch(batch, mesh)
            while True:
                yield dev_batch
    elif getattr(spec, "integer_input", False):
        # NCF: [B, 2] (user, item) id pairs + binary labels — same
        # fixed-batch contract as the image members
        from tpu_hc_bench.data.synthetic import SyntheticIds

        if cfg.data_dir is not None:
            raise ValueError(
                f"--data_dir is not supported for {cfg.model} "
                "(synthetic implicit-feedback pairs only)")
        m = model
        ds = SyntheticIds(global_batch, num_users=m.num_users,
                          num_items=m.num_items, seed=cfg.seed)
        batch = ds.batch()

        def batches():
            dev_batch = step_mod.shard_batch(batch, mesh)
            while True:
                yield dev_batch
    else:
        ds = SyntheticImages(
            global_batch, spec.input_shape, num_classes=cfg.num_classes,
            seed=cfg.seed,
        )
        batch = ds.batch()

        def batches():
            dev_batch = step_mod.shard_batch(batch, mesh)
            while True:
                yield dev_batch

    # --- state + step ---
    pp_save_ctx = None     # (model, template) when PP saves need restacking
    place_fn = None        # re-place a host-restored state on the mesh (the
                           # --on_nonfinite=rewind mid-run restore path)
    if sp_active:
        print_fn(f"sequence parallel: {sp} shards x "
                 f"{spec.input_shape[0] // sp} tokens/shard "
                 f"({cfg.attention_impl})")
        # init with the unsharded twin (identical params; axis_index needs
        # a bound mesh axis so the SP model itself can't init here), then
        # swap in the SP apply
        init_model = model.clone(attention_impl="dense", seq_axis=None)
        state = step_mod.make_train_state(init_model, cfg, batch)
        state = state.replace(apply_fn=model.apply)
        if not sharded_ckpt:
            state, sp_restored, resume_rec = _maybe_restore(
                state, cfg, print_fn, topo=topo_rec, obs_writer=obs_writer)
        if tp > 1:
            # DP x SP x TP: params/opt model-sharded (auto axis), the SP
            # step's shard_map stays manual over data+seq only
            print_fn(f"tensor parallel: {tp}-way (hybrid with SP)")
            place_fn = lambda s: step_mod.shard_state_tp(s, mesh)
        else:
            place_fn = lambda s: step_mod.replicate_state(s, mesh)
        state = place_fn(state)
        if sharded_ckpt:
            # multi-host SP x TP (round 4): same restore-after-placement
            # as the plain TP arm — Orbax reads each array straight into
            # its committed model sharding
            state, sp_restored, resume_rec = _maybe_restore(
                state, cfg, print_fn, sharded=True, topo=topo_rec,
                obs_writer=obs_writer)
        batch_iter = batches()
        if cfg.eval:
            # round 3: SP eval — the (data, seq) shard_map eval arm with
            # the shared text-metric formulas (exact global weighted
            # mean); round 4 extends it to the DP x SP x TP hybrid
            # (partial-manual, model axis auto), completing the eval
            # matrix (DP/TP/EP/PP/SP/hybrids)
            _require_checkpoint_for_eval(cfg, sp_restored, print_fn)
            return _run_eval(
                cfg, spec, layout, mesh, state, batch_iter, global_batch,
                fab, print_fn, sp=True, tp=tp > 1, obs_writer=obs_writer,
            )
        # the shared psum step builder handles SP (axes = (data, seq),
        # fusion buckets reduce over both)
        train_step = step_mod.build_train_step(mesh, cfg, spec, fab)
    elif pp > 1:
        # the PP step builder derives the stage forward from the model's
        # pp_embed/pp_layer_module/pp_head interface (GPT + llama
        # families); models without it (CNNs, encoder-only) can't pipeline
        if not all(hasattr(model, m) for m in
                   ("pp_embed", "pp_layer_module", "pp_head")):
            raise ValueError(
                "--pipeline_parallel requires a decoder implementing the "
                "PP interface (pp_embed/pp_layer_module/pp_head: the GPT "
                f"and llama families), not {cfg.model}")
        from tpu_hc_bench.parallel import pipeline as pipe_mod

        if model.num_layers % pp:
            raise ValueError(
                f"{cfg.model}: {model.num_layers} layers not divisible by "
                f"pipeline_parallel={pp}")
        num_mb = cfg.num_microbatches or (
            2 * pp if cfg.batch_size % (2 * pp) == 0 else pp)
        if cfg.batch_size % num_mb:
            raise ValueError(
                f"per-worker batch {cfg.batch_size} not divisible by "
                f"num_microbatches={num_mb}")
        print_fn(f"pipeline: {pp} stages x {num_mb} microbatches "
                 f"({model.num_layers // pp} layers/stage)")
        if tp > 1:
            print_fn(f"tensor parallel: {tp}-way (hybrid with PP)")
        pp_base_step = 0
        restored = False
        if pp_native_ckpt:
            # multi-host PP (round 4): PP-native sharded checkpoints —
            # init placed, then restore each array into its committed
            # pipe/model sharding (utils.checkpoint.restore_pp); saves go
            # through save_pp in save_now (no DP-layout interchange)
            from tpu_hc_bench.utils import checkpoint as ckpt_mod

            params, opt_state = pipe_mod.make_pp_state(model, cfg, batch[0],
                                                       mesh, tp=tp > 1)
            if (cfg.resume in ("must", "elastic")
                    and ckpt_mod.latest_step(cfg.train_dir) is None):
                raise FileNotFoundError(
                    f"--resume={cfg.resume}: no complete checkpoint "
                    f"under {cfg.train_dir}")
            if (cfg.resume != "never"
                    and ckpt_mod.latest_step(cfg.train_dir) is not None):
                saved_topo = ckpt_mod.read_topology(cfg.train_dir)
                if saved_topo is not None:
                    # pp-native stacked global shapes are pipe-degree
                    # independent and Orbax re-places them, so same-
                    # layout mesh changes validate as a no-op; cross-
                    # layout transitions refuse loudly here instead of
                    # dying in an Orbax structure mismatch
                    _, plan = ckpt_mod.check_topology(
                        saved_topo, topo_rec, cfg.train_dir,
                        elastic=cfg.resume == "elastic")
                    if plan:
                        print_fn(f"elastic resume: {plan}")
                if cfg.eval:
                    params, _, pp_base_step = ckpt_mod.restore_pp(
                        params, None, cfg.train_dir)
                    opt_state = None
                else:
                    params, opt_state, pp_base_step = ckpt_mod.restore_pp(
                        params, opt_state, cfg.train_dir)
                restored = True
                print_fn(f"restored checkpoint step {pp_base_step} from "
                         f"{cfg.train_dir} (PP-native)")
                if saved_topo is not None:
                    resume_rec = {
                        "restored_step": pp_base_step,
                        "saved_world": saved_topo.get("world"),
                        "live_world": topo_rec.get("world"),
                        "arm": saved_topo.get("variable_update"),
                        "elastic": False}
                    obs_writer.event("resume", **resume_rec,
                                     saved_topology=saved_topo,
                                     live_topology=topo_rec, plan=None)
            if cfg.eval:
                _require_checkpoint_for_eval(cfg, restored, print_fn)
        else:
            if cfg.train_dir:
                # DP<->DPxPP checkpoint interchange: restore the DP-layout
                # checkpoint through a host-side abstract template (no
                # device memory — PP models may not fit one device),
                # restack the layer subtrees into the pipe-sharded trunk,
                # re-place
                pp_template = step_mod.abstract_train_state(model, cfg,
                                                            batch)
                restored_t, restored, resume_rec = _maybe_restore(
                    pp_template, cfg, print_fn, topo=topo_rec,
                    obs_writer=obs_writer)
                if restored:
                    pp_base_step = int(np.asarray(restored_t.step))
                    if cfg.eval:
                        # forward-only: never restack or place the
                        # params-sized momentum trace (a PP model may not
                        # fit one device WITH it)
                        params = pipe_mod.stack_layer_params(
                            restored_t.params, model.num_layers)
                        params = pipe_mod.place_pp_state(
                            params, None, mesh, tp=tp > 1)
                        opt_state = None
                    else:
                        params, opt_state = \
                            pipe_mod.pp_state_from_train_state(
                                restored_t, model.num_layers)
                        params, opt_state = pipe_mod.place_pp_state(
                            params, opt_state, mesh, tp=tp > 1)
                pp_save_ctx = (model, pp_template, pp_base_step)
            if not restored:
                if cfg.eval:
                    _require_checkpoint_for_eval(cfg, restored, print_fn)
                params, opt_state = pipe_mod.make_pp_state(
                    model, cfg, batch[0], mesh, tp=tp > 1)
        if cfg.eval:
            # round 3: PP eval — forward-only pipeline (deterministic),
            # same loss/top-1 arms as DP eval of the same checkpoint
            pp_eval = pipe_mod.build_pp_eval_step(
                mesh, model, cfg, num_mb, params, tp=tp > 1)
            return _run_eval(
                cfg, spec, layout, mesh, params, batches(), global_batch,
                fab, print_fn, eval_step=pp_eval, obs_writer=obs_writer,
            )
        pp_step, _ = pipe_mod.build_pp_train_step(
            mesh, model, cfg, num_mb, params, opt_state, tp=tp > 1)
        state = (params, opt_state)

        def train_step(state, batch, rng):
            new_params, new_opt, loss = pp_step(*state, batch, rng)
            return (new_params, new_opt), {"loss": loss}

        batch_iter = batches()
    else:
        zero1 = cfg.variable_update == "zero1"
        if zero1:
            # the compositions flags.resolve can't see (fabric, slices)
            # die here, before any state is built
            if fab is fabric_mod.Fabric.HOST:
                raise ValueError(
                    "--variable_update=zero1 needs a device fabric "
                    "(ici): the host path has no sharded optimizer")
            if num_slices > 1:
                raise ValueError(
                    "--variable_update=zero1 composes with single-slice "
                    "data parallelism only (no multislice reduce-scatter "
                    "layout yet)")
            print_fn(
                f"zero1: optimizer state sharded {layout.total_workers}"
                f"-way over the data axis (reduce-scatter + sharded "
                f"update + all-gather; overlap_grad_comm="
                f"{cfg.overlap_grad_comm})")
            state = step_mod.make_zero1_state(model, cfg, batch,
                                              layout.total_workers)
        else:
            state = step_mod.make_train_state(model, cfg, batch)
        if not sharded_ckpt:
            state, restored, resume_rec = _maybe_restore(
                state, cfg, print_fn, topo=topo_rec, obs_writer=obs_writer)
        if mp > 1:
            mode = "ep" if getattr(cfg, "expert_parallel", 1) > 1 else "tp"
            place_fn = lambda s, m=mode: step_mod.shard_state_tp(s, mesh, m)
        elif zero1:
            place_fn = lambda s: step_mod.place_zero1_state(s, mesh)
        else:
            place_fn = lambda s: step_mod.replicate_state(s, mesh)
        state = place_fn(state)
        if sharded_ckpt:
            # multi-host TP/EP: restore AFTER placement so Orbax reads
            # each array straight into its committed sharding
            state, restored, resume_rec = _maybe_restore(
                state, cfg, print_fn, sharded=True, topo=topo_rec,
                obs_writer=obs_writer)
        if cfg.eval:
            _require_checkpoint_for_eval(cfg, restored, print_fn)
        batch_iter = batches()
        if cfg.eval:
            # round 4: dcn=True is the multislice eval arm — the same
            # (dcn, data) batch split + hierarchical metric psum as the
            # multislice train step, forward-only
            return _run_eval(
                cfg, spec, layout, mesh, state, batch_iter, global_batch,
                fab, print_fn, follow_inputs=mp > 1, dcn=num_slices > 1,
                obs_writer=obs_writer,
            )
        train_step = step_mod.build_train_step(mesh, cfg, spec, fab)
    rng = jax.random.PRNGKey(cfg.seed + 17)

    # per-host heartbeat stream (obs.fleet): EVERY process writes its
    # own metrics.<process_index>.jsonl — per-host visibility is the
    # point, so this is deliberately not primary-gated like the main
    # stream.  Train loop only (created after the eval arms return).
    fleet_writer = obs_fleet.FleetWriter(cfg.metrics_dir)
    # runtime HBM ledger (obs.memory): sampled once per sync window on
    # metrics runs, plus one end-of-run sample on every run
    mem_ledger = obs_memory.MemoryLedger()

    # --- warmup (includes compile; reference warmup=50, :32) ---
    # rng is folded with the step counter so dropout masks differ per step
    phases.enter("compile")
    t_compile = time.perf_counter()
    metrics = None
    warm_batch = next(batch_iter)
    flops_probe = None
    probe_wanted = bool(obs_writer.enabled or cfg.fabric_ceiling
                        or hbm_budget is not None)
    if hbm_budget is not None:
        # --hbm_budget: the AOT memory report must exist BEFORE the
        # warmup pays for the full run's compile, so the probe runs
        # SYNCHRONOUSLY here (its compiled handle also serves the MFU
        # probe — one compile, both measurements) and the verdict
        # prints at run start.
        flops_probe = obs_efficiency.StepFlopsProbe(
            train_step, state, warm_batch, rng, background=False)
        budget_bytes, budget_note = obs_memory.resolve_hbm_budget_bytes(
            hbm_budget)
        mem_an = flops_probe.memory_analysis()
        for ln in obs_memory.budget_lines(mem_an, budget_bytes,
                                          budget_note):
            print_fn(ln)
        if budget_bytes is not None and mem_an:
            obs_writer.event(
                "hbm_budget", budget_bytes=budget_bytes,
                total_bytes=mem_an.get("total_bytes", 0),
                exceeded=mem_an.get("total_bytes", 0) > budget_bytes)
    try:
        for w in range(max(1, cfg.num_warmup_batches)):
            if w:
                warm_batch = next(batch_iter)
            state, metrics = train_step(state, warm_batch,
                                        jax.random.fold_in(rng, w))
        drain(metrics["loss"])
    except BaseException as e:
        # OOM forensics: the warmup (first full materialization of the
        # step's activations) is where memory walls actually hit
        if obs_memory.is_oom_error(e) and cfg.metrics_dir:
            dpath = obs_memory.dump_forensics(
                cfg.metrics_dir, reason="oom", error=str(e),
                print_fn=print_fn)
            if dpath:
                obs_writer.event("memory_dump",
                                 path=os.path.basename(dpath),
                                 reason="oom")
            tpath = timeline_mod.dump_timeline(cfg.metrics_dir,
                                               reason="oom")
            if tpath:
                obs_writer.event("timeline_dump",
                                 path=os.path.basename(tpath),
                                 reason="oom")
        raise
    warmup_elapsed = time.perf_counter() - t_compile
    print_fn(
        f"warmup done: {cfg.num_warmup_batches} steps in "
        f"{warmup_elapsed:.1f}s (includes compile)"
    )
    if compile_cache_dir:
        # hit/miss accounting: entries that appeared during warmup are
        # the compiles this run actually paid for; zero new entries over
        # a non-empty cache is a warm start (the ledger's compile phase
        # shows the wall-clock consequence)
        cache_entries_after = compile_cache.entry_count(compile_cache_dir)
        cache_new = cache_entries_after - cache_entries_before
        cache_warm = cache_new == 0 and cache_entries_before > 0
        print_fn(f"compile cache: {cache_new} new entr"
                 f"{'y' if cache_new == 1 else 'ies'} "
                 f"({'warm start' if cache_warm else 'cold/partial'}); "
                 f"{cache_entries_after} total")
        cache_rec = {"dir": compile_cache_dir,
                     "entries_before": cache_entries_before,
                     "entries_after": cache_entries_after,
                     "new_entries": cache_new, "warm": cache_warm}
        obs_writer.event("compile_cache", **cache_rec)
        obs_writer.update_manifest({"compile_cache": cache_rec})

    # measured FLOPs (obs.efficiency): AOT-lower the very step program
    # and ask XLA's cost analysis — the honest MFU numerator.  Only on
    # observability-enabled runs: the extra compile is wasted wall on a
    # bare benchmark run.  Round 10: the probe runs on a BACKGROUND
    # thread (pure telemetry — nothing the loop depends on), so its
    # lower+compile overlaps the timed loop instead of sitting in the
    # ledger's compile phase; the result is joined after the loop.
    # (--hbm_budget runs already created it synchronously pre-warmup.)
    if flops_probe is None and probe_wanted:
        flops_probe = obs_efficiency.StepFlopsProbe(
            train_step, state, warm_batch, rng)
    # analytic memory table (obs.memory): params/opt/batch bytes from
    # the live shapes — pure host arithmetic, computed while the warmup
    # batch is still referenced; the post-run memory_report pairs it
    # with the probe's AOT byte account
    analytic_mem = obs_memory.analytic_memory_table(state, warm_batch)
    # drop the reference NOW: the probe only needed shapes, and holding
    # the last warmup batch through the timed run would pin one extra
    # device batch in HBM (max_inflight exists because batch HBM matters)
    warm_batch = None
    if cfg.metrics_dir:
        # the compile phase's memory high water (the warmup materialized
        # the step program's buffers for the first time)
        obs_writer.event("memory", **mem_ledger.sample("compile"))

    # --- timed loop (reference num_batches=100, display_every=10) ---
    # Fully asynchronous dispatch: the main thread never syncs, so the
    # device never waits on a host round trip; progress is observed by
    # the shared _AsyncTimeline protocol.  The already-
    # fetched warmup loss is the t=0 marker, so the measured span covers
    # exactly the num_batches timed steps.
    units = _example_units(cfg, spec)
    timeline = _AsyncTimeline(cfg.num_batches, cfg.display_every,
                              global_batch)
    # windowed jax.profiler tracing (--profile_steps, or the legacy
    # first-sync-window default) — the structured replacement for the
    # reference's I_MPI_DEBUG=5 fabric tracing
    # (run-tf-sing-libfabric-intelmpi.sh:98)
    trace_window = _TraceWindow(cfg, print_fn, timeline.sync_every)
    timeline.start(metrics["loss"])
    phases.enter("step")
    hb_ewma = obs_fleet.StepEwma()
    warmup_steps = max(1, cfg.num_warmup_batches)

    # --- resilience runtime (round 8): fault-injection plan, preemption
    # handler, hung-step watchdog, non-finite guard tracking.  The guard
    # itself runs INSIDE the compiled step (train/step.py); here the
    # driver threads its per-step flag into device-side counters and pays
    # one scalar fetch per sync window to enforce policy.
    plan = inject_mod.parse_plan(cfg.inject_fault)
    policy = cfg.on_nonfinite
    tracker = (guards_mod.GuardTracker()
               if policy in ("skip", "rewind") else None)
    rewind_base_step = 0
    if policy == "rewind":
        # the absolute step counter at this RUN's start (nonzero on
        # --resume runs): rewind waste accounting must place checkpoint
        # stamps relative to this run's timed loop, not step 0 (the
        # post-warmup fetch is one tiny scalar, after the drain)
        rewind_base_step = (int(np.asarray(jax.device_get(state.step)))
                            - warmup_steps)
    world = jax.process_count()
    preempt_h = preempt_mod.PreemptionHandler(print_fn=print_fn).install()
    timeout_s = watchdog_mod.resolve_timeout(
        cfg.step_timeout_s, warmup_elapsed / warmup_steps)
    dog = None

    # async checkpoint writer (round 10): periodic saves overlap their
    # Orbax write with the step loop; only the device→host snapshot
    # blocks.  Synchronous whenever the save is collective or must
    # preserve the resilience exit-code contract: multi-host (Orbax
    # barriers + the sentinel wait are collective — a backgrounded
    # collective on some hosts is a deadlock), PP (restack/stacked
    # layouts), sharded states, io_error@ckpt injection (the retry
    # proof drives the sync path), and every emergency/preempt save.
    async_ckpt = None
    if (cfg.train_dir and cfg.async_checkpoint and world == 1
            and pp == 1 and not sharded_ckpt
            and not (plan is not None and plan.io_error)):
        from tpu_hc_bench.utils import checkpoint as ckpt_mod

        async_ckpt = ckpt_mod.AsyncCheckpointWriter(cfg.train_dir,
                                                    print_fn=print_fn)
        print_fn("checkpointing: async (snapshot blocks, write "
                 "overlapped, in-flight <= 1; emergency saves stay "
                 "synchronous)")

    def _drain_async_commits() -> None:
        """Move landed-save records from the writer thread's queue into
        the metrics stream — on the main thread, where MetricsWriter
        is safe to touch."""
        if async_ckpt is None:
            return
        while async_ckpt.commits:
            obs_writer.event("checkpoint_commit",
                             **async_ckpt.commits.popleft())

    def _flush_async_for_exit() -> None:
        """Land (or report) any in-flight overlapped save before a
        fatal-exit path closes the writers — a background write error
        or an unrecorded commit must not vanish under the budget/abort
        error that outranks it."""
        if async_ckpt is None:
            return
        try:
            async_ckpt.wait()
        except Exception as e:
            print_fn(f"WARNING: async checkpoint write failed during "
                     f"abort: {e}")
            obs_writer.event("async_ckpt_error", error=str(e))
        _drain_async_commits()

    def save_now(i: int, phase: str = "checkpoint") -> None:
        if async_ckpt is not None and phase == "checkpoint":
            # overlapped save: barrier on the previous write (usually
            # long landed — a save per sync window leaves a whole
            # window to finish), snapshot to host, hand off.  The
            # ledger's checkpoint_async phase records only this
            # blocking slice; the write's own seconds ride the
            # checkpoint_commit record it queues when it lands.
            if dog is not None:
                dog.pause()
            phases.enter("checkpoint_async", step=i)
            t_snap = time.monotonic()
            try:
                async_ckpt.submit(state, gc_keep=cfg.keep_checkpoints,
                                  topology=topo_rec)
                print_fn(f"checkpoint snapshot: step {i} "
                         f"({time.monotonic() - t_snap:.3f}s blocking; "
                         f"write overlapped)")
            finally:
                if cfg.metrics_dir:
                    # the snapshot's host copy of the full state is the
                    # phase's memory signature — attribute it
                    obs_writer.event("memory", **mem_ledger.sample(
                        "checkpoint_async", step=i))
                phases.enter("step", step=i)
                if dog is not None:
                    dog.resume()
            return
        def _do() -> None:
            if plan is not None:
                plan.maybe_io_error("ckpt")
            if pp_native_ckpt:
                from tpu_hc_bench.utils import checkpoint as ckpt_mod

                p, o = state
                path = ckpt_mod.save_pp(
                    p, o, pp_base_step + warmup_steps + i, cfg.train_dir,
                    topology=topo_rec)
                print_fn(f"checkpoint saved: {path} (PP-native)")
                return
            ctx = None
            if pp_save_ctx is not None:
                pp_model, pp_template, pp_base = pp_save_ctx
                # resume-aware stamp: continue the restored checkpoint's
                # step count so a resumed PP run never saves under a
                # lower step
                ctx = (pp_model, pp_template, pp_base + warmup_steps + i)
            _save_state(state, cfg, print_fn, pp_ctx=ctx,
                        sharded=sharded_ckpt, topology=topo_rec)

        # a multi-GB save to slow storage stalls the step loop
        # legitimately — the watchdog must not count it as a hang
        if dog is not None:
            dog.pause()
        phases.enter(phase, step=i)
        try:
            # multi-host saves are COLLECTIVE (Orbax barriers + the
            # commit-sentinel wait): a one-sided retry would leave the
            # retrier alone in a barrier, so retries are single-host only
            retry_io(_do, what="checkpoint save", print_fn=print_fn,
                     obs_writer=obs_writer,
                     attempts=1 if world > 1 else 3)
            if cfg.keep_checkpoints and cfg.train_dir:
                from tpu_hc_bench.utils import checkpoint as ckpt_mod

                # writer barrier: retention must never reap the .tmp an
                # in-flight overlapped save is still committing into
                ckpt_mod.gc_checkpoints(cfg.train_dir,
                                        cfg.keep_checkpoints,
                                        print_fn=print_fn,
                                        writer=async_ckpt)
        finally:
            if cfg.metrics_dir:
                obs_writer.event("memory", **mem_ledger.sample(
                    phase, step=i))
            phases.enter("step", step=i)
            if dog is not None:
                dog.resume()

    def _emergency(completed: int) -> None:
        """Preemption honored at a step boundary: one emergency
        checkpoint, metrics flush, distinct exit (launcher maps the
        raised PreemptedError to EXIT_PREEMPTED)."""
        print_fn(f"preemption: stopping after timed step {completed} "
                 f"(signal {preempt_h.signum})")
        phases.enter("emergency_save", step=completed)
        if async_ckpt is not None:
            # land (or surface the failure of) any in-flight overlapped
            # save before the emergency save claims the same directory
            async_ckpt.wait()
            _drain_async_commits()
        saved = bool(cfg.train_dir)
        if saved and tracker is not None:
            # settle the guard first: under rewind the state may carry
            # poisoned mid-window updates, and the emergency checkpoint
            # must never persist them for --resume=auto to restore
            try:
                _settle_guard(completed)
            except guards_mod.GuardBudgetError:
                saved = False   # budget died on poisoned state: keep it
                                # off disk, exit preempted without a save
        if saved:
            save_now(completed, phase="emergency_save")
            if not pp_native_ckpt:
                _fingerprint_line(
                    state.params if hasattr(state, "params") else state[0],
                    print_fn)
            obs_writer.event("emergency_ckpt", step=completed)
        if cfg.metrics_dir:
            # emergency forensics (obs.memory): what the devices held
            # when the run was killed — written BEFORE the streams
            # close, best-effort so it can never mask the preemption
            obs_writer.event("memory", **mem_ledger.sample(
                "emergency_save", step=completed))
            dpath = obs_memory.dump_forensics(
                cfg.metrics_dir, reason="emergency_save", step=completed,
                print_fn=print_fn)
            if dpath:
                obs_writer.event("memory_dump",
                                 path=os.path.basename(dpath),
                                 reason="emergency_save", step=completed)
            # time forensics beside the memory forensics: the last-K
            # spans per rank — what phase everyone was in at the kill
            tpath = timeline_mod.dump_timeline(
                cfg.metrics_dir, reason="emergency_save", step=completed)
            if tpath:
                obs_writer.event("timeline_dump",
                                 path=os.path.basename(tpath),
                                 reason="emergency_save", step=completed)
        obs_writer.event("preempt", step=completed,
                         signal=preempt_h.signum, checkpoint_saved=saved,
                         world=topo_rec.get("world"),
                         arm=topo_rec.get("variable_update"))
        phases.end(step=completed)
        obs_writer.close()
        fleet_writer.close()
        timeline_mod.detach()
        raise preempt_mod.PreemptedError(completed, saved, preempt_h.signum,
                                         topology=topo_rec)

    guard_seen_total = 0
    guard_last_poll_i = 0
    rewind_streak = 0
    # Non-blocking sync windows (round 10): the guard-counter fetch is
    # DOUBLE-BUFFERED.  At each sync window the driver snapshots the
    # device counters (refs only — no fetch) and fetches the PREVIOUS
    # window's snapshot: a full window of compute has drained behind
    # those scalars, so the device_get returns without stalling the
    # dispatch path, and the hot loop never synchronously round-trips
    # mid-run.  Policy therefore acts one window late; the settle paths
    # (_settle_guard: before saves, at preemption, at the final step)
    # flush the pipeline AND poll live, so no badness is ever persisted
    # to disk or survives the run unseen.
    guard_pending: list = []    # [(window_end_step, counter handles)]
    guard_wiped_until = -1      # a rewind's tracker.reset() wipes the
                                # counters for steps up to this stamp:
                                # that window must not pass as
                                # "observed clean" and break the
                                # consecutive-rewind budget

    def _apply_guard(j: int, streak: int, total: int, peak: int,
                     now_i: int) -> None:
        """Enforce --max_bad_steps / run the rewind restore on counters
        observed through step ``j`` (``now_i`` = the loop's current
        step — under the deferred fetch, later than ``j``)."""
        nonlocal guard_seen_total, guard_last_poll_i, rewind_streak
        nonlocal guard_wiped_until, state
        steps_since = j - guard_last_poll_i
        guard_last_poll_i = j
        new_bad = total - guard_seen_total
        if new_bad <= 0:
            # only a CLEAN window with actual steps in it breaks a rewind
            # streak — not a second poll at the same step (the settle-
            # before-save path), and not a window whose counters a
            # rewind's reset wiped (the post-restore replay span)
            if steps_since > 0 and j > guard_wiped_until:
                rewind_streak = 0
            return
        guard_seen_total = total
        if policy == "skip":
            print_fn(f"nonfinite: dropped {new_bad} update(s) in window "
                     f"ending step {j} (consecutive {streak}, "
                     f"total {total})")
            obs_writer.event("nonfinite_skip", step=j, new_bad=new_bad,
                             streak=streak, total=total)
            # dropped updates burned step time whose work was discarded:
            # the goodput ledger counts them against the run
            phases.note_skipped_updates(new_bad)
            # budget on the PEAK streak: a consecutive run that ended
            # inside the window (streak already reset by a good step)
            # still counts
            if peak >= cfg.max_bad_steps:
                _flush_async_for_exit()
                phases.end(step=j)
                obs_writer.close()
                fleet_writer.close()
                raise guards_mod.GuardBudgetError(
                    f"{peak} consecutive non-finite steps "
                    f"(--max_bad_steps={cfg.max_bad_steps})")
            return
        # rewind: restore the last complete checkpoint and re-enter the
        # loop with a skip-window over the offending data batches.
        # Budget matches the skip policy's: the run dies on the
        # max_bad_steps-th consecutive bad window.
        rewind_streak += 1
        if rewind_streak >= cfg.max_bad_steps:
            _flush_async_for_exit()
            phases.end(step=j)
            obs_writer.close()
            fleet_writer.close()
            raise guards_mod.GuardBudgetError(
                f"{rewind_streak} consecutive rewinds without a clean "
                f"window (--max_bad_steps={cfg.max_bad_steps})")
        from tpu_hc_bench.utils import checkpoint as ckpt_mod

        phases.enter("rewind_replay", step=now_i)
        if dog is not None:
            dog.pause()     # a long restore from slow storage is not a hang
        try:
            if async_ckpt is not None:
                # the newest overlapped save must land (or its failure
                # surface) before we pick the checkpoint to restore
                async_ckpt.wait()
                _drain_async_commits()
            restored = ckpt_mod.restore(state, cfg.train_dir,
                                        sharded=sharded_ckpt)
            state = restored if sharded_ckpt else place_fn(restored)
        finally:
            if dog is not None:
                dog.resume()
        restored_step = int(np.asarray(jax.device_get(restored.step)))
        skip_n = timeline.sync_every
        for _ in range(skip_n):
            next(batch_iter)
        tracker.reset()
        guard_pending.clear()   # snapshot refs predate the reset: a
                                # deferred fetch would re-report the
                                # badness this restore just cured
        guard_wiped_until = now_i
        guard_seen_total = 0
        # every timed step since the restored checkpoint ran for nothing
        # — its updates were just discarded; the ledger re-attributes
        # that span as wasted (resume-aware: restored_step counts prior
        # runs' steps and this run's warmup)
        lost_steps = obs_goodput.rewind_lost_steps(
            now_i, restored_step, rewind_base_step, warmup_steps)
        phases.note_lost_steps(lost_steps)
        phases.enter("step", step=now_i)
        print_fn(f"rewind: non-finite step(s) in window ending step {j}; "
                 f"restored checkpoint step {restored_step}, skipping "
                 f"{skip_n} batches")
        obs_writer.event("rewind", step=now_i, restored_step=restored_step,
                         skipped_batches=skip_n, streak=streak,
                         lost_steps=lost_steps)

    def _fetch_guard(handles) -> tuple[int, int, int]:
        streak, total, peak = jax.device_get(list(handles))
        return int(streak), int(total), int(peak)

    def _settle_guard(i: int) -> None:
        """Flush the deferred guard pipeline, then poll the live
        counters — the one deliberate host sync of the resilience path,
        paid only where state is about to be persisted (saves,
        preemption) or the run is ending."""
        while guard_pending:
            j, handles = guard_pending.pop(0)
            _apply_guard(j, *_fetch_guard(handles), now_i=i)
        _apply_guard(i, *tracker.poll(), now_i=i)

    try:
        if timeout_s is not None:
            dog = watchdog_mod.Watchdog(
                timeout_s, lambda: timeline.fetcher.last_arrival_t,
                print_fn=print_fn,
                last_record_fn=lambda: obs_writer.last_record,
                obs_writer=obs_writer,
                forensics_fn=(
                    (lambda: (obs_memory.dump_forensics(
                        cfg.metrics_dir, reason="watchdog",
                        print_fn=print_fn),
                        timeline_mod.dump_timeline(
                            cfg.metrics_dir, reason="watchdog")))
                    if cfg.metrics_dir else None)).start()
            print_fn(f"watchdog armed: step timeout {timeout_s:.1f}s")
        if policy == "rewind":
            from tpu_hc_bench.utils import checkpoint as ckpt_mod

            if ckpt_mod.latest_step(cfg.train_dir) is None:
                save_now(0)     # rewind baseline: the post-warmup state
        for i in range(1, cfg.num_batches + 1):
            # step boundary: honor preemption.  Single-host checks the
            # local flag every step; multi-host runs the cross-host
            # agreement at sync-window boundaries only — it is a
            # collective and must execute at the same step everywhere.
            if world == 1:
                if preempt_h.requested():
                    _emergency(i - 1)
            elif ((i - 1) % timeline.sync_every == 0
                    and preempt_h.agreed(world)):
                _emergency(i - 1)
            trace_window.maybe_start(i, timeline.fetcher)
            with timeline_mod.span("input_wait", step=i) as waited:
                batch = next(batch_iter)
            # host time blocked on the input pipeline — carved out of
            # the "step" phase by the ledger (a cheap float add here;
            # the jsonl write happens once per sync window): the
            # input_wait span's own clock pair
            phases.note_data_wait(waited.t1 - waited.t0)
            # host-side dispatch cost only (the step itself is async;
            # device progress is the fetch thread's device_step spans)
            with timeline_mod.span("step_dispatch", step=i):
                if plan is not None:
                    plan.fire_step_faults(i, print_fn, obs_writer)
                    batch = plan.poison_batch(i, batch, print_fn,
                                              obs_writer)
                state, metrics = train_step(
                    state, batch,
                    jax.random.fold_in(rng, warmup_steps + i))
            timeline.record(i, metrics["loss"])
            if tracker is not None:
                tracker.update(metrics["nonfinite"])
                if i == cfg.num_batches:
                    # run end: flush the deferred window AND the live
                    # counters — nothing may survive the run unseen
                    _settle_guard(i)
                elif i % timeline.sync_every == 0:
                    # double-buffered: fetch window N-1's counters
                    # (complete long ago — no stall) while window N's
                    # steps execute; snapshot this window's refs
                    if guard_pending:
                        j, handles = guard_pending.pop(0)
                        _apply_guard(j, *_fetch_guard(handles), now_i=i)
                    guard_pending.append((i, tracker.handles()))
            if i % timeline.sync_every == 0 or i == cfg.num_batches:
                # sync-window bookkeeping: flush the accumulated
                # data-wait into the ledger stream, beat this host's
                # heartbeat file, and (multi-host) run the device-backed
                # progress allgather.  The whole block is gated on
                # cfg.metrics_dir: a bare benchmark run must not pay a
                # memory-stats poll or a host-blocking collective inside
                # the timed loop for telemetry nobody recorded.  The
                # gate must be this launch-uniform IMMUTABLE flag — not
                # fleet_writer.enabled, which a host whose heartbeat
                # write failed flips to False unilaterally, and a
                # collective only some hosts enter is a deadlock.  The
                # condition is a function of i only, so the allgather
                # executes at the same step everywhere.
                phases.flush(i)
                _drain_async_commits()
                if cfg.metrics_dir:
                    hb_step = timeline.fetcher.fetched_step
                    ewma_ms = hb_ewma.update(hb_step)
                    # HBM ledger (obs.memory): ONE device-memory poll
                    # per sync window, phase-attributed, written as one
                    # `memory` record; the running peak rides this
                    # host's heartbeat under the unified name
                    obs_writer.event("memory",
                                     **mem_ledger.sample("step", step=i))
                    # flight recorder: persist this window's spans and
                    # stamp the heartbeat with the rank's current phase
                    # — the `watch` per-rank "where is it" column
                    timeline_mod.flush()
                    # input-service backpressure rides the heartbeat:
                    # ring occupancy now + consumer-wait delta this
                    # window, so a starved host is visible fleet-wide
                    hb_input = ({"input": svc_client.window_stats()}
                                if svc_client is not None else {})
                    fleet_writer.heartbeat(
                        step=hb_step, step_ewma_ms=ewma_ms,
                        mem_peak_bytes=mem_ledger.peak_bytes or None,
                        phase=timeline_mod.current_phase(),
                        **hb_input)
                    if world > 1:
                        skew = obs_fleet.straggler_gather(hb_step, ewma_ms)
                        if skew is not None:
                            obs_writer.event("straggler", step=i, **skew)
            if (cfg.train_dir and cfg.save_model_steps
                    and i % cfg.save_model_steps == 0
                    and i < cfg.num_batches):
                # NOTE: saving fetches the full state — it syncs the
                # device and perturbs the throughput measurement around
                # this step
                if tracker is not None:
                    # settle the guard first: under rewind the state may
                    # carry un-detected poisoned updates mid-window, and
                    # persisting them would make the poisoned checkpoint
                    # the one rewind restores (the save syncs on the
                    # state anyway, so the flush is free)
                    _settle_guard(i)
                save_now(i)
            trace_window.poll(timeline.fetcher.fetched_step)
    except BaseException:
        if dog is not None:
            dog.stop()
        raise
    finally:
        preempt_h.uninstall()
    losses: list[float] = []
    nonfinite_display: list[int] = []

    def line(i: int, rate: float, v) -> None:
        loss = float(np.asarray(v))
        losses.append(loss)
        if not np.isfinite(loss):
            nonfinite_display.append(i)
        print_fn(f"{i}\t{units}/sec: {rate:.1f}\tloss: {loss:.3f}")
        obs_writer.event("window", step=i, rate=rate,
                         step_ms=1e3 * global_batch / rate, loss=loss)

    try:
        # the watchdog stays armed THROUGH the drain: up to max_inflight
        # steps are still executing when the loop exits, and a collective
        # that deadlocks in that tail would otherwise hang finish()
        # forever with no stack dump (arrivals keep advancing during a
        # healthy drain, so no false positive)
        total_time = timeline.finish(line)
    finally:
        if dog is not None:
            dog.stop()
    trace_window.stop()     # no-op if the in-loop poll already stopped it
    if policy == "abort" and nonfinite_display:
        # the default non-finite policy: fail the run loudly (the
        # display-step losses the timeline already fetches are the
        # zero-cost detector) instead of printing a NaN table and
        # exiting 0 the way the reference would
        obs_writer.event("nonfinite_abort", steps=nonfinite_display[:16])
        _flush_async_for_exit()
        phases.end(step=cfg.num_batches)
        obs_writer.close()
        fleet_writer.close()
        timeline_mod.detach()
        raise guards_mod.NonFiniteError(
            f"non-finite loss at display step(s) "
            f"{nonfinite_display[:16]} (--on_nonfinite=abort; use skip "
            f"or rewind to survive, or inspect the data/lr)")
    if cfg.train_dir:
        save_now(cfg.num_batches)       # final state (tf_cnn train_dir)
    if async_ckpt is not None:
        # exit barrier: the final overlapped write must land (and any
        # background write error must surface) before the run reports
        # success; the wait is accounted as checkpoint_async blocking
        phases.enter("checkpoint_async", step=cfg.num_batches)
        async_ckpt.wait()
        _drain_async_commits()
    phases.end(step=cfg.num_batches)
    ledger = phases.ledger()
    total_rate = cfg.num_batches * global_batch / total_time
    per_chip = total_rate / layout.total_workers
    mean_ms = 1e3 * total_time / cfg.num_batches
    p50_ms = timeline.p50_step_ms()
    p50_gran = timeline.p50_granularity

    # MFU (obs.efficiency): the measured cost_analysis() figure when the
    # AOT probe ran, the analytic table (fwd+bwd ~= 3x forward FLOPs;
    # forward-only 1x) otherwise — source labeled, both recorded, loud
    # when they disagree >10%.  The background probe has had the whole
    # timed loop to finish; the join here is normally instant.
    measured_flops = (flops_probe.result() if flops_probe is not None
                      else None)
    flops_mult = 1.0 if cfg.forward_only else 3.0
    peak = hw.peak_flops(dtype=cfg.compute_dtype)
    analytic_step_flops = (flops_mult * spec.flops_per_example
                           * global_batch / layout.total_workers)
    mfu_rep = obs_efficiency.mfu_report(
        measured_flops, analytic_step_flops, mean_ms / 1e3, peak)

    result = BenchmarkResult(
        model=cfg.model,
        total_workers=layout.total_workers,
        global_batch=global_batch,
        total_images_per_sec=total_rate,
        images_per_sec_per_chip=per_chip,
        mean_step_ms=mean_ms,
        p50_step_ms=p50_ms,
        p50_step_granularity=p50_gran,
        mfu=mfu_rep["mfu"],
        final_loss=losses[-1] if losses else float("nan"),
        fabric=fab.value,
        goodput=ledger.goodput if ledger is not None else float("nan"),
        goodput_phases=({k: round(v, 3)
                         for k, v in ledger.seconds.items() if v > 0.0}
                        if ledger is not None else None),
        data_wait_frac=(ledger.seconds.get("data_wait", 0.0)
                        / ledger.wall_s
                        if ledger is not None and ledger.wall_s > 0
                        else float("nan")),
        input_service=(svc_client is not None
                       if cfg.data_dir is not None and not spec.is_text
                       else None),
        mfu_source=mfu_rep["mfu_source"],
        flash_tile_share=(flash_plan.computed_share
                          if flash_plan is not None else None),
        resume=resume_rec,
    )
    tsum = trace_window.post_summary()
    trace_rec = None
    if tsum is not None:
        from tpu_hc_bench.obs import trace as obs_trace

        # per-collective-kind split so the ceiling attribution can name
        # the collective, not just "collective time"
        coll_ops: dict[str, float] = {}
        overlap_rec = None
        try:
            # ONE trace load + track split serves both consumers
            # (profile traces run to hundreds of MB of JSON): the
            # per-kind durations fold from the same leaf intervals the
            # --overlap_grad_comm exposure attribution walks
            intervals = obs_trace.leaf_intervals(
                obs_trace.load_events(cfg.trace_dir))
            ops: dict[str, float] = {}
            for name, s, e in intervals:
                ops[name] = ops.get(name, 0.0) + (e - s)
            coll_ops = obs_efficiency.collective_kind_times(ops)
            overlap_rec = obs_efficiency.collective_overlap(intervals)
        except Exception:
            if jax.default_backend() == "tpu":
                raise       # same rule as the bucket summary above
        trace_rec = {"buckets": tsum.totals, "steps": len(tsum.steps),
                     "collective_ops": coll_ops}
        if overlap_rec is not None:
            trace_rec["overlap"] = overlap_rec
            for ln in obs_efficiency.overlap_lines(overlap_rec):
                print_fn(ln.strip())
        obs_writer.event("trace_buckets", **trace_rec)
    if hasattr(ds, "stats"):    # host decode-pool counters (real images)
        obs_writer.event("data", **ds.stats())
    if input_svc is not None:
        # host-level backpressure account (ring occupancy percentiles,
        # producer stalls, consumer waits) — the `obs summarize` input
        # line and `obs diff` delta row read this record
        obs_writer.event("input_service", **input_svc.stats())
    if svc_client is not None:
        svc_client.close()
    if input_svc is not None:
        input_svc.stop()
    # final memory sample + the compile-time report (obs.memory): the
    # ledger's high water and its phase ride the summary; the AOT
    # memory_analysis() byte account is cross-checked against the
    # analytic params+opt+batch table (same 10% tripwire as MFU)
    obs_writer.event("memory",
                     **mem_ledger.sample("step", step=cfg.num_batches))
    mem_an = (flops_probe.memory_analysis()
              if flops_probe is not None else None)
    mem_rep = obs_memory.memory_report(mem_an, analytic_mem)
    obs_writer.event("memory_report", **mem_rep)
    result.peak_hbm_bytes = mem_ledger.peak_bytes or None
    result.hbm_bytes_limit = mem_ledger.bytes_limit
    result.mem_source = mem_ledger.source
    result.memory_analysis = mem_an
    # gradient-allreduce wire bytes (the dominant collective): what the
    # fabric-ceiling attribution divides by.  DP/SP/TP psum+GSPMD arms
    # only — PP's pipeline and the host fabric reduce differently.
    summary_fields = dict(result.json_line())
    summary_fields.update(mfu_rep)
    if (not cfg.forward_only and pp == 1
            and fab is not fabric_mod.Fabric.HOST
            and hasattr(state, "params")):
        accum_wire = (cfg.accum_dtype
                      if cfg.gradient_accumulation_steps > 1 else "f32")
        summary_fields["allreduce_bytes_per_step"] = \
            obs_efficiency.grad_allreduce_bytes(state.params, accum_wire)
    # round 24: the per-rank step-time sketch — bucket-wise mergeable
    # across ranks, so a fleet-wide step p50/p99 is one merge away
    step_sk = timeline.step_sketch()
    if step_sk is not None:
        obs_writer.event("latency_sketch", window=0,
                         fields={"step_ms": step_sk.to_record()})
    obs_writer.event("summary", **summary_fields)
    obs_writer.close()
    fleet_writer.close()
    timeline_mod.detach()       # flush the span tail, close spans.<k>.jsonl
    print_fn("-" * 40)
    print_fn(f"total {units}/sec: {total_rate:.2f}")
    # the p50 token names its own granularity: "/step" is a true per-step
    # median; "/N-step-window" admits the marker stream only resolved
    # N-step intervals
    p50_label = ("/step" if p50_gran == 1 else f"/{p50_gran}-step-window")
    print_fn(
        f"{units}/sec/chip: {per_chip:.2f}  step: {mean_ms:.2f}ms "
        f"(p50{p50_label} {p50_ms:.2f}ms)  MFU: {100 * result.mfu:.1f}% "
        f"({result.mfu_source})"
    )
    if mfu_rep.get("flops_disagree"):
        print_fn(obs_efficiency.mfu_lines(mfu_rep)[-1].strip())
    if ledger is not None:
        for ln in ledger.format_lines():
            print_fn(ln)
    for ln in obs_memory.memory_lines(mem_ledger.fold()):
        print_fn(ln.strip())
    if probe_wanted or mem_an:
        # bare runs never created the probe — printing the report's
        # "unavailable on this arm/backend" head there would blame a
        # backend that was simply never asked
        for ln in obs_memory.memory_report_lines(mem_rep):
            print_fn(ln.strip())
    if fabric_ceiling is not None:
        for ln in obs_efficiency.ceiling_utilization_lines(
                summary_fields, trace_rec, fabric_ceiling):
            print_fn(ln.strip())
    return result
