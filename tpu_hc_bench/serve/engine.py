"""Continuous-batching serving engine over AOT-compiled bucket shapes.

The serving lane in three parts.  This module is the programs and their
warm-up: ``ServeEngine`` AOT-compiles every bucket a run can dispatch
(``serve.decode`` builds them) and keeps the device's cache tree.
``serve.loop`` is one run's scheduler — admission, the step, retirement,
telemetry — over its state in one object; ``serve.cache`` is the one
interface to pages, state slots, the ledger and the prefix trie.
``ServeEngine.run`` resolves a run's policy, plays one ``ServeLoop`` and
summarizes it.  The lane's design constraints, in order:

1. **Zero lowering after warmup.**  Every shape the engine can ever run
   — one prefill program per prompt-length bucket, one decode program
   per batch bucket, one classify program per batch bucket — is
   AOT-compiled at construction through ``obs.efficiency.aot_compile``
   (the ``StepFlopsProbe`` lowering path, so the persistent compile
   cache warms them across runs).  After warmup the engine only calls AOT
   executables: an off-ladder shape *raises* instead of recompiling,
   and the ``serve-bucket-recompile`` analysis lint guards the source
   so no jit/lower call site creeps into the traffic path.  Measured
   the same way as the round-10 hit/miss banner: compile-cache entry
   deltas, re-counted after traffic (``post_warmup_compiles``).
2. **Continuous batching** (Orca): admission and retirement happen per
   decode step.  A newly arrived request is prefilled as soon as a
   slot and pages are free, joins the running batch at the next step,
   and retires the step it hits its output budget — short requests are
   never held hostage to long batchmates.  ``--batching=static`` is
   the classic control arm: collect a full batch, run it to
   completion, only then admit again.
3. **Paged KV cache** (vLLM): requests hold page tables into one
   shared pool, not max-length slabs.  Allocation is conservative —
   a request's worst-case page count is reserved at admission — and
   under ``--kv_preempt=on`` a starved admit preempts the resident
   with the most pages per token of progress, frees its pages, and
   requeues it carrying its generated prefix: re-admission re-prefills
   prompt+prefix, so no token is lost across residencies (round 23;
   the admission half of the ROADMAP on-demand-paging item).
4. **Graceful degradation** (round 23): deadline-aware load shedding
   (``--shed`` against ``--deadline_ms``), per-request quarantine of
   non-finite logits, a SIGTERM drain that journals every unfinished
   request for ``--serve_resume``, and a scheduler-iteration watchdog
   (``--serve_step_timeout_s``) — overload and faults degrade the
   answer set, never the process.  Every knob defaults off, and the
   off path adds no host transfers: the determinism and zero-lowering
   pins ride on an unarmed ``run()`` staying byte-identical.

Timing goes through an injectable clock so tests drive the closed
loop in virtual time (``VirtualClock``): real runs measure wall
seconds, virtual runs charge a deterministic modeled cost per step
kind and make ``sleep`` instant — same scheduler code path either way.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Callable

import numpy as np

from tpu_hc_bench.flags import BenchmarkConfig, parse_serve_buckets
from tpu_hc_bench.obs import efficiency as obs_efficiency
from tpu_hc_bench.obs import metrics as obs_metrics
from tpu_hc_bench.obs import timeline as timeline_mod
from tpu_hc_bench.serve import faults as faults_mod
from tpu_hc_bench.serve.arrivals import Request


def ceil_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def pick_bucket(ladder: tuple[int, ...], n: int) -> int:
    """Smallest bucket >= n (admission control guarantees one exists)."""
    for b in ladder:
        if b >= n:
            return b
    raise ValueError(f"no bucket >= {n} in ladder {ladder} — admission "
                     f"control should have clamped this")


class MonotonicClock:
    """Real time: the closed-loop benchmark clock."""

    def now(self) -> float:
        return time.monotonic()

    def sleep(self, dt: float) -> None:
        if dt > 0:
            time.sleep(dt)

    def charge(self, kind: str, real_s: float) -> None:
        # real compute already advanced now(); nothing to model
        del kind, real_s


class VirtualClock:
    """Deterministic test clock: ``sleep`` is instant (time jumps) and
    each engine step advances time by ``costs[kind]`` — or by the real
    measured seconds when the kind has no modeled cost, so a cost-free
    VirtualClock still yields compute-shaped (just sleep-free) time."""

    def __init__(self, costs: dict[str, float] | None = None):
        self.t = 0.0
        self.costs = dict(costs or {})

    def now(self) -> float:
        return self.t

    def sleep(self, dt: float) -> None:
        self.t += max(0.0, dt)

    def charge(self, kind: str, real_s: float) -> None:
        self.t += self.costs.get(kind, real_s)


class ServeEngine:
    """One model's serving engine: compiled buckets + scheduler.

    Construction compiles every bucket (the warmup); ``run`` plays a
    request trace through either batching arm.  One engine instance
    serves any number of runs — arms share the warmed executables, so
    the A/B never pays a second compile.
    """

    def __init__(self, cfg: BenchmarkConfig,
                 print_fn: Callable[[str], None] = print):
        import jax
        import jax.numpy as jnp

        from tpu_hc_bench.models import get_model_spec, create_model
        from tpu_hc_bench.utils import compile_cache, hw

        hw.require_accelerator(cfg.virtual_devices)
        if cfg.workload != "serve":
            raise ValueError(
                "ServeEngine needs a workload='serve' config (use "
                "flags.parse_flags(argv, workload='serve') or set the "
                "field before resolve())")
        self.cfg = cfg
        self.print_fn = print_fn
        self._jnp = jnp

        # persistent compile cache first, so the warmup compiles hit or
        # populate it (the same resolver as the training lane)
        self.cache_dir = compile_cache.resolve(cfg.compile_cache)
        self._count_cache = (
            (lambda: compile_cache.entry_count(self.cache_dir))
            if self.cache_dir else (lambda: 0))
        entries_before = self._count_cache()

        spec = get_model_spec(cfg.model)
        if spec.is_text and not spec.causal_lm:
            raise ValueError(
                f"--model {cfg.model}: MLM members have no "
                "autoregressive serving story; serve a decoder family "
                "(gpt2*/moe*/llama*) or a classify member")
        self.decode_mode = bool(spec.causal_lm)
        self.max_ctx = cfg.max_prompt_len + cfg.max_output_len
        # decode-kernel/quant arms (round 18) are decode-lane knobs;
        # a classify member accepting them would be the silent-no-op
        # flag the lane contract forbids
        self.decode_attention = cfg.decode_attention
        self.quant = cfg.quant
        self.block_pages = cfg.decode_block_pages or 1
        if not self.decode_mode and (
                cfg.decode_attention != "gather" or cfg.quant != "off"
                or cfg.decode_block_pages):
            raise ValueError(
                f"--model {cfg.model} serves single-forward classify "
                "requests; --decode_attention/--quant/"
                "--decode_block_pages shape the paged decode step and "
                "have no meaning here")
        if not self.decode_mode and (
                cfg.kv_reserve != "worst" or cfg.prefix_cache != "off"):
            raise ValueError(
                f"--model {cfg.model} serves single-forward classify "
                "requests with no KV pool; --kv_reserve/--prefix_cache "
                "shape paged-decode admission and have no meaning here")

        dtype = jnp.dtype(cfg.compute_dtype)
        if self.decode_mode:
            self.model, self.spec = create_model(
                cfg.model, dtype=dtype, seq_len=self.max_ctx)
        else:
            self.model, self.spec = create_model(
                cfg.model, num_classes=cfg.num_classes, dtype=dtype)

        rng = jax.random.PRNGKey(cfg.seed)
        if self.decode_mode:
            example = jnp.zeros((1, min(8, self.max_ctx)), jnp.int32)
        else:
            example = jnp.zeros((1,) + tuple(self.spec.input_shape),
                                jnp.float32)
        self.variables = self.model.init(rng, example, train=False)
        if self.decode_mode and cfg.use_fp16 and not any(
                x.dtype == dtype
                for x in jax.tree_util.tree_leaves(self.variables)):
            # --use_fp16 serves in bfloat16: the matrices are HELD so,
            # vectors (norm scales, biases) stay float32.  A family that
            # declares its own parameter types already holds them so
            self.variables = jax.tree_util.tree_map(
                lambda x: x.astype(dtype) if x.ndim >= 2 else x,
                self.variables)
        self.params = self.variables.get("params", self.variables)

        # --- bucket ladders + KV pool geometry ---
        self.batch_buckets = parse_serve_buckets(cfg.serve_buckets,
                                                 cfg.max_in_flight)
        self.cap = min(cfg.max_in_flight, max(self.batch_buckets))
        if self.cap < cfg.max_in_flight:
            print_fn(f"serve: max_in_flight clamped to the top decode "
                     f"bucket: {cfg.max_in_flight} -> {self.cap}")
        ladder = []
        s = min(8, ceil_pow2(cfg.max_prompt_len))
        while s < cfg.max_prompt_len:
            ladder.append(s)
            s *= 2
        # the top bucket never exceeds max_ctx: the models' position
        # tables are max_ctx rows, and an oversized bucket would both
        # compile a wider program than any request needs and rely on
        # XLA's out-of-bounds gather clamping for the pad positions
        ladder.append(min(s, self.max_ctx))
        self.prefill_buckets = tuple(ladder)
        self.page_size = cfg.kv_page_size
        self.table_width = -(-self.max_ctx // self.page_size)
        self.num_pages = cfg.kv_pages or (1 + self.cap * self.table_width)
        if self.decode_mode and self.num_pages < 1 + self.table_width:
            # classify members never allocate the pool, so an explicit
            # --kv_pages must not crash their (KV-free) construction
            raise ValueError(
                f"--kv_pages={cfg.kv_pages} cannot hold even one request "
                f"(need {1 + self.table_width}: a trash page + "
                f"{self.table_width} pages of {self.page_size} tokens "
                f"for prompt+output {self.max_ctx})")

        # a family with a recurrent-state pool: cap + 1 slots (slot 0
        # the trash slot), the slot index in one more table column
        self.state_slots = 0
        self.state_pool_bytes = 0
        self.op_parts: dict = {}
        # gather arm: pages a chunk of each decode bucket's cache read
        self.decode_chunk: dict[int, int] = {}

        # --- warmup: AOT-compile every bucket ---
        self.compiled: dict[tuple[str, int], Any] = {}
        # the device's cache tree (None: a classify member keeps none);
        # every decode-lane program takes it and returns it
        self._kv = None
        self.lower_count = 0
        # pool geometry bytes (round 22: the serve summary renders the
        # configured pool beside the utilization line) — measured off
        # the actual device arrays at warmup, None for classify members
        self.kv_pool_bytes: int | None = None
        self.kv_scale_bytes = 0
        t0 = time.perf_counter()
        if self.decode_mode:
            self._warm_decode()
        else:
            self._warm_classify()
        warm_s = time.perf_counter() - t0
        self.entries_after_warmup = self._count_cache()
        self.compile_record = {
            "buckets": len(self.compiled),
            "warmup_s": round(warm_s, 3),
            "cache_dir": self.cache_dir,
            "entries_before": entries_before,
            "entries_after_warmup": self.entries_after_warmup,
            "new_entries": self.entries_after_warmup - entries_before,
            "warm": (self.entries_after_warmup == entries_before
                     and entries_before > 0),
            "decode_attention": (self.decode_attention
                                 if self.decode_mode else None),
            "quant": self.quant,
            # block pages only exist on the paged arm: reporting the
            # coerced 1 under gather would render a knob resolve()
            # itself rejects there
            "decode_block_pages": (
                self.block_pages if self.decode_mode
                and self.decode_attention == "paged" else None),
        }
        if self.decode_mode:
            _, worst_decode = self.aot_memory_worst(kinds=("decode",))
            self.compile_record["aot_decode_temp_bytes"] = (
                worst_decode.get("temp_bytes") if worst_decode else None)
            arm = (f"serve decode arm: attention={self.decode_attention} "
                   f"quant={self.quant}")
            if self.decode_attention == "paged":
                arm += f" block_pages={self.block_pages}"
            tb = self.compile_record["aot_decode_temp_bytes"]
            if tb is not None:
                arm += (f"; worst decode bucket AOT temp "
                        f"{tb / 2**20:.1f} MiB")
            ratio = self.kv_pool_temp_ratio()
            self.compile_record["kv_pool_temp_ratio"] = ratio
            if ratio is not None:
                arm += f"; kv_pool_temp_ratio {ratio:.3f}"
            print_fn(arm)
        devs = jax.local_devices()
        print_fn(
            f"serve device: {devs[0].device_kind} d{devs[0].id}"
            + (f" (1 of {len(devs)} local devices: the serve lane is "
               f"single-device)" if len(devs) > 1 else ""))
        kinds = collections.Counter(k for k, _ in self.compiled)
        print_fn(
            "serve warmup: "
            + ", ".join(f"{n} {k} bucket(s)" for k, n in sorted(
                kinds.items()))
            + f" AOT-compiled in {warm_s:.1f}s"
            + (f"; compile cache: "
               f"{self.compile_record['new_entries']} new entr"
               f"{'y' if self.compile_record['new_entries'] == 1 else 'ies'}"
               f" ({'warm start' if self.compile_record['warm'] else 'cold/partial'})"
               if self.cache_dir else ""))
        self._check_hbm_budget(print_fn)

    def aot_memory_worst(self, kinds=None) -> tuple:
        """``(bucket key, memory_analysis dict)`` of the warmed
        ladder's worst bucket by AOT total bytes, optionally limited to
        the given program kinds (``("decode",)`` isolates the decode
        arm the kernel A/B moves) — ``(None, None)`` where the backend
        exposes no analysis."""
        from tpu_hc_bench.obs import memory as obs_memory

        worst, worst_key = None, None
        for key, compiled in self.compiled.items():
            if kinds is not None and key[0] not in kinds:
                continue
            ma = obs_memory.memory_analysis_of_compiled(compiled)
            if ma and (worst is None
                       or ma["total_bytes"] > worst["total_bytes"]):
                worst, worst_key = ma, key
        return worst_key, worst

    def kv_pool_temp_ratio(self) -> float | None:
        """The largest AOT ``temp`` bytes over the decode and prefill
        programs, in pool leaves (the cache tree's largest: one of K /
        V, or the recurrent state).  A program that holds
        a second copy of a leaf — a re-layout of the pool around its
        gather or its write — reads >= 1; one that reads and writes the
        pool where it rests holds a layer's gathered rows at most.
        None where the backend exposes no analysis."""
        from tpu_hc_bench.obs import memory as obs_memory

        temps = []
        for (kind, _), compiled in self.compiled.items():
            if kind in ("decode", "prefill"):
                ma = obs_memory.memory_analysis_of_compiled(compiled)
                if ma and "temp_bytes" in ma:
                    temps.append(ma["temp_bytes"])
        if not temps:
            return None
        import jax

        return round(max(temps) / max(
            x.nbytes for x in jax.tree_util.tree_leaves(self._kv)), 4)

    def _check_hbm_budget(self, print_fn) -> None:
        """``--hbm_budget`` in the serving lane: the warmed ladder's
        worst bucket (by AOT ``memory_analysis`` total — arguments
        include the params and the whole KV pool) against the budget,
        verdict printed BEFORE traffic.  A shared flag that parsed but
        never checked anything would be the silent-no-op knob the lane
        contract forbids."""
        if self.cfg.hbm_budget is None:
            return
        from tpu_hc_bench.obs import memory as obs_memory

        budget_bytes, note = obs_memory.resolve_hbm_budget_bytes(
            obs_memory.parse_hbm_budget(self.cfg.hbm_budget))
        worst_key, worst = self.aot_memory_worst()
        for ln in obs_memory.budget_lines(
                worst, budget_bytes, note,
                advice="shrink --serve_buckets/--max_in_flight, "
                       "--kv_pages, or --max_prompt_len/--max_output_len"):
            print_fn(ln + (f" [worst bucket: {worst_key[0]} "
                           f"{worst_key[1]}]"
                           if worst_key and budget_bytes else ""))
        self.compile_record["hbm_budget"] = {
            "budget_bytes": budget_bytes,
            "worst_bucket": list(worst_key) if worst_key else None,
            "memory_analysis": worst,
        }

    # -- warmup namespace: the ONLY place that may lower/compile --------

    def _aot(self, key: tuple[str, int], fn, *example, donate=()):
        import jax

        if jax.default_backend() == "cpu":
            donate = ()             # CPU backend: donation unimplemented,
                                    # avoid the per-compile warning
        jitted = jax.jit(fn, donate_argnums=donate)
        self.lower_count += 1
        self.compiled[key] = obs_efficiency.aot_compile(jitted, *example)

    def _warm_decode(self) -> None:
        from tpu_hc_bench.serve import decode as decode_mod

        jnp = self._jnp
        self.family = decode_mod.build_family(self.model,
                                              quant=self.quant)
        stateful = bool(self.family.state_layers)
        if stateful:
            # the file's own rule: an unsupported combination fails at
            # construction, never mid-traffic
            if self.decode_attention != "gather":
                raise ValueError(
                    f"--model {self.cfg.model} keeps a recurrent state "
                    "beside its KV pages; --decode_attention=paged has "
                    "no kernel that knows that cache tree")
            if self.cfg.prefix_cache != "off":
                raise ValueError(
                    f"--model {self.cfg.model}: --prefix_cache=on would "
                    "share K/V pages without the recurrent state that "
                    "belongs to the same prefix (no state snapshots "
                    "yet); sharing is refused for this family")
            self.state_slots = self.cap + 1
        # int8_w: the decode programs read the quantized tree; the
        # original f32 params stay on self.params (parity tests read
        # them for the full-forward reference)
        self.exec_params = (
            decode_mod.quantize_weights(self.family, self.params)
            if self.quant == "int8_w" else self.params)
        self._kv = decode_mod.init_kv_state(
            self.family, self.num_pages, self.page_size,
            jnp.dtype(self.cfg.compute_dtype), quant=self.quant,
            slots=self.state_slots)
        import jax

        leaves = jax.tree_util.tree_leaves(self._kv)
        self.kv_pool_bytes = int(sum(x.nbytes for x in leaves))
        if stateful:
            self.state_pool_bytes = int(sum(
                x.nbytes
                for x in jax.tree_util.tree_leaves(self._kv["state"])))
            self.kv_pool_bytes -= self.state_pool_bytes
        if self.quant == "int8_kv":
            # the per-(layer, page) f32 scale planes ride the pool
            # bytes — int8 pages without their scales would undercount
            self.kv_scale_bytes = int(sum(
                x.nbytes for x in leaves if x.dtype == jnp.float32))
        w = self.table_width
        # the table handed to the programs: the pages, then the slot
        self.table_cols = cols = w + (1 if stateful else 0)
        for s in self.prefill_buckets:
            fn = decode_mod.build_prefill_fn(
                self.family, self.page_size, w, quant=self.quant)
            self._aot(("prefill", s), fn, self.exec_params, self._kv,
                      np.zeros((1, s), np.int32), np.int32(1),
                      np.zeros((cols,), np.int32), donate=(1,))
        for b in self.batch_buckets:
            fn = decode_mod.build_decode_fn(
                self.family, self.page_size, w,
                attention=self.decode_attention, quant=self.quant,
                block_pages=self.block_pages)
            self._aot(("decode", b), fn, self.exec_params, self._kv,
                      np.zeros((b,), np.int32),
                      np.zeros((b, cols), np.int32),
                      np.zeros((b,), np.int32), np.zeros((b,), bool),
                      donate=(1,))
        if self.decode_attention == "gather":
            # pages a chunk of each bucket's packed cache read visits:
            # what the programs compute from the same shapes
            self.decode_chunk = {
                b: decode_mod.chunk_pages(leaves[0], b, w)
                for b in self.batch_buckets}
        # round 25: the one COW program — page-count-shaped, not
        # bucket-shaped, so a single warmup covers every copy the
        # prefix cache can ever trigger (zero lowering after warmup)
        self._aot(("page_copy", 0), decode_mod.build_page_copy_fn(),
                  self._kv, np.int32(0), np.int32(0), donate=(0,))
        if stateful:
            # which named part (kda / gqa / moe / head) each operation
            # of each program belongs to, keyed as a device trace names
            # it: the trace's events carry the instruction, not the scope
            self.op_parts = {
                f"{kind}@{n}": decode_mod.part_of_ops(c.as_text())
                for (kind, n), c in self.compiled.items()
                if kind in ("prefill", "decode")}

    def _warm_classify(self) -> None:
        model = self.model

        def classify(variables, x):
            return self._jnp.argmax(
                model.apply(variables, x, train=False), axis=-1)

        shape = tuple(self.spec.input_shape)
        for b in self.batch_buckets:
            self._aot(("classify", b), classify, self.variables,
                      np.zeros((b,) + shape, np.float32))

    # -- traffic path: AOT executables only -----------------------------

    def classify_input(self, req: Request) -> np.ndarray:
        rng = np.random.default_rng((self.cfg.seed, 13, req.rid))
        return rng.standard_normal(
            tuple(self.spec.input_shape)).astype(np.float32)

    def run(self, requests: list[Request], batching: str | None = None,
            writer: obs_metrics.MetricsWriter | None = None,
            clock=None, fleet=None, *, faults=None, shed=None,
            deadline_ms=None, kv_preempt=None, kv_reserve=None,
            prefix_cache=None, journal_path=None,
            drain_handler=None, step_timeout_s=None,
            on_watchdog=None) -> dict:
        """Play a request trace; returns the serve summary record.

        Deterministic given (engine seed, trace, clock): greedy decode,
        counter-keyed synthesis, and arrival-ordered admission leave no
        hidden state between runs — arms share one warmed engine.

        ``fleet`` is an optional ``obs.fleet.FleetWriter``: when given
        (``serve/cli.run_serve`` wires one on metrics runs) the engine
        heartbeats at the serve-record cadence with the pool high-water
        under ``kv_peak_pages``, so ``obs watch``'s fleet view shows
        per-host KV pressure the same way it shows ``mem_peak_bytes``.

        The keyword-only degradation knobs (round 23) override their
        config twins per run, so tests and the faults A/B drive policy
        arms through ONE warmed engine — a second warmup per arm would
        break the zero-compile contract.  A ``faults`` plan is
        consumed as it fires (one-shot hooks): pass a fresh
        ``faults.parse_serve_plan`` result per run.  ``drain_handler``
        replaces the engine's own SIGTERM/SIGINT handler (tests poll a
        fake); ``on_watchdog`` replaces the watchdog's ``os._exit``.
        """
        from tpu_hc_bench.serve import loop as loop_mod

        if faults is None and self.cfg.serve_faults:
            faults = faults_mod.parse_serve_plan(self.cfg.serve_faults)
        policy = loop_mod.RunPolicy.resolve(
            self, batching=batching, shed=shed, deadline_ms=deadline_ms,
            kv_preempt=kv_preempt, kv_reserve=kv_reserve,
            prefix_cache=prefix_cache, faults=faults)
        writer = writer or obs_metrics.MetricsWriter(None)
        # flight recorder: honor --flight_recorder and, on metrics runs,
        # persist this process's spans beside the stream
        timeline_mod.configure(
            enabled=self.cfg.flight_recorder != "off",
            run_dir=getattr(writer, "out_dir", None))
        loop = loop_mod.ServeLoop(
            self, requests, policy, kv=self._kv, writer=writer,
            clock=clock or MonotonicClock(), fleet=fleet, faults=faults,
            journal_path=journal_path)
        loop.play(drain_handler, step_timeout_s, on_watchdog)
        self._kv = loop.kv
        loop.close()
        entries_final = self._count_cache()
        summary = loop_mod.summarize(
            loop, entries_final - self.entries_after_warmup)
        writer.event("serve_summary", **summary)
        writer.event("serve_compile", **self.compile_record,
                     entries_final=entries_final,
                     post_warmup_compiles=summary["post_warmup_compiles"])
        timeline_mod.detach()   # flush the serve spans, close the file
        # each program's operations by named part (what a device trace
        # calls them -> kda / gqa / moe / head): for the caller that
        # reduces a trace, too large for the stream's summary record
        summary["op_parts"] = self.op_parts
        return summary
