"""Continuous-batching serving engine over AOT-compiled bucket shapes.

The scheduler half of the serving lane (``serve.decode`` is the program
half).  Design constraints, in order:

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
import dataclasses
import os
import time
from typing import Any, Callable

import numpy as np

from tpu_hc_bench.flags import BenchmarkConfig, parse_serve_buckets
from tpu_hc_bench.obs import efficiency as obs_efficiency
from tpu_hc_bench.obs import kv as kv_mod
from tpu_hc_bench.obs import metrics as obs_metrics
from tpu_hc_bench.obs import requests as requests_mod
from tpu_hc_bench.obs import timeline as timeline_mod
from tpu_hc_bench.obs import signals as signals_mod
from tpu_hc_bench.obs import sketch as sketch_mod
from tpu_hc_bench.resilience import preempt as preempt_mod
from tpu_hc_bench.resilience import watchdog as watchdog_mod
from tpu_hc_bench.serve import faults as faults_mod
from tpu_hc_bench.serve import slo as slo_mod
from tpu_hc_bench.serve.arrivals import Request

# serve records land every this-many engine steps — frequent enough for
# `obs watch` to show a live queue, rare enough to stay O(run)/stream
_SERVE_RECORD_EVERY = 16

# round 24: the retained-request-record cap.  Percentiles stream
# through the mergeable sketch (exact over the whole run, bounded
# buckets); the raw record ring only feeds the folds that genuinely
# need per-request rows (tail attribution, burn-rate windows, the KV
# honesty gap), which degrade gracefully to the freshest N under a
# week-long serve instead of growing without bound.
_DONE_SAMPLE_CAP = 4096


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


class PageAllocator:
    """Refcounted free-list allocator over the KV page pool; page 0 is
    the reserved trash page (padded/inactive rows read and write it)
    and is never handed out.

    Round 25 makes pages a SHARED resource: a physical page can be
    held by several requests (a prefix-cache hit) and by the cache
    itself, so every holder takes a reference (``alloc``/``share``)
    and drops it through ``free`` — a page returns to the free list
    only when its last holder lets go.  All page-table stores and
    free-list motion live inside this class (``bind`` is the one
    sanctioned table store); the ``page-refcount-discipline`` lint
    pins that invariant at the source level, because a bare
    ``free_list.append`` beside a nonzero refcount is exactly the
    silent-corruption class COW introduces.

    Counter semantics (the r22 ``obs timeline`` counter track reads
    these, so they must stay honest):

    - ``recycled`` counts a page handed out again by ``alloc`` after a
      genuine free — the pool-churn signal a leak (pages freed but
      never reused) hides.
    - ``cow_copies`` counts copy-on-write page duplications
      (``cow_alloc``).  A COW is NOT a recycle: the page it pops was
      already churned through ``alloc``'s account when it last left
      the free list, and folding copies into ``recycled`` would read
      as pool churn when it is sharing traffic.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(
                f"KV pool needs >= 2 pages (one is the reserved trash "
                f"page): {num_pages}")
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))
        self.pages_peak = 0
        self.recycled = 0
        self.cow_copies = 0
        self._ever_used = [False] * num_pages
        self._refcount = [0] * num_pages

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.num_pages - 1 - len(self._free)

    def _take(self, count_recycle: bool) -> int:
        p = self._free.pop()
        self._refcount[p] = 1
        if self._ever_used[p]:
            if count_recycle:
                self.recycled += 1
        else:
            self._ever_used[p] = True
        return p

    def alloc(self, n: int) -> list[int] | None:
        if n > len(self._free):
            return None
        out = [self._take(count_recycle=True) for _ in range(n)]
        if self.used_pages > self.pages_peak:
            self.pages_peak = self.used_pages
        return out

    def cow_alloc(self) -> int | None:
        """One page for a copy-on-write duplication: counted under
        ``cow_copies``, never ``recycled`` (see class docstring)."""
        if not self._free:
            return None
        p = self._take(count_recycle=False)
        self.cow_copies += 1
        if self.used_pages > self.pages_peak:
            self.pages_peak = self.used_pages
        return p

    def share(self, pages: list[int]) -> None:
        """One additional reference per page (a prefix-cache hit or
        the cache's own retention hold)."""
        for p in pages:
            assert self._refcount[p] > 0, f"share of unheld page {p}"
            self._refcount[p] += 1

    def refcount(self, page: int) -> int:
        return self._refcount[page]

    def free(self, pages: list[int]) -> None:
        """Drop one reference per page; a page rejoins the free list
        at refcount zero (sole-holder frees behave exactly like the
        pre-r25 allocator)."""
        for p in pages:
            assert self._refcount[p] > 0, f"free of unheld page {p}"
            self._refcount[p] -= 1
            if self._refcount[p] == 0:
                self._free.append(p)

    def bind(self, table: np.ndarray, slot: int, page: int) -> None:
        """The one sanctioned page-table store: point ``table[slot]``
        at a page this allocator has handed out and still tracks."""
        assert self._refcount[page] > 0, f"bind of unheld page {page}"
        table[slot] = page


class SlotAllocator:
    """Free list over the recurrent-state slots of a family whose cache
    tree has a ``state`` pool (``serve.decode``): a request owns one
    slot from admit to finish; slot 0 is the reserved trash slot
    (inactive rows name it) and is never handed out.  A slot is never
    shared and never scrubbed: the prefill program starts every
    residency from a zero state whatever the slot held."""

    def __init__(self, num_slots: int):
        if num_slots < 2:
            raise ValueError(f"state pool needs >= 2 slots (one is the "
                             f"trash slot): {num_slots}")
        self.num_slots = num_slots
        self._idle = list(range(num_slots - 1, 0, -1))

    @property
    def free_slots(self) -> int:
        return len(self._idle)

    def alloc(self) -> int | None:
        return self._idle.pop() if self._idle else None

    def free(self, slot: int) -> None:
        assert 0 < slot < self.num_slots and slot not in self._idle, (
            f"free of unheld slot {slot}")
        self._idle.append(slot)


class CacheManager:
    """What a resident request holds of the cache tree, behind one seam:
    pages from the ``PageAllocator`` and, for a family with a state
    pool, one slot from the ``SlotAllocator``.  Admission binds both,
    and every exit of the ledger (finish, shed, quarantine, preempt,
    drain) gives both back through ``release``."""

    def __init__(self, allocator: PageAllocator,
                 slots: SlotAllocator | None):
        self.allocator, self.slots = allocator, slots

    def slot_free(self) -> bool:
        return self.slots is None or self.slots.free_slots > 0

    def bind_slot(self) -> int:
        """The request's slot (0 where the family keeps no state)."""
        if self.slots is None:
            return 0
        slot = self.slots.alloc()
        assert slot is not None, "admission checked slot_free"
        return slot

    def release(self, fl: "_InFlight") -> None:
        self.allocator.free(fl.pages)
        if self.slots is not None and fl.slot:
            self.slots.free(fl.slot)
            fl.slot = 0


class KVLedger:
    """Round 22 (obs.kv): the KV-pool utilization ledger — pages
    reserved by admission vs pages actually written, integrated over
    step wall into the page-seconds behind ``kv_pool_util``.

    Writer-side bookkeeping, by declared limit: "written" is inferred
    from scheduler state (prompt length at admit, one token per decode
    step), not device introspection — the compiled programs do write
    those slots, but nothing here reads HBM back.  Every update is a
    couple of host int/float ops, pinned under the round-17
    1%-of-step-wall guard by test.
    """

    __slots__ = ("page_size", "reserved_now", "written_now",
                 "reserved_page_s", "written_page_s")

    def __init__(self, page_size: int):
        self.page_size = page_size
        self.reserved_now = 0       # pages held by in-flight requests
        self.written_now = 0        # pages with >= 1 written token
        self.reserved_page_s = 0.0
        self.written_page_s = 0.0

    def admit(self, pages_reserved: int, prompt_len: int) -> None:
        self.reserved_now += pages_reserved
        self.written_now += -(-prompt_len // self.page_size)

    def grow(self, n: int = 1) -> None:
        """Round 25 on-demand growth: pages taken mid-flight extend the
        holder's reservation from the moment they are bound (written
        follows through ``token`` when the boundary token lands)."""
        self.reserved_now += n

    def token(self, length_before: int) -> None:
        # one appended token touches a new page iff the pre-append
        # length sits on a page boundary — O(1) per generated token
        if length_before % self.page_size == 0:
            self.written_now += 1

    def retire(self, pages_reserved: int, length: int) -> int:
        """Release a request's pages; returns its final written-page
        count (== peak under worst-case reservation: lengths only grow
        and pages free only at retirement)."""
        final = -(-length // self.page_size)
        self.reserved_now -= pages_reserved
        self.written_now -= final
        return final

    def charge(self, dt: float) -> None:
        self.reserved_page_s += self.reserved_now * dt
        self.written_page_s += self.written_now * dt


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


@dataclasses.dataclass
class _InFlight:
    """Host-side bookkeeping for one admitted request."""

    req: Request
    pages: list[int]
    table: np.ndarray               # int32 [table_width]
    length: int = 0                 # tokens in KV cache
    produced: int = 0               # generated tokens (prefill's counts)
    last_token: int = 0
    t_admit: float = 0.0
    t_first: float | None = None
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    # request-attribution bookkeeping (round 20, obs.requests): summed
    # wall of the decode/classify steps this request was resident for,
    # and the end instant of its last such step — two float stores per
    # resident per step, well under the round-17 recorder guard
    active_s: float = 0.0
    t_last: float | None = None
    # round 23 (KV-pressure preemption): completed residencies, and
    # tokens produced in THIS residency — a re-admitted victim must
    # earn one decode token before it is preemptible again, which is
    # the whole livelock-freedom argument (every residency advances
    # the request by >= 1 token)
    preempts: int = 0
    produced_res: int = 0
    # round 25 (lazy reservation + prefix sharing): pages grown on
    # demand after admission, and page slots admitted pointing at
    # shared prefix-cache pages — the footprint record stamps both
    pages_grown: int = 0
    prefix_shared: int = 0
    # the recurrent-state slot (0 = none: the family keeps no state)
    slot: int = 0


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

        # --- warmup: AOT-compile every bucket ---
        self.compiled: dict[tuple[str, int], Any] = {}
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

    def _timed(self, clock, kind: str, fn, phases, then: str):
        """Run one device program: ``<kind>_dispatch`` is the call until
        it returns, ``<kind>_wait`` the ``block_until_ready``, both under
        the parent span ``kind`` (flight recorder + any open profiler
        trace, real wall even under a VirtualClock); the loop goes on in
        phase ``then``.  The boundaries' own clock reads charge the
        engine clock."""
        import jax

        c0 = clock.now()
        phases.enter(kind + "_dispatch", parent=kind)
        m0 = phases.t
        out = fn()
        phases.enter(kind + "_wait", parent=kind)
        jax.block_until_ready(out)
        phases.enter(then)
        clock.charge(kind, phases.t - m0)
        return out, clock.now() - c0

    def _classify_input(self, req: Request) -> np.ndarray:
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
        batching = batching or self.cfg.batching
        if batching not in ("continuous", "static"):
            raise ValueError(f"batching must be continuous|static: "
                             f"{batching!r}")
        if faults is None and self.cfg.serve_faults:
            faults = faults_mod.parse_serve_plan(self.cfg.serve_faults)
        shed = shed if shed is not None else self.cfg.shed
        kv_preempt = (kv_preempt if kv_preempt is not None
                      else self.cfg.kv_preempt)
        # round 25: the reservation/sharing arms override per run like
        # the other policy knobs — the three-arm kv bench drives all of
        # worst / lazy / lazy+prefix through ONE warmed engine
        kv_reserve = (kv_reserve if kv_reserve is not None
                      else self.cfg.kv_reserve)
        prefix_cache = (prefix_cache if prefix_cache is not None
                        else self.cfg.prefix_cache)
        if kv_reserve not in ("worst", "lazy"):
            raise ValueError(
                f"kv_reserve must be worst|lazy: {kv_reserve!r}")
        if prefix_cache not in ("off", "on"):
            raise ValueError(
                f"prefix_cache must be off|on: {prefix_cache!r}")
        if prefix_cache == "on" and kv_reserve != "lazy":
            raise ValueError(
                "prefix_cache=on requires kv_reserve=lazy (sharing "
                "only saves pages when admission stops reserving the "
                "worst case)")
        if prefix_cache == "on" and self.state_slots:
            raise ValueError(
                f"--model {self.cfg.model}: prefix_cache=on would share "
                "K/V pages without the recurrent state of the same "
                "prefix; refused for this family")
        headroom = self.cfg.kv_growth_headroom
        deadline_ms = (deadline_ms if deadline_ms is not None
                       else (self.cfg.deadline_ms or self.cfg.slo_e2e_ms))
        if shed not in ("off", "admit", "deadline"):
            raise ValueError(f"shed must be off|admit|deadline: {shed!r}")
        if shed != "off" and not deadline_ms:
            raise ValueError(
                "--shed needs a deadline to shed against: set "
                "--deadline_ms (or --slo_e2e_ms, its fallback)")
        deadline_s = (deadline_ms or 0.0) / 1e3
        if not self.decode_mode and (faults or kv_preempt == "on"):
            raise ValueError(
                f"--model {self.cfg.model} serves single-forward "
                "classify requests; --serve_faults/--kv_preempt drive "
                "the paged decode path and have no meaning here")
        if not self.decode_mode and (kv_reserve != "worst"
                                     or prefix_cache != "off"):
            raise ValueError(
                f"--model {self.cfg.model} serves single-forward "
                "classify requests with no KV pool; "
                "--kv_reserve/--prefix_cache have no meaning here")
        # the quarantine guard arms with EITHER policy knob: reading
        # logits back is one host transfer per step that the unarmed
        # lane must not pay (an injected NaN with both knobs off flows
        # through undetected — the faults A/B's control arm)
        guard = shed != "off" or kv_preempt == "on"
        writer = writer or obs_metrics.MetricsWriter(None)
        # flight recorder: honor --flight_recorder and, on metrics runs,
        # persist this process's spans beside the stream
        timeline_mod.configure(
            enabled=self.cfg.flight_recorder != "off",
            run_dir=getattr(writer, "out_dir", None))
        clock = clock or MonotonicClock()
        allocator = PageAllocator(self.num_pages) if self.decode_mode \
            else None
        ledger = KVLedger(self.page_size) if self.decode_mode else None
        cache_mgr = (CacheManager(
            allocator, SlotAllocator(self.state_slots)
            if self.state_slots else None) if self.decode_mode else None)
        # program counters of a family with a state pool / routed
        # experts held as a share (summed from what each decode step
        # returns with its tokens: no extra transfer)
        counters = dict.fromkeys(
            self.family.counters + (("moe_picks",) * bool(
                self.family.picks_per_token)) if self.decode_mode else (), 0)
        state_slot_steps = [0, 0]       # in use, slots x steps
        # round 25: the shared-prefix cache lives per run (it holds
        # references into THIS run's allocator) and its counters feed
        # prefix_hit_frac on the kv_pool record cadence
        cache = None
        if self.decode_mode and prefix_cache == "on":
            from tpu_hc_bench.serve import prefix_cache as prefix_mod

            cache = prefix_mod.PrefixCache(allocator, self.page_size)
        pages_grown_total = 0
        prefix_hits = 0
        prefix_lookups = 0
        prefix_shared_total = 0
        # queue-wait cause split (round 22): rid -> accumulated seconds
        # blocked on [pool_starved, batch_full] while sitting in queue
        wait_causes: dict[int, list[float]] = {}
        # round 23 degradation state: terminal dispositions counted by
        # cause, the preempted-victim carry (rid -> prefix + original
        # lifecycle instants, so the conserved components span both
        # residencies), and the admit-to-done EWMA the predictive shed
        # judges against
        degrade: dict = {"shed": {}, "preempts": 0, "requeues": 0,
                         "quarantined": 0}
        carry: dict[int, dict] = {}
        finished = 0
        service_ewma_s: float | None = None
        squeezed_seen = 0
        drained: dict | None = None
        pending = sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        n = len(pending)
        if self.decode_mode:
            over = [r for r in pending
                    if r.prompt_len > self.cfg.max_prompt_len
                    or r.output_len > self.cfg.max_output_len]
            if over:
                raise ValueError(
                    f"{len(over)} request(s) exceed the compiled ladder "
                    f"(prompt<={self.cfg.max_prompt_len}, "
                    f"output<={self.cfg.max_output_len}); request "
                    f"{over[0].rid} is {over[0].prompt_len}/"
                    f"{over[0].output_len} — shapes outside the warmed "
                    "buckets never run")
            kv = self._kv
        queue: collections.deque[Request] = collections.deque()
        active: list[_InFlight] = []
        # bounded retention (round 24): the freshest N raw records; the
        # sketches below carry the run-lifetime percentiles
        done: collections.deque[dict] = collections.deque(
            maxlen=_DONE_SAMPLE_CAP)
        completed_ok = 0
        run_sk = {f: sketch_mod.QuantileSketch()
                  for f in slo_mod.LATENCY_FIELDS}
        win_sk = {f: sketch_mod.QuantileSketch()
                  for f in slo_mod.LATENCY_FIELDS}
        win_idx = 0
        win_t0 = 0.0
        last_productive = 0.0
        win_stats: dict = {"n": 0, "viol": 0, "blocked": [0.0, 0.0]}
        # live health signals (round 24): hysteresis-gated judgments
        # per record window, appended to signals.jsonl beside the
        # stream; the e2e target is the deadline (or SLO) when set —
        # without one the overload measure is "no evidence", never 0
        sig_engine = signals_mod.SignalEngine()
        sig_target_ms = deadline_ms or self.cfg.slo_e2e_ms or None
        out_dir = getattr(writer, "out_dir", None)
        signals_file = (signals_mod.signals_path(out_dir)
                        if writer.enabled and out_dir else None)
        idx = 0
        steps = {"prefill": 0, "decode": 0, "classify": 0}
        tokens_out = 0
        productive_s = 0.0
        loop_iters = queue_depth_max = queue_depth_sum = 0
        # the loop's wall by exclusive phase (obs.timeline.Phases): the
        # summary's ``loop_phases``, on the real monotonic clock whatever
        # clock drives the scheduler
        phases = timeline_mod.Phases()
        # rid -> engine time of the loop's first look at the request
        # (``queue_unseen_ms``); kept across preempt/requeue
        first_look: dict[int, float] = {}
        # per-(kind,bucket) utilization: key -> [steps, rows, active
        # rows, wall s] — the occupancy heatmap's raw counts
        butil: dict[str, list] = {}
        t0 = clock.now()
        last_record_step = 0
        # the request-lane timeline anchor: engine-relative instants
        # (arrival_s et al.) placed on the wall by `obs timeline`
        writer.event("serve_clock", t_unix=time.time(),
                     t_mono=time.monotonic(), batching=batching)

        def now() -> float:
            return clock.now() - t0

        def flush_window() -> None:
            """Close one sketch/signal window (the serve-record
            cadence): land the window's delta sketches on the stream —
            bucket-wise mergeable into fleet-wide percentiles — and
            feed the live signal engine one observation."""
            nonlocal win_idx, win_t0, last_productive
            t = now()
            if writer.enabled and any(sk.count for sk in win_sk.values()):
                writer.event(
                    "latency_sketch", t=round(t, 4), window=win_idx,
                    fields={f: sk.to_record()
                            for f, sk in win_sk.items() if sk.count})
            measures: dict = {}
            causes: dict = {}
            if sig_target_ms and win_stats["n"]:
                measures["SUSTAINED_OVERLOAD"] = (win_stats["viol"]
                                                  / win_stats["n"])
                causes["SUSTAINED_OVERLOAD"] = {
                    "violations": win_stats["viol"],
                    "completed": win_stats["n"],
                    "target_ms": sig_target_ms}
            blk = win_stats["blocked"]
            if blk[0] + blk[1] > 1e-9:
                measures["KV_PRESSURE"] = blk[0] / (blk[0] + blk[1])
                causes["KV_PRESSURE"] = {
                    "pool_starved_s": round(blk[0], 4),
                    "batch_full_s": round(blk[1], 4),
                    "queued": len(queue),
                    "free_pages": (allocator.free_pages
                                   if allocator else None)}
            dt_win = t - win_t0
            if dt_win > 1e-9 and (queue or active):
                # goodput only means collapse while a backlog exists —
                # an idle engine between arrivals is not unhealthy
                gw = (productive_s - last_productive) / dt_win
                measures["GOODPUT_COLLAPSE"] = gw
                causes["GOODPUT_COLLAPSE"] = {
                    "window_goodput": round(gw, 4),
                    "queued": len(queue), "in_flight": len(active)}
            events = sig_engine.observe(round(t, 4), measures, causes)
            if events and signals_file:
                signals_mod.append_events(signals_file, events)
            for f in list(win_sk):
                win_sk[f] = sketch_mod.QuantileSketch()
            win_stats["n"] = win_stats["viol"] = 0
            win_stats["blocked"] = [0.0, 0.0]
            win_t0 = t
            last_productive = productive_s
            win_idx += 1

        def kv_pool_event() -> None:
            """One pool-ledger snapshot (the periodic cadence and the
            terminal flush share it): counters the engine already
            holds, no device round-trips.  Round 25 adds the growth/
            sharing/COW counters — pre-r25 readers see the keys as
            absent and normalize to 0."""
            writer.event(
                "kv_pool", t=round(now(), 4),
                pages_reserved=ledger.reserved_now,
                pages_written=ledger.written_now,
                free_pages=allocator.free_pages,
                pages_peak=allocator.pages_peak,
                pages_recycled=allocator.recycled,
                reserved_page_s=round(ledger.reserved_page_s, 6),
                written_page_s=round(ledger.written_page_s, 6),
                pages_grown=pages_grown_total,
                pages_cow=allocator.cow_copies,
                prefix_hits=prefix_hits,
                prefix_lookups=prefix_lookups,
                prefix_pages_shared=prefix_shared_total)

        def bucket_acct(kind: str, bucket: int, active_rows: int,
                        dt: float) -> None:
            u = butil.setdefault(f"{kind}@{bucket}", [0, 0, 0, 0.0])
            u[0] += 1
            u[1] += bucket
            u[2] += active_rows
            u[3] += dt

        def unseen_ms(req: Request) -> float:
            seen = first_look.pop(req.rid, req.arrival_s)
            return round(1e3 * max(0.0, seen - req.arrival_s), 3)

        def finish(fl: _InFlight, t_done: float, status: str = "ok",
                   cause: str | None = None) -> None:
            nonlocal finished, service_ewma_s, completed_ok
            back = phases.enter("retire")
            finished += 1
            rec = {
                "id": fl.req.rid,
                # the terminal disposition every ledger exit stamps
                # (the retire-without-status lint pins call sites)
                "status": status,
                "arrival_s": round(fl.req.arrival_s, 6),
                "ttft_ms": round(
                    1e3 * ((fl.t_first if fl.t_first is not None
                            else t_done) - fl.req.arrival_s), 3),
                "e2e_ms": round(1e3 * (t_done - fl.req.arrival_s), 3),
                "prompt_len": fl.req.prompt_len,
                "output_len": fl.produced,
            }
            if cause:
                rec["cause"] = cause
            if fl.preempts:
                rec["preempts"] = fl.preempts
            # the conserved e2e decomposition (obs.requests): classify
            # members have no prompt pass, so their whole resident
            # window belongs to the decode lane (t_first := t_admit)
            rec.update(requests_mod.components_ms(
                fl.req.arrival_s, fl.t_admit,
                (fl.t_first if self.decode_mode and fl.t_first is not None
                 else fl.t_admit),
                fl.t_last if fl.t_last is not None else t_done,
                t_done, fl.active_s))
            # queue-wait cause split (obs.kv): which resource this
            # request's queue_ms was blocked on; ``queue_unseen_ms`` is
            # the part of queue_ms before the loop first looked at the
            # request (it polls arrivals once an iteration, so one due
            # mid-step waits for that program to return): alignment,
            # not a resource
            causes = wait_causes.pop(fl.req.rid, None) or [0.0, 0.0]
            rec["queue_pool_starved_ms"] = round(1e3 * causes[0], 3)
            rec["queue_batch_full_ms"] = round(1e3 * causes[1], 3)
            rec["queue_unseen_ms"] = unseen_ms(fl.req)
            if self.decode_mode:
                # the greedy token ids (synthetic anyway) — the decode
                # parity tests and postmortems read them; <= 32 ints
                rec["generated"] = list(fl.out_tokens)
                # per-request KV footprint (obs.kv): the honesty gap —
                # worst-case pages reserved at admission vs pages that
                # ever held a token.  peak == final under worst-case
                # reservation; they diverge once mid-flight release
                # (on-demand paging) lands
                final_pages = ledger.retire(len(fl.pages), fl.length)
                rec["pages_reserved"] = len(fl.pages)
                rec["pages_peak_used"] = final_pages
                rec["pages_final"] = final_pages
                # round 25 footprint fields (absent on pre-r25 records;
                # readers normalize to 0, the r20/r22 seam): on-demand
                # growths after admission, and slots admitted pointing
                # at shared prefix-cache pages
                rec["pages_grown"] = fl.pages_grown
                rec["prefix_pages_shared"] = fl.prefix_shared
            if status == "ok":
                if not fl.preempts:
                    # the predictive-shed service estimate: first-admit
                    # to done of NEVER-preempted requests only — a
                    # requeued request's span includes its requeue wait,
                    # and folding that in spirals the estimate up until
                    # prediction sheds the whole queue
                    svc = t_done - fl.t_admit
                    service_ewma_s = (
                        svc if service_ewma_s is None
                        else 0.7 * service_ewma_s + 0.3 * svc)
                completed_ok += 1
                # the streaming percentile path (round 24): run- and
                # window-scoped sketches see every completion even
                # after the raw ring starts evicting
                for f in slo_mod.LATENCY_FIELDS:
                    v = rec.get(f)
                    if isinstance(v, (int, float)):
                        run_sk[f].add(float(v))
                        win_sk[f].add(float(v))
                win_stats["n"] += 1
                if sig_target_ms and rec["e2e_ms"] > sig_target_ms:
                    win_stats["viol"] += 1
                done.append(rec)
                writer.event("request", **rec)
            elif status == "shed":
                # degraded terminals land under their OWN record kind:
                # the percentile/attribution folds read kind=="request"
                # only, so a shed or quarantined request never skews
                # the served-latency percentiles
                degrade["shed"][cause] = degrade["shed"].get(cause, 0) + 1
                writer.event("shed", **rec)
                timeline_mod.instant("shed", rid=fl.req.rid, cause=cause)
            else:
                degrade["quarantined"] += 1
                writer.event("quarantine", **rec)
                timeline_mod.instant("quarantine", rid=fl.req.rid,
                                     cause=cause)
            if cache_mgr is not None:
                cache_mgr.release(fl)
            phases.enter(back)

        def shed_queued(req: Request, cause: str, t: float) -> None:
            """Admit-time shed: the request never became resident, so
            there is no _InFlight to finish — but the disposition is
            terminal and carries its cause all the same."""
            nonlocal finished
            finished += 1
            degrade["shed"][cause] = degrade["shed"].get(cause, 0) + 1
            causes = wait_causes.pop(req.rid, None) or [0.0, 0.0]
            c = carry.pop(req.rid, None)
            rec = {
                "id": req.rid, "status": "shed", "cause": cause,
                "arrival_s": round(req.arrival_s, 6),
                "waited_ms": round(1e3 * (t - req.arrival_s), 3),
                "queue_pool_starved_ms": round(1e3 * causes[0], 3),
                "queue_batch_full_ms": round(1e3 * causes[1], 3),
                "queue_unseen_ms": unseen_ms(req),
            }
            if c:
                rec["preempts"] = c["preempts"]
            writer.event("shed", **rec)
            timeline_mod.instant("shed", rid=req.rid, cause=cause)

        def free_now() -> int:
            """Allocator free pages minus any injected pool squeeze —
            the admission path's ONE view of pool headroom."""
            f = allocator.free_pages
            if faults is not None:
                f -= faults.squeezed_pages(now())
            return max(0, f)

        def preempt_one() -> bool:
            """KV pressure: preempt the resident holding the most pages
            per token of progress and requeue it carrying its prefix.
            Victims must (a) have produced 2**preempts tokens THIS
            residency — a readmitted victim earns geometrically more
            decode progress before it is preemptible again, so every
            residency advances its request (no livelock) and the total
            re-prefill overhead a request can accrue is bounded by a
            constant factor of its output (no thrash under sustained
            pool pressure) — and (b) re-prefill prompt+prefix inside
            the warmed ladder (an off-ladder shape never runs).  With
            a deadline armed, victims must additionally have burned
            3/4 of their deadline: preempting a resident that can
            still finish in time converts pool pressure into
            re-prefill thrash AND a missed SLO, while one deep into
            its budget is about to expire holding pages anyway."""
            top = max(self.prefill_buckets)
            t_now = now()
            cands = [fl for fl in active
                     if fl.produced_res >= (1 << fl.preempts)
                     and fl.length <= top
                     and (not deadline_s or shed == "off"
                          or t_now - fl.req.arrival_s > 0.75 * deadline_s)]
            if not cands:
                return False
            victim = max(cands, key=lambda fl: len(fl.pages)
                         / max(1, fl.produced))
            active.remove(victim)
            ledger.retire(len(victim.pages), victim.length)
            # pages AND slot: the re-prefill starts from a zero state in
            # whatever slot it is given then
            cache_mgr.release(victim)
            carry[victim.req.rid] = {
                "prefix": list(victim.out_tokens),
                "t_admit": victim.t_admit, "t_first": victim.t_first,
                "active_s": victim.active_s, "t_last": victim.t_last,
                "preempts": victim.preempts + 1,
            }
            queue.append(victim.req)
            degrade["preempts"] += 1
            timeline_mod.instant("preempt", rid=victim.req.rid)
            timeline_mod.instant("requeue", rid=victim.req.rid)
            writer.event("preempt", rid=victim.req.rid,
                         cause="pool_starved",
                         pages_freed=len(victim.pages),
                         produced=victim.produced)
            return True

        def drain(t: float) -> dict:
            """SIGTERM drain: stop admitting, preempt every resident
            into the journal, and commit queued + not-yet-arrived
            requests with the checkpoint tmp->fsync->rename idiom —
            the serving lane's emergency checkpoint."""
            timeline_mod.instant("drain", queued=len(queue),
                                 in_flight=len(active))
            entries = []
            for fl in list(active):
                entries.append(faults_mod.journal_entry(
                    fl.req, produced=fl.produced,
                    prefix=list(fl.out_tokens),
                    preempts=fl.preempts + 1))
                if ledger is not None:
                    ledger.retire(len(fl.pages), fl.length)
                if cache_mgr is not None:
                    cache_mgr.release(fl)
            active.clear()
            for req in queue:
                c = carry.pop(req.rid, None)
                pfx = c["prefix"] if c else ()
                entries.append(faults_mod.journal_entry(
                    req, produced=len(pfx), prefix=list(pfx),
                    preempts=c["preempts"] if c else 0))
            queue.clear()
            for req in pending[idx:]:
                entries.append(faults_mod.journal_entry(req))
            path = (journal_path or self.cfg.serve_journal
                    or os.path.join(
                        getattr(writer, "out_dir", None) or ".",
                        faults_mod.JOURNAL_NAME))
            faults_mod.write_journal(path, entries,
                                     model=self.cfg.model,
                                     seed=self.cfg.seed)
            writer.event("preempt", scope="drain", cause="sigterm",
                         t=round(t, 4), unfinished=len(entries),
                         journal=path)
            self.print_fn(
                f"serve drain: {len(entries)} unfinished request(s) "
                f"journaled to {path} — relaunch with "
                f"--serve_resume={path} to replay them")
            return {"journal": path, "unfinished": len(entries),
                    "reason": "sigterm"}

        def feed_of(req: Request, c: dict | None) -> np.ndarray:
            """The prefill token feed: the prompt, plus — for a
            requeued preemption victim — its generated prefix minus
            the newest token (the greedy pass regenerates that one,
            so resumption is exact: zero tokens lost or duplicated)."""
            if c and c["prefix"]:
                return np.concatenate(
                    [req.prompt,
                     np.asarray(c["prefix"][:-1], np.int32)])
            return req.prompt

        def need_pages(req: Request) -> int:
            """Pages admission must pull from the FREE list for this
            request right now: the full table under worst-case
            reservation; prompt + headroom minus the prefix-cache
            cover under lazy (the cache peek is pure — acquire
            happens inside admit in the same scheduler iteration)."""
            if kv_reserve == "worst":
                return self.table_width
            c = carry.get(req.rid)
            plen = req.prompt_len + (max(0, len(c["prefix"]) - 1)
                                     if c else 0)
            slots = min(self.table_width,
                        -(-plen // self.page_size) + headroom)
            if cache is not None:
                slots -= cache.match(feed_of(req, c)).slots
            return max(0, slots)

        def admit(req: Request) -> None:
            nonlocal kv, tokens_out, productive_s
            nonlocal prefix_hits, prefix_lookups, prefix_shared_total
            t_admit = now()
            c = carry.pop(req.rid, None)
            timeline_mod.instant("admit", rid=req.rid)
            if not self.decode_mode:
                active.append(_InFlight(req=req, pages=[],
                                        table=np.zeros(0, np.int32),
                                        t_admit=t_admit))
                return
            prefix = c["prefix"] if c else []
            if c:
                degrade["requeues"] += 1
            feed = feed_of(req, c)
            plen = int(len(feed))
            shared: list[int] = []
            m = None
            if cache is not None:
                prefix_lookups += 1
                m = cache.match(feed)
                if m.slots:
                    prefix_hits += 1
                    shared = cache.acquire(m)
                    prefix_shared_total += len(shared)
            if kv_reserve == "lazy":
                # reserve only what the prompt needs plus decode
                # headroom; every later page is an on-demand growth
                slots = min(self.table_width,
                            -(-plen // self.page_size) + headroom)
            else:
                slots = self.table_width
            fresh = allocator.alloc(max(0, slots - len(shared)))
            assert fresh is not None, "admission checked free_pages"
            pages = shared + fresh
            slot = cache_mgr.bind_slot()
            table = np.pad(np.asarray(pages, np.int32),
                           (0, self.table_width - len(pages)))
            if cache_mgr.slots is not None:
                # the slot rides in one more column, after the pages
                table = np.append(table, np.int32(slot))
            ledger.admit(len(pages), plen)
            s = pick_bucket(self.prefill_buckets, plen)
            toks = np.zeros((1, s), np.int32)
            toks[0, :plen] = feed
            wtable = table
            if shared:
                # the prefill-skip seam: shared slots' physical pages
                # already hold this prefix's K/V bitwise (same params,
                # same absolute positions, deterministic prefill), so
                # the WRITE table routes their stores to trash page 0
                # — the decode table keeps the real shared ids.  The
                # dense pass itself still runs: next_token attends
                # over every prompt position either way.
                wtable = np.where(
                    np.arange(self.table_cols) < len(shared),
                    0, table).astype(np.int32)
            (next_tok, logits, kv), dt = self._timed(
                clock, "prefill",
                lambda: self.compiled[("prefill", s)](
                    self.exec_params, kv, toks,
                    np.int32(plen), wtable), phases, "admit_host")
            # host-side numpy view BEFORE indexing: jax.Array.__getitem__
            # dispatches a jitted gather — a post-warmup compile the
            # zero-recompile contract (and the cache-entry assertion)
            # would catch
            next_tok = np.asarray(next_tok)
            steps["prefill"] += 1
            if not c:
                # a re-prefill regenerates an already-counted token
                tokens_out += 1
            productive_s += dt * (plen / s)
            bucket_acct("prefill", s, plen, dt)
            ledger.charge(dt)
            fl = _InFlight(
                req=req, pages=pages, table=table, length=plen,
                produced=(len(prefix) if c else 1),
                last_token=int(next_tok[0]),
                t_admit=(c["t_admit"] if c else t_admit),
                t_first=(c["t_first"] if c else now()),
                out_tokens=(list(prefix[:-1]) + [int(next_tok[0])]
                            if c else [int(next_tok[0])]),
                active_s=(c["active_s"] + dt if c else 0.0),
                t_last=(c["t_last"] if c else None),
                preempts=(c["preempts"] if c else 0),
                produced_res=(0 if c else 1),
                prefix_shared=len(shared), slot=slot)
            if guard:
                row = np.asarray(logits)
                if faults is not None and faults.poison_rids([req.rid]):
                    row = np.full_like(np.array(row), np.nan)
                    announce_nan(req.rid, "prefill")
                if not np.isfinite(row).all():
                    fl.t_last = now()
                    finish(fl, now(), status="quarantined",
                           cause="nonfinite_logits")
                    return
            if cache is not None:
                # seed the trie with this prefill's pages (a finite,
                # non-quarantined pass only): full chunks as nodes,
                # the partial tail under its exact-token key — the
                # cache's own reference keeps them alive past this
                # request's retirement
                cache.insert(feed, pages, plen)
            if fl.produced >= req.output_len:
                finish(fl, now(), status="ok")
            else:
                active.append(fl)

        def announce_nan(rid: int, where: str) -> None:
            self.print_fn(f"inject: nan_logits rid {rid} ({where})")
            writer.event("injected_fault", fault="nan_logits", rid=rid,
                         where=where)

        def ensure_capacity(fl: _InFlight) -> bool:
            """Round 25 growth/COW pre-pass for one resident: make this
            step's append slot a writable, exclusively-owned page.
            Crossing a page boundary allocates from the free list AT
            THAT MOMENT (on-demand growth); the first append into a
            shared page duplicates it through the warmed page-copy
            program (copy-on-write).  Returns False to PAUSE the row
            this step — its batch slot masks off and nothing is
            written, so the next step retries after eviction,
            preemption, or a retirement frees pages."""
            nonlocal kv, pages_grown_total
            slot = fl.length // self.page_size
            if slot >= len(fl.pages):
                if free_now() < 1 and cache is not None:
                    cache.evict(1)
                if free_now() < 1:
                    return False
                grown = allocator.alloc(1)
                allocator.bind(fl.table, slot, grown[0])
                fl.pages.append(grown[0])
                ledger.grow(1)
                fl.pages_grown += 1
                pages_grown_total += 1
                return True
            page = fl.pages[slot]
            if allocator.refcount(page) == 1:
                return True
            # shared tail page (this holder + the cache and/or other
            # residents): copy before the write
            if free_now() < 1 and cache is not None:
                cache.evict(1)
            if free_now() < 1:
                return False
            dst = allocator.cow_alloc()
            (kv), dt = self._timed(
                clock, "page_copy",
                lambda: self.compiled[("page_copy", 0)](
                    kv, np.int32(page), np.int32(dst)), phases, "pack")
            ledger.charge(dt)
            allocator.bind(fl.table, slot, dst)
            fl.pages[slot] = dst
            allocator.free([page])
            return True

        def decode_step() -> bool:
            nonlocal kv, tokens_out, productive_s
            phases.enter("pack")
            if faults is not None:
                hang_s = faults.hang_before_decode(steps["decode"] + 1)
                if hang_s:
                    self.print_fn(f"inject: hang {hang_s}s before "
                                  f"decode step {steps['decode'] + 1}")
                    writer.event("injected_fault", fault="hang",
                                 step=steps["decode"] + 1,
                                 seconds=hang_s)
                    # REAL wall, whatever the engine clock: the wedged-
                    # host signature the watchdog's (real-time)
                    # progress oracle exists to catch
                    time.sleep(hang_s)
            sched = active
            if kv_reserve == "lazy" or cache is not None:
                sched = [fl for fl in active if ensure_capacity(fl)]
                if not sched and active and kv_preempt == "on" \
                        and preempt_one():
                    # every resident paused on growth: the r23
                    # machinery frees a victim's pages and the rest
                    # retry in the same step
                    sched = [fl for fl in active if ensure_capacity(fl)]
                if not sched:
                    return False
            b = pick_bucket(self.batch_buckets, len(sched))
            toks = np.zeros((b,), np.int32)
            tables = np.zeros((b, self.table_cols), np.int32)
            lengths = np.zeros((b,), np.int32)
            mask = np.zeros((b,), bool)
            for i, fl in enumerate(sched):
                toks[i] = fl.last_token
                tables[i] = fl.table
                lengths[i] = fl.length
                mask[i] = True
            (next_toks, logits, kv), dt = self._timed(
                clock, "decode",
                lambda: self.compiled[("decode", b)](
                    self.exec_params, kv, toks, tables, lengths, mask),
                phases, "retire")
            steps["decode"] += 1
            tokens_out += len(sched)
            productive_s += dt * (len(sched) / b)
            bucket_acct("decode", b, len(sched), dt)
            ledger.charge(dt)
            next_toks = np.asarray(next_toks)
            for j, name in enumerate(self.family.counters):
                counters[name] += int(next_toks[b + j])
            if self.family.picks_per_token:
                counters["moe_picks"] += (
                    len(sched) * self.family.picks_per_token)
            if cache_mgr.slots is not None:
                state_slot_steps[0] += len(sched)
                state_slot_steps[1] += self.cap
            bad: set[int] = set()
            if guard:
                # per-request quarantine: ONE host read of the step's
                # logits, rows checked independently — a poisoned
                # request retires alone, batch-mates keep their
                # (finite) tokens
                lg = np.asarray(logits)[:len(sched)]
                hit = (set(faults.poison_rids(
                    [fl.req.rid for fl in sched]))
                    if faults is not None else set())
                if hit:
                    lg = np.array(lg)   # writable copy to poison
                    for i, fl in enumerate(sched):
                        if fl.req.rid in hit:
                            lg[i] = np.nan
                            announce_nan(fl.req.rid, "decode")
                finite = np.isfinite(lg.reshape(len(lg), -1)).all(axis=1)
                bad = {i for i in range(len(sched)) if not finite[i]}
            t_done = now()
            dropped: set[int] = set()
            for i, fl in enumerate(sched):
                fl.active_s += dt
                fl.t_last = t_done
                if i in bad:
                    finish(fl, t_done, status="quarantined",
                           cause="nonfinite_logits")
                    dropped.add(fl.req.rid)
                    continue
                fl.last_token = int(next_toks[i])
                fl.out_tokens.append(fl.last_token)
                ledger.token(fl.length)
                fl.length += 1
                fl.produced += 1
                fl.produced_res += 1
                if fl.produced >= fl.req.output_len:
                    finish(fl, t_done, status="ok")
                    dropped.add(fl.req.rid)
            if dropped:
                # paused rows (not in sched) keep their place; retire
                # by rid, not list rebuild from sched
                active[:] = [fl for fl in active
                             if fl.req.rid not in dropped]
            return True

        def classify_step() -> None:
            nonlocal tokens_out, productive_s
            phases.enter("pack")
            b = pick_bucket(self.batch_buckets, len(active))
            x = np.zeros((b,) + tuple(self.spec.input_shape), np.float32)
            for i, fl in enumerate(active):
                x[i] = self._classify_input(fl.req)
            _, dt = self._timed(
                clock, "classify",
                lambda: self.compiled[("classify", b)](self.variables, x),
                phases, "retire")
            steps["classify"] += 1
            tokens_out += len(active)
            productive_s += dt * (len(active) / b)
            bucket_acct("classify", b, len(active), dt)
            t_done = now()
            for fl in active:
                fl.t_first = t_done
                fl.produced = 1
                fl.active_s += dt
                fl.t_last = t_done
                finish(fl, t_done, status="ok")
            active.clear()

        # round 23: the drain handler + the scheduler-iteration
        # watchdog.  The engine installs a real SIGTERM/SIGINT handler
        # unless the caller injected one (tests poll a fake; install()
        # is a no-op off the main thread)
        own_handler = None
        handler = drain_handler
        if handler is None:
            own_handler = preempt_mod.PreemptionHandler(
                print_fn=self.print_fn).install()
            handler = own_handler
        timeout_s = watchdog_mod.resolve_timeout(
            step_timeout_s if step_timeout_s is not None
            else self.cfg.serve_step_timeout_s,
            warmup_step_s=(self.compile_record["warmup_s"]
                           / max(1, self.compile_record["buckets"])))
        last_iter_t: list = [None]

        def watchdog_forensics() -> None:
            # round-17 forensics on the serve lane: the flight-recorder
            # tail + the live-buffer memory dump, best-effort by
            # contract (both swallow their own failures)
            out_dir = getattr(writer, "out_dir", None)
            timeline_mod.dump_timeline(out_dir, "serve_watchdog",
                                       step=sum(steps.values()))
            if out_dir:
                from tpu_hc_bench.obs import memory as obs_memory
                obs_memory.dump_forensics(out_dir, "serve_watchdog",
                                          step=sum(steps.values()))

        dog = None
        if timeout_s:
            dog = watchdog_mod.Watchdog(
                timeout_s, lambda: last_iter_t[0],
                print_fn=self.print_fn,
                last_record_fn=lambda: getattr(writer, "last_record",
                                               None),
                obs_writer=writer if writer.enabled else None,
                on_timeout=on_watchdog,
                forensics_fn=watchdog_forensics).start()

        last_blocked: str | None = None
        loop_m0 = time.monotonic()
        try:
            while finished < n:
                phases.enter("arrivals")
                t = now()
                while idx < n and pending[idx].arrival_s <= t:
                    first_look[pending[idx].rid] = t
                    queue.append(pending[idx])
                    idx += 1
                if faults is not None:
                    sq = faults.squeezed_pages(t)
                    if sq != squeezed_seen:
                        self.print_fn(
                            f"inject: pool_squeeze -> {sq} page(s) "
                            f"withheld at t={t:.3f}s")
                        writer.event("injected_fault",
                                     fault="pool_squeeze", pages=sq,
                                     t=round(t, 4))
                        squeezed_seen = sq
                    if faults.sigterm_due(t):
                        self.print_fn(f"inject: sigterm at t={t:.3f}s")
                        writer.event("injected_fault", fault="sigterm",
                                     t=round(t, 4))
                        faults.deliver_sigterm()
                if handler is not None and handler.requested():
                    drained = drain(t)
                    break
                loop_iters += 1
                queue_depth_sum += len(queue)
                queue_depth_max = max(queue_depth_max, len(queue))
                progressed = False
                if shed != "off":
                    # expiry pass: a request past its deadline decodes
                    # only dead tokens — shed it (queued) or retire it
                    # (resident) with a cause instead
                    for req in [r for r in queue
                                if t - r.arrival_s > deadline_s]:
                        queue.remove(req)
                        shed_queued(req, "deadline_expired", t)
                        progressed = True
                    for fl in [f for f in active
                               if t - f.req.arrival_s > deadline_s]:
                        active.remove(fl)
                        finish(fl, t, status="shed",
                               cause="resident_expired")
                        progressed = True
                phases.enter("admit_host")
                if batching == "continuous":
                    while queue and len(active) < self.cap:
                        head = queue[0]
                        if (shed == "deadline"
                                and service_ewma_s is not None
                                and (now() - head.arrival_s)
                                + service_ewma_s > deadline_s):
                            # predictive shed: queue wait plus the
                            # admit-to-done EWMA already blows the
                            # deadline — reject at admission instead
                            # of decoding a dead answer
                            shed_queued(queue.popleft(),
                                        "deadline_predicted", now())
                            progressed = True
                            continue
                        if allocator is None or (
                                free_now() >= need_pages(head)
                                and cache_mgr.slot_free()):
                            admit(queue.popleft())
                            progressed = True
                            continue
                        if not cache_mgr.slot_free():
                            # every slot is held (paused rows keep
                            # theirs): only a retirement frees one
                            break
                        # starved: reclaim cold cache pages first (they
                        # are free capacity the trie is merely keeping
                        # warm), then the r23 preemption machinery
                        if cache is not None and cache.evict(
                                need_pages(head) - free_now()):
                            continue
                        if kv_preempt == "on" and preempt_one():
                            progressed = True
                            continue
                        break
                elif not active:
                    # static: wait for a full batch (or the trace
                    # tail); the batch is additionally bounded by what
                    # the KV pool can hold — resolve() only guarantees
                    # pages for ONE request, so a tuned half-pool row
                    # would otherwise crash admission (active empty =>
                    # every page is free)
                    want = min(self.cap, n - finished)
                    if allocator is not None:
                        want = min(want,
                                   free_now() // self.table_width)
                    if len(queue) >= want or idx == n:
                        for _ in range(min(want, len(queue))):
                            admit(queue.popleft())
                            progressed = True
                # admission forensics (round 22, obs.kv): when requests
                # stay queued past the admission pass, name the BINDING
                # resource — the scaling-policy input.  Continuous: a
                # full batch gates before a full pool (freeing pages
                # would not open a slot), so batch_full wins when both
                # bind.  Static: the run-to-completion batch policy is
                # always the gate — even a pool-capped batch admits
                # nothing mid-flight, so scale-out (not pool growth) is
                # the remedy.
                phases.enter("telemetry")
                blocked_cause = None
                if queue:
                    if batching != "continuous":
                        blocked_cause = "batch_full"
                    elif len(active) >= self.cap:
                        blocked_cause = "batch_full"
                    elif cache_mgr is not None and \
                            not cache_mgr.slot_free():
                        blocked_cause = "slot_starved"
                    elif allocator is not None and \
                            free_now() < need_pages(queue[0]):
                        blocked_cause = "pool_starved"
                if blocked_cause != last_blocked:
                    # edge-triggered flight-recorder instants: the
                    # moment admission blocks on (or frees from) a
                    # resource — bounded by transitions, not steps
                    if blocked_cause == "pool_starved":
                        timeline_mod.instant("pool_starved",
                                             queued=len(queue))
                    elif blocked_cause == "slot_starved":
                        timeline_mod.instant("slot_starved",
                                             queued=len(queue))
                    elif blocked_cause == "batch_full":
                        timeline_mod.instant("batch_full",
                                             queued=len(queue))
                    last_blocked = blocked_cause
                t_blocked = now()
                if active:
                    if self.decode_mode:
                        # a False return means every resident paused on
                        # growth/COW starvation — not progress
                        if decode_step():
                            progressed = True
                    else:
                        classify_step()
                        progressed = True
                if not progressed:
                    phases.enter("arrival_wait")
                    if idx >= n:
                        if shed == "off" or not queue:
                            raise RuntimeError(
                                "serve engine stalled: no request can "
                                "make progress — KV pool undersized? "
                                "(under --kv_reserve=lazy, "
                                "--kv_preempt=on frees pages by "
                                "preempting the worst resident)")
                        # shedding armed: a squeezed pool can pin the
                        # queue with nothing resident — idle to the
                        # next deadline; the expiry pass drains it
                        nxt = (min(r.arrival_s for r in queue)
                               + deadline_s)
                        clock.sleep(max(1e-4, nxt - now() + 1e-4))
                    else:
                        gap = pending[idx].arrival_s - now()
                        if timeout_s:
                            # chunked: an idle arrival gap must never
                            # read as a wedged scheduler
                            gap = min(gap, timeout_s / 2)
                        clock.sleep(gap)
                phases.enter("telemetry")
                if blocked_cause is not None:
                    # charge the elapsed step/sleep to the blocking
                    # cause for every request that sat in queue through
                    # it (they rejoin admission at the next loop top)
                    dt_blk = now() - t_blocked
                    if dt_blk > 0:
                        # a starved slot is cache capacity, as a
                        # starved page is
                        ci = 0 if blocked_cause in (
                            "pool_starved", "slot_starved") else 1
                        # the KV_PRESSURE measure: wall seconds this
                        # window spent blocked, split by binding cause
                        win_stats["blocked"][ci] += dt_blk
                        for r in queue:
                            wait_causes.setdefault(
                                r.rid, [0.0, 0.0])[ci] += dt_blk
                total_steps = sum(steps.values())
                if total_steps - last_record_step >= _SERVE_RECORD_EVERY:
                    last_record_step = total_steps
                    if writer.enabled:
                        writer.event(
                            "serve", t=round(now(), 4),
                            queue_depth=len(queue),
                            in_flight=len(active),
                            free_pages=(allocator.free_pages
                                        if allocator else None),
                            tokens=tokens_out,
                            # running per-bucket occupancy — `obs
                            # watch`'s live utilization column
                            bucket_occ={k: round(u[2] / u[1], 3)
                                        for k, u in butil.items()
                                        if u[1]},
                            **{f"{k}_steps": v
                               for k, v in steps.items()})
                        if ledger is not None:
                            kv_pool_event()
                        # persist the ring at the record cadence: ten
                        # spans an iteration would roll off before the
                        # run-end flush
                        timeline_mod.flush()
                    if fleet is not None:
                        fleet.heartbeat(
                            step=total_steps,
                            step_ewma_ms=1e3 * now()
                            / max(1, total_steps),
                            kv_peak_pages=(allocator.pages_peak
                                           if allocator else None),
                            phase="serve")
                    flush_window()
                # a completed scheduler iteration IS progress to the
                # watchdog — admission, shedding, and idle arrival
                # waits all count; only a wedged step does not
                last_iter_t[0] = time.perf_counter()
        finally:
            phases.close()
            loop_wall_s = time.monotonic() - loop_m0
            if dog is not None:
                dog.stop()
            if own_handler is not None:
                own_handler.uninstall()

        if self.decode_mode:
            self._kv = kv
        wall = max(now(), 1e-9)
        if ledger is not None and writer.enabled:
            # terminal ledger snapshot: runs shorter than one record
            # window still land their cumulative page-second integrals
            kv_pool_event()
        if fleet is not None:
            fleet.heartbeat(
                step=sum(steps.values()),
                step_ewma_ms=1e3 * wall / max(1, sum(steps.values())),
                kv_peak_pages=(allocator.pages_peak
                               if allocator else None),
                phase="serve")
        # the tail window (possibly under one record cadence) still
        # lands its sketch + one final signal observation
        flush_window()
        entries_final = self._count_cache()
        # summary percentiles come from the run-lifetime sketches —
        # exact over every completion, not just the retained ring
        fold = slo_mod.fold_sketches(run_sk)
        attribution = requests_mod.fold_attribution(list(done))
        kv_fold = None
        if ledger is not None:
            kv_fold = kv_mod.fold_ledger(
                reserved_page_s=ledger.reserved_page_s,
                written_page_s=ledger.written_page_s,
                pages_peak=allocator.pages_peak,
                pages_recycled=allocator.recycled,
                pages_grown=pages_grown_total,
                cow_copies=allocator.cow_copies,
                prefix_hits=prefix_hits,
                prefix_lookups=prefix_lookups,
                prefix_pages_shared=prefix_shared_total,
                request_records=list(done))
        summary = {
            "workload": "serve",
            "model": self.cfg.model,
            "batching": batching,
            "arrival": self.cfg.arrival,
            "arrival_rate": self.cfg.arrival_rate,
            "requests": n,
            "completed": completed_ok,
            "wall_s": round(wall, 4),
            "tokens": tokens_out,
            "tokens_per_s": round(tokens_out / wall, 3),
            "goodput": round(productive_s / wall, 4),
            "queue_depth_max": queue_depth_max,
            "queue_depth_mean": round(
                queue_depth_sum / loop_iters if loop_iters else 0.0, 3),
            "buckets": list(self.batch_buckets),
            "max_in_flight": self.cap,
            "kv_page_size": self.page_size,
            "kv_pages": self.num_pages,
            # round 22 (obs.kv): pool geometry + the utilization ledger
            "kv_layers": (len(self.family.kv_layers)
                          if self.decode_mode else None),
            "kv_pool_bytes": self.kv_pool_bytes,
            "kv_scale_bytes": self.kv_scale_bytes,
            "kv_pool": kv_fold,
            **kv_mod.flatten_kv(kv_fold),
            # round 25: the reservation/sharing arms are config
            # identity for this run (regress fingerprints on them)
            "kv_reserve": (kv_reserve if self.decode_mode else None),
            "prefix_cache": (prefix_cache if self.decode_mode
                             else None),
            "decode_attention": (self.decode_attention
                                 if self.decode_mode else None),
            "quant": self.quant,
            "decode_block_pages": self.compile_record.get(
                "decode_block_pages"),
            "aot_decode_temp_bytes": self.compile_record.get(
                "aot_decode_temp_bytes"),
            "kv_pool_temp_ratio": self.compile_record.get(
                "kv_pool_temp_ratio"),
            # a family with a recurrent-state pool: its bytes, the slots
            # in use at each decode step summed beside slots x steps,
            # the decode steps' expert picks and those that landed on an
            # expert held here
            "state_pool_bytes": self.state_pool_bytes,
            "state_slots": state_slot_steps[0],
            "state_slot_steps": state_slot_steps[1],
            **counters,
            "post_warmup_compiles": entries_final
                                    - self.entries_after_warmup,
            # round 20 (obs.requests): the tail-attribution fold, its
            # regress projection, and the per-bucket occupancy account
            "attribution": attribution,
            **requests_mod.flatten_attribution(attribution),
            "bucket_util": {
                k: {"steps": u[0], "rows": u[1], "active_rows": u[2],
                    "wall_s": round(u[3], 4),
                    "occupancy": round(u[2] / u[1], 4) if u[1] else 0.0}
                for k, u in butil.items()},
            # the loop's real wall by exclusive phase: conserved (the
            # phases tile the loop; ``loop_wall_s`` is clocked apart)
            "loop_phases": {
                k: {"count": c, "wall_s": round(w, 6)}
                for k, (c, w) in phases.fold.items()},
            "loop_wall_s": round(loop_wall_s, 6),
            **{f"{k}_steps": v for k, v in steps.items()},
            **fold,
            # round 24: the mergeable-sketch account — source label,
            # retention cap, and the fleet-mergeable headline tail
            # (single host: the run sketch IS the merge of its
            # windows, so this equals p99_e2e_ms by construction)
            "latency_source": "sketch",
            "latency_sample_cap": _DONE_SAMPLE_CAP,
            "sketch_windows": win_idx,
            "p99_merged_ms": round(run_sk["e2e_ms"].quantile(99), 3),
            "signals_fired": dict(sorted(sig_engine.fired.items())),
            "signals_fired_total": sum(sig_engine.fired.values()),
        }
        # round 23 degradation account: always present so `obs regress`
        # can gate shed_frac against baselines that predate the knob
        shed_total = sum(degrade["shed"].values())
        summary["shed_frac"] = round(shed_total / max(1, n), 4)
        summary["degrade"] = {
            "shed": dict(sorted(degrade["shed"].items())),
            "shed_frac": summary["shed_frac"],
            "preempts": degrade["preempts"],
            "requeues": degrade["requeues"],
            "quarantined": degrade["quarantined"],
        }
        if drained is not None:
            summary["drained"] = drained
        if self.cfg.slo_e2e_ms:
            # windowed SLO burn rate: sustained overload vs transient
            # burst, against the --slo_e2e_ms e2e target
            summary["slo"] = slo_mod.fold_burn_rate(
                list(done), self.cfg.slo_e2e_ms)
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
