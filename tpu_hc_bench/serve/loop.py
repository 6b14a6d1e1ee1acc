"""One run of the serving engine: the scheduler loop and its state.

``ServeEngine.run`` resolves a run's policy, builds ONE ``ServeLoop`` and
plays it; ``summarize`` folds the finished loop into the serve summary.
The loop's parts, in the order its exclusive phases name them:

- arrivals and admission policy (``arrivals``, ``admit_host``): the trace's
  due requests join the queue, injected faults are announced, expired
  requests are shed, and the queue's head is admitted, reclaimed for or
  preempted for — every question about pages and slots goes to the run's
  ``serve.cache.CacheManager``;
- the step (``pack``, ``<kind>_dispatch``, ``<kind>_wait``): one AOT
  program of the engine's warmed ladder, run to completion and read back;
- retirement (``retire``): a request's terminal record, whatever its
  disposition;
- telemetry (``telemetry``, ``arrival_wait``): the blocked-cause account
  and the periodic ``serve`` / ``kv_pool`` / sketch records.

The engine keeps the programs and the device arrays; the loop holds the
run's host state and nothing that outlives the run.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import time

import numpy as np

from tpu_hc_bench.obs import kv as kv_mod
from tpu_hc_bench.obs import requests as requests_mod
from tpu_hc_bench.obs import signals as signals_mod
from tpu_hc_bench.obs import sketch as sketch_mod
from tpu_hc_bench.obs import timeline as timeline_mod
from tpu_hc_bench.resilience import preempt as preempt_mod
from tpu_hc_bench.resilience import watchdog as watchdog_mod
from tpu_hc_bench.serve import cache as cache_mod
from tpu_hc_bench.serve import faults as faults_mod
from tpu_hc_bench.serve import slo as slo_mod
from tpu_hc_bench.serve.arrivals import Request
from tpu_hc_bench.serve.decode import pages_held, window_pages_held
from tpu_hc_bench.serve.engine import pick_bucket

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


@dataclasses.dataclass(frozen=True)
class RunPolicy:
    """One run's resolved policy (``ServeEngine.run``'s keyword overrides
    over their config twins, validated together)."""

    batching: str                   # continuous | static
    shed: str                       # off | admit | deadline
    deadline_ms: float | None       # the deadline shed judges against
    kv_preempt: str                 # off | on
    kv_reserve: str                 # worst | lazy
    prefix_cache: str               # off | on
    # the quarantine guard arms with EITHER policy knob: reading
    # logits back is one host transfer per step that the unarmed
    # lane must not pay (an injected NaN with both knobs off flows
    # through undetected — the faults A/B's control arm)
    guard: bool

    @property
    def deadline_s(self) -> float:
        return (self.deadline_ms or 0.0) / 1e3

    @classmethod
    def resolve(cls, eng, *, batching, shed, deadline_ms, kv_preempt,
                kv_reserve, prefix_cache, faults) -> "RunPolicy":
        """Each per-run override over its config twin (``None``: the
        twin), validated together against what the engine ``eng``
        serves."""
        cfg = eng.cfg
        batching = batching or cfg.batching
        if batching not in ("continuous", "static"):
            raise ValueError(f"batching must be continuous|static: "
                             f"{batching!r}")
        shed = shed if shed is not None else cfg.shed
        kv_preempt = (kv_preempt if kv_preempt is not None
                      else cfg.kv_preempt)
        # round 25: the reservation/sharing arms override per run like
        # the other policy knobs — the three-arm kv bench drives all of
        # worst / lazy / lazy+prefix through ONE warmed engine
        kv_reserve = (kv_reserve if kv_reserve is not None
                      else cfg.kv_reserve)
        prefix_cache = (prefix_cache if prefix_cache is not None
                        else cfg.prefix_cache)
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
        if prefix_cache == "on" and eng.state_slots:
            raise ValueError(
                f"--model {cfg.model}: prefix_cache=on would share "
                "K/V pages without the recurrent state of the same "
                "prefix; refused for this family")
        deadline_ms = (deadline_ms if deadline_ms is not None
                       else (cfg.deadline_ms or cfg.slo_e2e_ms))
        if shed not in ("off", "admit", "deadline"):
            raise ValueError(f"shed must be off|admit|deadline: {shed!r}")
        if shed != "off" and not deadline_ms:
            raise ValueError(
                "--shed needs a deadline to shed against: set "
                "--deadline_ms (or --slo_e2e_ms, its fallback)")
        if not eng.decode_mode and (faults or kv_preempt == "on"):
            raise ValueError(
                f"--model {cfg.model} serves single-forward "
                "classify requests; --serve_faults/--kv_preempt drive "
                "the paged decode path and have no meaning here")
        if not eng.decode_mode and (kv_reserve != "worst"
                                    or prefix_cache != "off"):
            raise ValueError(
                f"--model {cfg.model} serves single-forward "
                "classify requests with no KV pool; "
                "--kv_reserve/--prefix_cache have no meaning here")
        return cls(
            batching=batching, shed=shed, deadline_ms=deadline_ms,
            kv_preempt=kv_preempt, kv_reserve=kv_reserve,
            prefix_cache=prefix_cache,
            guard=shed != "off" or kv_preempt == "on")


@dataclasses.dataclass(kw_only=True)
class _InFlight(cache_mod.Holding):
    """Host-side bookkeeping for one admitted request (its share of the
    cache is the ``Holding`` it extends)."""

    req: Request
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


class ServeLoop:
    """The state of one ``ServeEngine.run`` and the loop over it."""

    def __init__(self, engine, requests: list[Request], policy: RunPolicy,
                 *, kv, writer, clock, fleet=None, faults=None,
                 journal_path=None):
        eng = self.eng = engine
        self.policy = policy
        self.writer, self.clock, self.fleet = writer, clock, fleet
        self.faults, self.journal_path = faults, journal_path
        self.t0 = 0.0
        self.cache = (cache_mod.CacheManager(
            eng.num_pages, eng.page_size, eng.table_width,
            state_slots=eng.state_slots, ring_pages=eng.ring_pages,
            ring_width=eng.ring_width, kv_reserve=policy.kv_reserve,
            growth_headroom=eng.cfg.kv_growth_headroom,
            prefix_cache=policy.prefix_cache == "on",
            squeezed=((lambda: faults.squeezed_pages(self.now()))
                      if faults is not None else None))
            if eng.decode_mode else None)
        # program counters of a family with a state pool / routed
        # experts held as a share (summed from what each decode step
        # returns with its tokens: no extra transfer)
        self.counters = dict.fromkeys(
            eng.family.counters + (("moe_picks",) * bool(
                eng.family.picks_per_token)) if eng.decode_mode else (), 0)
        self.state_slot_steps = [0, 0]      # in use, slots x steps
        # the gather arm's packed cache read: pages its decode steps
        # visited (whole chunks) in every layer that reads pages, beside
        # rows x table width in each such layer
        self.kv_read = [0, 0]
        # queue-wait cause split (round 22): rid -> accumulated seconds
        # blocked on [pool_starved, batch_full] while sitting in queue
        self.wait_causes: dict[int, list[float]] = {}
        # round 23 degradation state: terminal dispositions counted by
        # cause, the preempted-victim carry (rid -> prefix + original
        # lifecycle instants, so the conserved components span both
        # residencies), and the admit-to-done EWMA the predictive shed
        # judges against
        self.degrade: dict = {"shed": {}, "preempts": 0, "requeues": 0,
                              "quarantined": 0}
        self.carry: dict[int, dict] = {}
        self.finished = 0
        self.service_ewma_s: float | None = None
        self.squeezed_seen = 0
        self.drained: dict | None = None
        self.pending = sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        self.n = len(self.pending)
        self.idx = 0
        # the device's cache tree: every program takes it and returns it
        self.kv = kv
        if eng.decode_mode:
            over = [r for r in self.pending
                    if r.prompt_len > eng.cfg.max_prompt_len
                    or r.output_len > eng.cfg.max_output_len]
            if over:
                raise ValueError(
                    f"{len(over)} request(s) exceed the compiled ladder "
                    f"(prompt<={eng.cfg.max_prompt_len}, "
                    f"output<={eng.cfg.max_output_len}); request "
                    f"{over[0].rid} is {over[0].prompt_len}/"
                    f"{over[0].output_len} — shapes outside the warmed "
                    "buckets never run")
        self.queue: collections.deque[Request] = collections.deque()
        self.active: list[_InFlight] = []
        # bounded retention (round 24): the freshest N raw records; the
        # sketches below carry the run-lifetime percentiles
        self.done: collections.deque[dict] = collections.deque(
            maxlen=_DONE_SAMPLE_CAP)
        self.completed_ok = 0
        self.run_sk = {f: sketch_mod.QuantileSketch()
                       for f in slo_mod.LATENCY_FIELDS}
        self.win_sk = {f: sketch_mod.QuantileSketch()
                       for f in slo_mod.LATENCY_FIELDS}
        self.win_idx = 0
        self.win_t0 = 0.0
        self.last_productive = 0.0
        self.win_stats: dict = {"n": 0, "viol": 0, "blocked": [0.0, 0.0]}
        # live health signals (round 24): hysteresis-gated judgments
        # per record window, appended to signals.jsonl beside the
        # stream; the e2e target is the deadline (or SLO) when set —
        # without one the overload measure is "no evidence", never 0
        self.sig_engine = signals_mod.SignalEngine()
        self.sig_target_ms = (policy.deadline_ms or eng.cfg.slo_e2e_ms
                              or None)
        out_dir = getattr(writer, "out_dir", None)
        self.signals_file = (signals_mod.signals_path(out_dir)
                             if writer.enabled and out_dir else None)
        self.steps = {"prefill": 0, "decode": 0, "classify": 0}
        self.tokens_out = 0
        self.productive_s = 0.0
        self.loop_iters = self.queue_depth_max = self.queue_depth_sum = 0
        # the loop's wall by exclusive phase (obs.timeline.Phases): the
        # summary's ``loop_phases``, on the real monotonic clock whatever
        # clock drives the scheduler
        self.phases = timeline_mod.Phases()
        self.loop_wall_s = 0.0
        self.wall = 0.0
        # rid -> engine time of the loop's first look at the request
        # (``queue_unseen_ms``); kept across preempt/requeue
        self.first_look: dict[int, float] = {}
        # per-(kind,bucket) utilization: key -> [steps, rows, active
        # rows, wall s] — the occupancy heatmap's raw counts
        self.butil: dict[str, list] = {}
        self.last_record_step = 0
        self.last_blocked: str | None = None

    def now(self) -> float:
        return self.clock.now() - self.t0

    # -- telemetry -----------------------------------------------------

    def flush_window(self) -> None:
        """Close one sketch/signal window (the serve-record
        cadence): land the window's delta sketches on the stream —
        bucket-wise mergeable into fleet-wide percentiles — and
        feed the live signal engine one observation."""
        t = self.now()
        writer, win_sk, win_stats = self.writer, self.win_sk, self.win_stats
        if writer.enabled and any(sk.count for sk in win_sk.values()):
            writer.event(
                "latency_sketch", t=round(t, 4), window=self.win_idx,
                fields={f: sk.to_record()
                        for f, sk in win_sk.items() if sk.count})
        measures: dict = {}
        causes: dict = {}
        if self.sig_target_ms and win_stats["n"]:
            measures["SUSTAINED_OVERLOAD"] = (win_stats["viol"]
                                              / win_stats["n"])
            causes["SUSTAINED_OVERLOAD"] = {
                "violations": win_stats["viol"],
                "completed": win_stats["n"],
                "target_ms": self.sig_target_ms}
        blk = win_stats["blocked"]
        if blk[0] + blk[1] > 1e-9:
            measures["KV_PRESSURE"] = blk[0] / (blk[0] + blk[1])
            causes["KV_PRESSURE"] = {
                "pool_starved_s": round(blk[0], 4),
                "batch_full_s": round(blk[1], 4),
                "queued": len(self.queue),
                "free_pages": (self.cache.free_pages
                               if self.cache is not None else None)}
        dt_win = t - self.win_t0
        if dt_win > 1e-9 and (self.queue or self.active):
            # goodput only means collapse while a backlog exists —
            # an idle engine between arrivals is not unhealthy
            gw = (self.productive_s - self.last_productive) / dt_win
            measures["GOODPUT_COLLAPSE"] = gw
            causes["GOODPUT_COLLAPSE"] = {
                "window_goodput": round(gw, 4),
                "queued": len(self.queue), "in_flight": len(self.active)}
        events = self.sig_engine.observe(round(t, 4), measures, causes)
        if events and self.signals_file:
            signals_mod.append_events(self.signals_file, events)
        for f in list(win_sk):
            win_sk[f] = sketch_mod.QuantileSketch()
        win_stats["n"] = win_stats["viol"] = 0
        win_stats["blocked"] = [0.0, 0.0]
        self.win_t0 = t
        self.last_productive = self.productive_s
        self.win_idx += 1

    def kv_pool_event(self) -> None:
        """One pool-ledger snapshot (the periodic cadence and the
        terminal flush share it).  Round 25 adds the growth/
        sharing/COW counters — pre-r25 readers see the keys as
        absent and normalize to 0."""
        self.writer.event("kv_pool", t=round(self.now(), 4),
                          **self.cache.snapshot())

    def bucket_acct(self, kind: str, bucket: int, active_rows: int,
                    dt: float) -> None:
        u = self.butil.setdefault(f"{kind}@{bucket}", [0, 0, 0, 0.0])
        u[0] += 1
        u[1] += bucket
        u[2] += active_rows
        u[3] += dt

    def heartbeat(self, t: float) -> None:
        total = sum(self.steps.values())
        self.fleet.heartbeat(
            step=total, step_ewma_ms=1e3 * t / max(1, total),
            kv_peak_pages=(self.cache.pages_peak
                           if self.cache is not None else None),
            phase="serve")

    def emit_records(self) -> None:
        """The periodic records, every ``_SERVE_RECORD_EVERY`` steps."""
        total_steps = sum(self.steps.values())
        if total_steps - self.last_record_step < _SERVE_RECORD_EVERY:
            return
        self.last_record_step = total_steps
        writer, cache = self.writer, self.cache
        if writer.enabled:
            writer.event(
                "serve", t=round(self.now(), 4),
                queue_depth=len(self.queue),
                in_flight=len(self.active),
                free_pages=(cache.free_pages
                            if cache is not None else None),
                tokens=self.tokens_out,
                # running per-bucket occupancy — `obs
                # watch`'s live utilization column
                bucket_occ={k: round(u[2] / u[1], 3)
                            for k, u in self.butil.items() if u[1]},
                **{f"{k}_steps": v for k, v in self.steps.items()})
            if cache is not None:
                self.kv_pool_event()
            # persist the ring at the record cadence: ten
            # spans an iteration would roll off before the
            # run-end flush
            timeline_mod.flush()
        if self.fleet is not None:
            self.heartbeat(self.now())
        self.flush_window()

    def blocked_cause(self) -> str | None:
        """Admission forensics (round 22, obs.kv): when requests
        stay queued past the admission pass, name the BINDING
        resource — the scaling-policy input.  Continuous: a
        full batch gates before a full pool (freeing pages
        would not open a slot), so batch_full wins when both
        bind.  Static: the run-to-completion batch policy is
        always the gate — even a pool-capped batch admits
        nothing mid-flight, so scale-out (not pool growth) is
        the remedy."""
        cause = None
        queue = self.queue
        if queue:
            if self.policy.batching != "continuous" \
                    or len(self.active) >= self.eng.cap:
                cause = "batch_full"
            elif self.cache is not None:
                cause = self.cache.blocked_on(self.feed_of(
                    queue[0], self.carry.get(queue[0].rid)))
        if cause != self.last_blocked:
            # edge-triggered flight-recorder instants: the
            # moment admission blocks on (or frees from) a
            # resource — bounded by transitions, not steps
            if cause is not None:
                timeline_mod.instant(cause, queued=len(queue))
            self.last_blocked = cause
        return cause

    def charge_blocked(self, cause: str, t_blocked: float) -> None:
        """Charge the elapsed step/sleep to the blocking cause for
        every request that sat in queue through it (they rejoin
        admission at the next loop top)."""
        dt_blk = self.now() - t_blocked
        if dt_blk > 0:
            # a starved slot is cache capacity, as a starved page is
            ci = 0 if cause in ("pool_starved", "slot_starved") else 1
            # the KV_PRESSURE measure: wall seconds this
            # window spent blocked, split by binding cause
            self.win_stats["blocked"][ci] += dt_blk
            wait_causes = self.wait_causes
            for r in self.queue:
                wait_causes.setdefault(r.rid, [0.0, 0.0])[ci] += dt_blk

    def announce_nan(self, rid: int, where: str) -> None:
        self.eng.print_fn(f"inject: nan_logits rid {rid} ({where})")
        self.writer.event("injected_fault", fault="nan_logits", rid=rid,
                          where=where)

    def announce_faults(self, t: float) -> None:
        """The injected faults due at ``t``: a pool squeeze's new level,
        a SIGTERM."""
        faults, writer = self.faults, self.writer
        sq = faults.squeezed_pages(t)
        if sq != self.squeezed_seen:
            self.eng.print_fn(f"inject: pool_squeeze -> {sq} page(s) "
                              f"withheld at t={t:.3f}s")
            writer.event("injected_fault", fault="pool_squeeze", pages=sq,
                         t=round(t, 4))
            self.squeezed_seen = sq
        if faults.sigterm_due(t):
            self.eng.print_fn(f"inject: sigterm at t={t:.3f}s")
            writer.event("injected_fault", fault="sigterm", t=round(t, 4))
            faults.deliver_sigterm()

    # -- retirement ----------------------------------------------------

    def unseen_ms(self, req: Request) -> float:
        seen = self.first_look.pop(req.rid, req.arrival_s)
        return round(1e3 * max(0.0, seen - req.arrival_s), 3)

    def finish(self, fl: _InFlight, t_done: float, status: str = "ok",
               cause: str | None = None) -> None:
        back = self.phases.enter("retire")
        decode_mode = self.eng.decode_mode
        self.finished += 1
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
            (fl.t_first if decode_mode and fl.t_first is not None
             else fl.t_admit),
            fl.t_last if fl.t_last is not None else t_done,
            t_done, fl.active_s))
        # queue-wait cause split (obs.kv): which resource this
        # request's queue_ms was blocked on; ``queue_unseen_ms`` is
        # the part of queue_ms before the loop first looked at the
        # request (it polls arrivals once an iteration, so one due
        # mid-step waits for that program to return): alignment,
        # not a resource
        causes = self.wait_causes.pop(fl.req.rid, None) or [0.0, 0.0]
        rec["queue_pool_starved_ms"] = round(1e3 * causes[0], 3)
        rec["queue_batch_full_ms"] = round(1e3 * causes[1], 3)
        rec["queue_unseen_ms"] = self.unseen_ms(fl.req)
        if decode_mode:
            # the greedy token ids (synthetic anyway) — the decode
            # parity tests and postmortems read them; <= 32 ints
            rec["generated"] = list(fl.out_tokens)
            # per-request KV footprint (obs.kv): the honesty gap —
            # worst-case pages reserved at admission vs pages that
            # ever held a token.  peak == final under worst-case
            # reservation; they diverge once mid-flight release
            # (on-demand paging) lands.  The one exit of the cache:
            # ledger, pages and slot together
            final_pages = self.cache.release(fl)
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
            self._served(fl, t_done, rec)
        elif status == "shed":
            # degraded terminals land under their OWN record kind:
            # the percentile/attribution folds read kind=="request"
            # only, so a shed or quarantined request never skews
            # the served-latency percentiles
            shed = self.degrade["shed"]
            shed[cause] = shed.get(cause, 0) + 1
            self.writer.event("shed", **rec)
            timeline_mod.instant("shed", rid=fl.req.rid, cause=cause)
        else:
            self.degrade["quarantined"] += 1
            self.writer.event("quarantine", **rec)
            timeline_mod.instant("quarantine", rid=fl.req.rid,
                                 cause=cause)
        self.phases.enter(back)

    def _served(self, fl: _InFlight, t_done: float, rec: dict) -> None:
        """A request answered in full: the service estimate, the
        sketches, the ring, the ``request`` record."""
        if not fl.preempts:
            # the predictive-shed service estimate: first-admit
            # to done of NEVER-preempted requests only — a
            # requeued request's span includes its requeue wait,
            # and folding that in spirals the estimate up until
            # prediction sheds the whole queue
            svc = t_done - fl.t_admit
            self.service_ewma_s = (
                svc if self.service_ewma_s is None
                else 0.7 * self.service_ewma_s + 0.3 * svc)
        self.completed_ok += 1
        # the streaming percentile path (round 24): run- and
        # window-scoped sketches see every completion even
        # after the raw ring starts evicting
        run_sk, win_sk = self.run_sk, self.win_sk
        for f in slo_mod.LATENCY_FIELDS:
            v = rec.get(f)
            if isinstance(v, (int, float)):
                run_sk[f].add(float(v))
                win_sk[f].add(float(v))
        self.win_stats["n"] += 1
        if self.sig_target_ms and rec["e2e_ms"] > self.sig_target_ms:
            self.win_stats["viol"] += 1
        self.done.append(rec)
        self.writer.event("request", **rec)

    def shed_queued(self, req: Request, cause: str, t: float) -> None:
        """Admit-time shed: the request never became resident, so
        there is no _InFlight to finish — but the disposition is
        terminal and carries its cause all the same."""
        self.finished += 1
        shed = self.degrade["shed"]
        shed[cause] = shed.get(cause, 0) + 1
        causes = self.wait_causes.pop(req.rid, None) or [0.0, 0.0]
        c = self.carry.pop(req.rid, None)
        rec = {
            "id": req.rid, "status": "shed", "cause": cause,
            "arrival_s": round(req.arrival_s, 6),
            "waited_ms": round(1e3 * (t - req.arrival_s), 3),
            "queue_pool_starved_ms": round(1e3 * causes[0], 3),
            "queue_batch_full_ms": round(1e3 * causes[1], 3),
            "queue_unseen_ms": self.unseen_ms(req),
        }
        if c:
            rec["preempts"] = c["preempts"]
        self.writer.event("shed", **rec)
        timeline_mod.instant("shed", rid=req.rid, cause=cause)

    # -- arrivals and admission policy ---------------------------------

    def expire(self, t: float) -> bool:
        """Expiry pass: a request past its deadline decodes only dead
        tokens — shed it (queued) or retire it (resident) with a cause
        instead."""
        deadline_s = self.policy.deadline_s
        queue, active = self.queue, self.active
        progressed = False
        for req in [r for r in queue if t - r.arrival_s > deadline_s]:
            queue.remove(req)
            self.shed_queued(req, "deadline_expired", t)
            progressed = True
        for fl in [f for f in active
                   if t - f.req.arrival_s > deadline_s]:
            active.remove(fl)
            self.finish(fl, t, status="shed", cause="resident_expired")
            progressed = True
        return progressed

    def preempt_one(self) -> bool:
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
        top = max(self.eng.prefill_buckets)
        t_now = self.now()
        deadline_s, shed = self.policy.deadline_s, self.policy.shed
        cands = [fl for fl in self.active
                 if fl.produced_res >= (1 << fl.preempts)
                 and fl.length <= top
                 and (not deadline_s or shed == "off"
                      or t_now - fl.req.arrival_s > 0.75 * deadline_s)]
        if not cands:
            return False
        victim = max(cands, key=lambda fl: len(fl.pages)
                     / max(1, fl.produced))
        self.active.remove(victim)
        # pages AND slot: the re-prefill starts from a zero state in
        # whatever slot it is given then
        self.cache.release(victim)
        self.carry[victim.req.rid] = {
            "prefix": list(victim.out_tokens),
            "t_admit": victim.t_admit, "t_first": victim.t_first,
            "active_s": victim.active_s, "t_last": victim.t_last,
            "preempts": victim.preempts + 1,
        }
        self.queue.append(victim.req)
        self.degrade["preempts"] += 1
        timeline_mod.instant("preempt", rid=victim.req.rid)
        timeline_mod.instant("requeue", rid=victim.req.rid)
        self.writer.event("preempt", rid=victim.req.rid,
                          cause="pool_starved",
                          pages_freed=len(victim.pages),
                          produced=victim.produced)
        return True

    def drain(self, t: float) -> dict:
        """SIGTERM drain: stop admitting, preempt every resident
        into the journal, and commit queued + not-yet-arrived
        requests with the checkpoint tmp->fsync->rename idiom —
        the serving lane's emergency checkpoint."""
        queue, active, carry = self.queue, self.active, self.carry
        cfg = self.eng.cfg
        timeline_mod.instant("drain", queued=len(queue),
                             in_flight=len(active))
        entries = []
        for fl in list(active):
            entries.append(faults_mod.journal_entry(
                fl.req, produced=fl.produced,
                prefix=list(fl.out_tokens),
                preempts=fl.preempts + 1))
            if self.cache is not None:
                self.cache.release(fl)
        active.clear()
        for req in queue:
            c = carry.pop(req.rid, None)
            pfx = c["prefix"] if c else ()
            entries.append(faults_mod.journal_entry(
                req, produced=len(pfx), prefix=list(pfx),
                preempts=c["preempts"] if c else 0))
        queue.clear()
        for req in self.pending[self.idx:]:
            entries.append(faults_mod.journal_entry(req))
        path = (self.journal_path or cfg.serve_journal
                or os.path.join(
                    getattr(self.writer, "out_dir", None) or ".",
                    faults_mod.JOURNAL_NAME))
        faults_mod.write_journal(path, entries, model=cfg.model,
                                 seed=cfg.seed)
        self.writer.event("preempt", scope="drain", cause="sigterm",
                          t=round(t, 4), unfinished=len(entries),
                          journal=path)
        self.eng.print_fn(
            f"serve drain: {len(entries)} unfinished request(s) "
            f"journaled to {path} — relaunch with "
            f"--serve_resume={path} to replay them")
        return {"journal": path, "unfinished": len(entries),
                "reason": "sigterm"}

    @staticmethod
    def feed_of(req: Request, c: dict | None) -> np.ndarray:
        """The prefill token feed: the prompt, plus — for a
        requeued preemption victim — its generated prefix minus
        the newest token (the greedy pass regenerates that one,
        so resumption is exact: zero tokens lost or duplicated)."""
        if c and c["prefix"]:
            return np.concatenate(
                [req.prompt, np.asarray(c["prefix"][:-1], np.int32)])
        return req.prompt

    def admit(self, req: Request) -> None:
        eng, cache = self.eng, self.cache
        t_admit = self.now()
        c = self.carry.pop(req.rid, None)
        timeline_mod.instant("admit", rid=req.rid)
        if not eng.decode_mode:
            self.active.append(_InFlight(req=req, pages=[],
                                         table=np.zeros(0, np.int32),
                                         t_admit=t_admit))
            return
        prefix = c["prefix"] if c else []
        if c:
            self.degrade["requeues"] += 1
        feed = self.feed_of(req, c)
        plen = int(len(feed))
        grant = cache.admit(feed)
        s = pick_bucket(eng.prefill_buckets, plen)
        toks = np.zeros((1, s), np.int32)
        toks[0, :plen] = feed
        (next_tok, logits, self.kv), dt = self._timed(
            "prefill",
            lambda: eng.compiled[("prefill", s)](
                eng.exec_params, self.kv, toks,
                np.int32(plen), grant.write_table), "admit_host")
        # host-side numpy view BEFORE indexing: jax.Array.__getitem__
        # dispatches a jitted gather — a post-warmup compile the
        # zero-recompile contract (and the cache-entry assertion)
        # would catch
        next_tok = np.asarray(next_tok)
        self.steps["prefill"] += 1
        if not c:
            # a re-prefill regenerates an already-counted token
            self.tokens_out += 1
        self.productive_s += dt * (plen / s)
        self.bucket_acct("prefill", s, plen, dt)
        cache.charge(dt)
        fl = _InFlight(
            req=req, pages=grant.pages, table=grant.table, length=plen,
            produced=(len(prefix) if c else 1),
            last_token=int(next_tok[0]),
            t_admit=(c["t_admit"] if c else t_admit),
            t_first=(c["t_first"] if c else self.now()),
            out_tokens=(list(prefix[:-1]) + [int(next_tok[0])]
                        if c else [int(next_tok[0])]),
            active_s=(c["active_s"] + dt if c else 0.0),
            t_last=(c["t_last"] if c else None),
            preempts=(c["preempts"] if c else 0),
            produced_res=(0 if c else 1),
            prefix_shared=grant.shared, slot=grant.slot, ring=grant.ring)
        if self.policy.guard:
            row = np.asarray(logits)
            if self.faults is not None \
                    and self.faults.poison_rids([req.rid]):
                row = np.full_like(np.array(row), np.nan)
                self.announce_nan(req.rid, "prefill")
            if not np.isfinite(row).all():
                fl.t_last = self.now()
                self.finish(fl, self.now(), status="quarantined",
                            cause="nonfinite_logits")
                return
        cache.seed(feed, fl.pages, plen)
        if fl.produced >= req.output_len:
            self.finish(fl, self.now(), status="ok")
        else:
            self.active.append(fl)

    def admit_pass(self) -> bool:
        """One admission pass over the queue's head; True when a request
        was admitted, shed or preempted for."""
        queue, active = self.queue, self.active
        eng, cache, policy = self.eng, self.cache, self.policy
        progressed = False
        if policy.batching == "continuous":
            predictive = policy.shed == "deadline"
            while queue and len(active) < eng.cap:
                head = queue[0]
                if (predictive and self.service_ewma_s is not None
                        and (self.now() - head.arrival_s)
                        + self.service_ewma_s > policy.deadline_s):
                    # predictive shed: queue wait plus the
                    # admit-to-done EWMA already blows the
                    # deadline — reject at admission instead
                    # of decoding a dead answer
                    self.shed_queued(queue.popleft(),
                                     "deadline_predicted", self.now())
                    progressed = True
                    continue
                feed = None
                binds = None
                if cache is not None:
                    feed = self.feed_of(head, self.carry.get(head.rid))
                    binds = cache.blocked_on(feed)
                if binds is None:
                    self.admit(queue.popleft())
                    progressed = True
                    continue
                if binds == "slot_starved":
                    break
                # starved: reclaim cold cache pages first, then the
                # r23 preemption machinery
                if cache.reclaim(feed):
                    continue
                if policy.kv_preempt == "on" and self.preempt_one():
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
            want = min(eng.cap, self.n - self.finished)
            if cache is not None:
                want = min(want, cache.worst_case_room())
            if len(queue) >= want or self.idx == self.n:
                for _ in range(min(want, len(queue))):
                    self.admit(queue.popleft())
                    progressed = True
        return progressed

    # -- the step ------------------------------------------------------

    def _timed(self, kind: str, fn, then: str):
        """Run one device program: ``<kind>_dispatch`` is the call until
        it returns, ``<kind>_wait`` the ``block_until_ready``, both under
        the parent span ``kind`` (flight recorder + any open profiler
        trace, real wall even under a VirtualClock); the loop goes on in
        phase ``then``.  The boundaries' own clock reads charge the
        engine clock."""
        import jax

        clock, phases = self.clock, self.phases
        c0 = clock.now()
        phases.enter(kind + "_dispatch", parent=kind)
        m0 = phases.t
        out = fn()
        phases.enter(kind + "_wait", parent=kind)
        jax.block_until_ready(out)
        phases.enter(then)
        clock.charge(kind, phases.t - m0)
        return out, clock.now() - c0

    def _copy_page(self, src: int, dst: int) -> None:
        """The cache named a copy-on-write: run the warmed page-copy
        program on the pool."""
        self.kv, dt = self._timed(
            "page_copy",
            lambda: self.eng.compiled[("page_copy", 0)](
                self.kv, np.int32(src), np.int32(dst)), "pack")
        self.cache.charge(dt)

    def _writable_rows(self) -> list[_InFlight]:
        """The residents whose append slot is writable this step; the
        rest pause."""
        ensure, copy = self.cache.make_writable, self._copy_page
        return [fl for fl in self.active if ensure(fl, copy)]

    def _inject_hang(self) -> None:
        step = self.steps["decode"] + 1
        hang_s = self.faults.hang_before_decode(step)
        if hang_s:
            self.eng.print_fn(f"inject: hang {hang_s}s before "
                              f"decode step {step}")
            self.writer.event("injected_fault", fault="hang",
                              step=step, seconds=hang_s)
            # REAL wall, whatever the engine clock: the wedged-
            # host signature the watchdog's (real-time)
            # progress oracle exists to catch
            time.sleep(hang_s)

    def _nonfinite_rows(self, logits, sched: list[_InFlight]) -> set[int]:
        """Per-request quarantine: ONE host read of the step's
        logits, rows checked independently — a poisoned
        request retires alone, batch-mates keep their
        (finite) tokens."""
        lg = np.asarray(logits)[:len(sched)]
        hit = (set(self.faults.poison_rids([fl.req.rid for fl in sched]))
               if self.faults is not None else set())
        if hit:
            lg = np.array(lg)   # writable copy to poison
            for i, fl in enumerate(sched):
                if fl.req.rid in hit:
                    lg[i] = np.nan
                    self.announce_nan(fl.req.rid, "decode")
        finite = np.isfinite(lg.reshape(len(lg), -1)).all(axis=1)
        return {i for i in range(len(sched)) if not finite[i]}

    def decode_step(self) -> bool:
        """One decode step over the residents; False when every one of
        them paused on growth/COW starvation — not progress."""
        eng, cache, active = self.eng, self.cache, self.active
        self.phases.enter("pack")
        if self.faults is not None:
            self._inject_hang()
        sched = active
        if cache.on_demand:
            sched = self._writable_rows()
            if not sched and active and self.policy.kv_preempt == "on" \
                    and self.preempt_one():
                # every resident paused on growth: the r23
                # machinery frees a victim's pages and the rest
                # retry in the same step
                sched = self._writable_rows()
            if not sched:
                return False
        rows = len(sched)
        b = pick_bucket(eng.batch_buckets, rows)
        toks = np.zeros((b,), np.int32)
        tables = np.zeros((b, cache.table_cols), np.int32)
        lengths = np.zeros((b,), np.int32)
        mask = np.zeros((b,), bool)
        for i, fl in enumerate(sched):
            toks[i] = fl.last_token
            tables[i] = fl.table
            lengths[i] = fl.length
            mask[i] = True
        (next_toks, logits, self.kv), dt = self._timed(
            "decode",
            lambda: eng.compiled[("decode", b)](
                eng.exec_params, self.kv, toks, tables, lengths, mask),
            "retire")
        self.steps["decode"] += 1
        self.tokens_out += rows
        self.productive_s += dt * (rows / b)
        self.bucket_acct("decode", b, rows, dt)
        if eng.decode_chunk:
            full, win = (len(eng.family.kv_layers),
                         len(eng.family.window_layers))
            chunk = eng.decode_chunk[b]
            held = int(pages_held(lengths, mask, eng.page_size,
                                  eng.table_width).sum())
            self.kv_read[0] += full * (-(-held // chunk) * chunk)
            if win:
                # a window layer reads the pages its window reaches
                chunk = eng.window_chunk[b]
                held = int(window_pages_held(lengths, mask, eng.page_size,
                                             eng.family.window).sum())
                self.kv_read[0] += win * (-(-held // chunk) * chunk)
            self.kv_read[1] += (full + win) * b * eng.table_width
        cache.charge(dt)
        next_toks = np.asarray(next_toks)
        family, counters = eng.family, self.counters
        for j, name in enumerate(family.counters):
            counters[name] += int(next_toks[b + j])
        if family.picks_per_token:
            counters["moe_picks"] += rows * family.picks_per_token
        if eng.state_slots:
            self.state_slot_steps[0] += rows
            self.state_slot_steps[1] += eng.cap
        bad = (self._nonfinite_rows(logits, sched)
               if self.policy.guard else ())
        t_done = self.now()
        token = cache.token
        dropped: set[int] = set()
        for i, fl in enumerate(sched):
            fl.active_s += dt
            fl.t_last = t_done
            if i in bad:
                self.finish(fl, t_done, status="quarantined",
                            cause="nonfinite_logits")
                dropped.add(fl.req.rid)
                continue
            fl.last_token = int(next_toks[i])
            fl.out_tokens.append(fl.last_token)
            token(fl.length)
            fl.length += 1
            fl.produced += 1
            fl.produced_res += 1
            if fl.produced >= fl.req.output_len:
                self.finish(fl, t_done, status="ok")
                dropped.add(fl.req.rid)
        if dropped:
            # paused rows (not in sched) keep their place; retire
            # by rid, not list rebuild from sched
            active[:] = [fl for fl in active
                         if fl.req.rid not in dropped]
        return True

    def classify_step(self) -> None:
        eng, active = self.eng, self.active
        self.phases.enter("pack")
        b = pick_bucket(eng.batch_buckets, len(active))
        x = np.zeros((b,) + tuple(eng.spec.input_shape), np.float32)
        for i, fl in enumerate(active):
            x[i] = eng.classify_input(fl.req)
        _, dt = self._timed(
            "classify",
            lambda: eng.compiled[("classify", b)](eng.variables, x),
            "retire")
        self.steps["classify"] += 1
        self.tokens_out += len(active)
        self.productive_s += dt * (len(active) / b)
        self.bucket_acct("classify", b, len(active), dt)
        t_done = self.now()
        for fl in active:
            fl.t_first = t_done
            fl.produced = 1
            fl.active_s += dt
            fl.t_last = t_done
            self.finish(fl, t_done, status="ok")
        active.clear()

    # -- the loop ------------------------------------------------------

    def idle(self, timeout_s: float | None) -> None:
        """Nothing progressed this iteration: sleep to the next arrival
        (or, with shedding armed and the trace spent, to the next
        deadline)."""
        if self.idx >= self.n:
            if self.policy.shed == "off" or not self.queue:
                raise RuntimeError(
                    "serve engine stalled: no request can "
                    "make progress — KV pool undersized? "
                    "(under --kv_reserve=lazy, "
                    "--kv_preempt=on frees pages by "
                    "preempting the worst resident)")
            # shedding armed: a squeezed pool can pin the
            # queue with nothing resident — idle to the
            # next deadline; the expiry pass drains it
            nxt = (min(r.arrival_s for r in self.queue)
                   + self.policy.deadline_s)
            self.clock.sleep(max(1e-4, nxt - self.now() + 1e-4))
        else:
            gap = self.pending[self.idx].arrival_s - self.now()
            if timeout_s:
                # chunked: an idle arrival gap must never
                # read as a wedged scheduler
                gap = min(gap, timeout_s / 2)
            self.clock.sleep(gap)

    def _watchdog(self, timeout_s: float, last_iter_t: list,
                  on_watchdog) -> watchdog_mod.Watchdog:
        writer = self.writer

        def forensics() -> None:
            # round-17 forensics on the serve lane: the flight-recorder
            # tail + the live-buffer memory dump, best-effort by
            # contract (both swallow their own failures)
            out_dir = getattr(writer, "out_dir", None)
            step = sum(self.steps.values())
            timeline_mod.dump_timeline(out_dir, "serve_watchdog",
                                       step=step)
            if out_dir:
                from tpu_hc_bench.obs import memory as obs_memory
                obs_memory.dump_forensics(out_dir, "serve_watchdog",
                                          step=step)

        return watchdog_mod.Watchdog(
            timeout_s, lambda: last_iter_t[0],
            print_fn=self.eng.print_fn,
            last_record_fn=lambda: getattr(writer, "last_record", None),
            obs_writer=writer if writer.enabled else None,
            on_timeout=on_watchdog, forensics_fn=forensics).start()

    def play(self, drain_handler=None, step_timeout_s=None,
             on_watchdog=None) -> None:
        """Play the trace to its end (or to a drain).  The loop installs
        a real SIGTERM/SIGINT handler unless the caller injected one
        (tests poll a fake; ``install()`` is a no-op off the main
        thread) and, with a step timeout, a scheduler-iteration
        watchdog: a completed iteration IS progress to it — admission,
        shedding and idle arrival waits all count; only a wedged step
        does not."""
        eng, policy, phases = self.eng, self.policy, self.phases
        queue, pending, n = self.queue, self.pending, self.n
        first_look = self.first_look
        decode_mode, shedding = eng.decode_mode, policy.shed != "off"
        self.t0 = self.clock.now()
        # the request-lane timeline anchor: engine-relative instants
        # (arrival_s et al.) placed on the wall by `obs timeline`
        self.writer.event("serve_clock", t_unix=time.time(),
                          t_mono=time.monotonic(),
                          batching=policy.batching)
        own_handler = None
        handler = drain_handler
        if handler is None:
            own_handler = preempt_mod.PreemptionHandler(
                print_fn=eng.print_fn).install()
            handler = own_handler
        timeout_s = watchdog_mod.resolve_timeout(
            step_timeout_s if step_timeout_s is not None
            else eng.cfg.serve_step_timeout_s,
            warmup_step_s=(eng.compile_record["warmup_s"]
                           / max(1, eng.compile_record["buckets"])))
        last_iter_t: list = [None]
        dog = (self._watchdog(timeout_s, last_iter_t, on_watchdog)
               if timeout_s else None)
        loop_m0 = time.monotonic()
        try:
            while self.finished < n:
                phases.enter("arrivals")
                t = self.now()
                idx = self.idx
                while idx < n and pending[idx].arrival_s <= t:
                    first_look[pending[idx].rid] = t
                    queue.append(pending[idx])
                    idx += 1
                self.idx = idx
                if self.faults is not None:
                    self.announce_faults(t)
                if handler is not None and handler.requested():
                    self.drained = self.drain(t)
                    break
                self.loop_iters += 1
                self.queue_depth_sum += len(queue)
                self.queue_depth_max = max(self.queue_depth_max,
                                           len(queue))
                progressed = shedding and self.expire(t)
                phases.enter("admit_host")
                if self.admit_pass():
                    progressed = True
                phases.enter("telemetry")
                blocked = self.blocked_cause()
                t_blocked = self.now()
                if self.active:
                    if not decode_mode:
                        self.classify_step()
                        progressed = True
                    elif self.decode_step():
                        progressed = True
                if not progressed:
                    phases.enter("arrival_wait")
                    self.idle(timeout_s)
                phases.enter("telemetry")
                if blocked is not None:
                    self.charge_blocked(blocked, t_blocked)
                self.emit_records()
                last_iter_t[0] = time.perf_counter()
        finally:
            phases.close()
            self.loop_wall_s = time.monotonic() - loop_m0
            if dog is not None:
                dog.stop()
            if own_handler is not None:
                own_handler.uninstall()

    def close(self) -> None:
        """After the loop: the run's wall, then the terminal ledger
        snapshot (runs shorter than one record window still land their
        cumulative page-second integrals), the last heartbeat, and the
        tail window's sketch + one final signal observation."""
        self.wall = max(self.now(), 1e-9)
        if self.cache is not None and self.writer.enabled:
            self.kv_pool_event()
        if self.fleet is not None:
            self.heartbeat(self.wall)
        self.flush_window()


def summarize(loop: ServeLoop, post_warmup_compiles: int) -> dict:
    """The serve summary of a closed loop and its engine's static facts."""
    eng, policy = loop.eng, loop.policy
    cfg, decode_mode = eng.cfg, eng.decode_mode
    n, wall, done = loop.n, loop.wall, list(loop.done)
    # summary percentiles come from the run-lifetime sketches —
    # exact over every completion, not just the retained ring
    fold = slo_mod.fold_sketches(loop.run_sk)
    attribution = requests_mod.fold_attribution(done)
    kv_fold = None
    if loop.cache is not None:
        kv_fold = kv_mod.fold_ledger(**loop.cache.fold_args(),
                                     request_records=done)
    summary = {
        "workload": "serve",
        "model": cfg.model,
        "batching": policy.batching,
        "arrival": cfg.arrival,
        "arrival_rate": cfg.arrival_rate,
        "requests": n,
        "completed": loop.completed_ok,
        "wall_s": round(wall, 4),
        "tokens": loop.tokens_out,
        "tokens_per_s": round(loop.tokens_out / wall, 3),
        "goodput": round(loop.productive_s / wall, 4),
        "queue_depth_max": loop.queue_depth_max,
        "queue_depth_mean": round(
            loop.queue_depth_sum / loop.loop_iters
            if loop.loop_iters else 0.0, 3),
        "buckets": list(eng.batch_buckets),
        "max_in_flight": eng.cap,
        "kv_page_size": eng.page_size,
        "kv_pages": eng.num_pages,
        # round 22 (obs.kv): pool geometry + the utilization ledger
        "kv_layers": (len(eng.family.kv_layers) if decode_mode else None),
        "kv_pool_bytes": eng.kv_pool_bytes,
        "kv_scale_bytes": eng.kv_scale_bytes,
        "kv_pool": kv_fold,
        **kv_mod.flatten_kv(kv_fold),
        # round 25: the reservation/sharing arms are config
        # identity for this run (regress fingerprints on them)
        "kv_reserve": (policy.kv_reserve if decode_mode else None),
        "prefix_cache": (policy.prefix_cache if decode_mode else None),
        "decode_attention": (eng.decode_attention
                             if decode_mode else None),
        "quant": eng.quant,
        "decode_block_pages": eng.compile_record.get(
            "decode_block_pages"),
        "aot_decode_temp_bytes": eng.compile_record.get(
            "aot_decode_temp_bytes"),
        "kv_pool_temp_ratio": eng.compile_record.get(
            "kv_pool_temp_ratio"),
        # a family with a recurrent-state pool: its bytes, the slots
        # in use at each decode step summed beside slots x steps,
        # the decode steps' expert picks and those that landed on an
        # expert held here
        "state_pool_bytes": eng.state_pool_bytes,
        "kv_read": ({"pages_read": loop.kv_read[0],
                     "pages_rect": loop.kv_read[1]}
                    if eng.decode_chunk else None),
        "state_slots": loop.state_slot_steps[0],
        "state_slot_steps": loop.state_slot_steps[1],
        "ssd_kernel_calls": eng.ssd_kernel_calls,
        "kda_kernel_calls": eng.kda_kernel_calls,
        **loop.counters,
        "post_warmup_compiles": post_warmup_compiles,
        # round 20 (obs.requests): the tail-attribution fold, its
        # regress projection, and the per-bucket occupancy account
        "attribution": attribution,
        **requests_mod.flatten_attribution(attribution),
        "bucket_util": {
            k: {"steps": u[0], "rows": u[1], "active_rows": u[2],
                "wall_s": round(u[3], 4),
                "occupancy": round(u[2] / u[1], 4) if u[1] else 0.0}
            for k, u in loop.butil.items()},
        # the loop's real wall by exclusive phase: conserved (the
        # phases tile the loop; ``loop_wall_s`` is clocked apart)
        "loop_phases": {
            k: {"count": c, "wall_s": round(w, 6)}
            for k, (c, w) in loop.phases.fold.items()},
        "loop_wall_s": round(loop.loop_wall_s, 6),
        **{f"{k}_steps": v for k, v in loop.steps.items()},
        **fold,
        # round 24: the mergeable-sketch account — source label,
        # retention cap, and the fleet-mergeable headline tail
        # (single host: the run sketch IS the merge of its
        # windows, so this equals p99_e2e_ms by construction)
        "latency_source": "sketch",
        "latency_sample_cap": loop.done.maxlen,
        "sketch_windows": loop.win_idx,
        "p99_merged_ms": round(loop.run_sk["e2e_ms"].quantile(99), 3),
        "signals_fired": dict(sorted(loop.sig_engine.fired.items())),
        "signals_fired_total": sum(loop.sig_engine.fired.values()),
    }
    # round 23 degradation account: always present so `obs regress`
    # can gate shed_frac against baselines that predate the knob
    degrade = loop.degrade
    summary["shed_frac"] = round(
        sum(degrade["shed"].values()) / max(1, n), 4)
    summary["degrade"] = {
        "shed": dict(sorted(degrade["shed"].items())),
        "shed_frac": summary["shed_frac"],
        "preempts": degrade["preempts"],
        "requeues": degrade["requeues"],
        "quarantined": degrade["quarantined"],
    }
    if loop.drained is not None:
        summary["drained"] = loop.drained
    if cfg.slo_e2e_ms:
        # windowed SLO burn rate: sustained overload vs transient
        # burst, against the --slo_e2e_ms e2e target
        summary["slo"] = slo_mod.fold_burn_rate(done, cfg.slo_e2e_ms)
    return summary
