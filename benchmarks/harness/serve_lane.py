"""One serve cell, once: the window drives
``serve.cli.build_engine_and_requests`` + ``run_serve`` ->
``ServeEngine.run`` with the benchmark's own weights and traffic; the
records the engine stamps per request are reduced here, and the served
tokens are held against the plain reference after the window has closed.
"""

from __future__ import annotations

import gc
import json
import os
import signal
import threading
import time


from harness import (adapters, checks, device, readers, tracing,
                     traffic)

DRAIN_LIMIT_S = 60.0        # how long past the close an answer is awaited
TAP_STEPS = 24              # consecutive decode steps whose logits are kept
TAP_AFTER = (0.25, 0.6)     # the burst starts this far into the window


class MemWriter:
    """Duck-types ``obs.metrics.MetricsWriter`` in memory: the engine's
    per-request records land in a list, nothing touches the disk.
    ``enabled`` False keeps the engine's periodic telemetry off."""

    enabled = False
    out_dir = None
    last_record = None

    def __init__(self):
        self.records: list[dict] = []

    def event(self, kind: str, **fields) -> None:
        rec = {"kind": kind, **fields}
        self.records.append(rec)
        self.last_record = rec

    def update_manifest(self, fields: dict) -> None:
        pass

    def close(self) -> None:
        pass


class _Tapped:
    """A compiled bucket whose outputs the tap may keep a reference to;
    everything else is the executable's own."""

    def __init__(self, exe, keep):
        self._exe, self._keep = exe, keep

    def __call__(self, *a, **k):
        out = self._exe(*a, **k)
        self._keep(a, out)
        return out

    def __getattr__(self, item):
        return getattr(self._exe, item)


class LogitTap:
    """Keeps, on the device, the logits the timed programs return anyway
    (the engine drops them): every prefill's one row, and the rows of
    ``TAP_STEPS`` consecutive decode steps from a point of the window the
    seed places.  No transfer and no program is added to the window; the
    rows are fetched once it has closed, and held against the reference
    (``checks.sample_with_rows``)."""

    def __init__(self, engine, seed: int, seconds: float):
        lo, hi = TAP_AFTER
        self.after = seconds * float(
            traffic.seed_rng(seed, 8).uniform(lo, hi))
        self.engine, self.programs = engine, engine.compiled
        self.t0 = None
        self.prefill: list = []
        self.decode: list = []
        keep = {"prefill": self._keep_prefill, "decode": self._keep_decode}
        engine.compiled = {
            key: _Tapped(exe, keep[key[0]]) if key[0] in keep else exe
            for key, exe in engine.compiled.items()}

    def arm(self) -> None:
        self.t0 = time.monotonic()

    def _keep_prefill(self, a, out) -> None:
        # (params, kv, tokens [1, s], length, table) -> (token, logits, kv)
        if self.t0 is not None:
            self.prefill.append((a[2][0, :int(a[3])].copy(), int(a[4][0]),
                                 out[1]))

    def _keep_decode(self, a, out) -> None:
        # (params, kv, tokens, tables, lengths, active) -> (tokens,
        # logits, kv)
        if self.t0 is None or len(self.decode) >= TAP_STEPS:
            return
        if self.decode or time.monotonic() - self.t0 >= self.after:
            self.decode.append((a[2].copy(), a[3][:, 0].copy(), a[4].copy(),
                                a[5].copy(), out[1], len(self.prefill)))

    def fetch(self) -> dict:
        """The kept rows on the host; the engine gets its own programs
        back and the device copies are dropped."""
        import numpy as np

        self.engine.compiled = self.programs
        got = {"prefill": [(p, page, np.asarray(lg)[0])
                           for p, page, lg in self.prefill],
               "decode": [(t, pages, n, on, np.asarray(lg), seen)
                          for t, pages, n, on, lg, seen in self.decode]}
        self.prefill, self.decode = [], []
        return got


def serve_flags(arm: dict, mix: dict, seed: int, journal: str) -> list[str]:
    """The lane's own flags for this cell: the configuration's serve arm
    plus the sizes the traffic mix reaches."""
    return [
        f"--model={arm['model']}",
        f"--max_prompt_len={mix['prompt_len']['max']}",
        f"--max_output_len={mix['output_len']['max']}",
        f"--max_in_flight={mix['max_in_flight']}",
        f"--kv_page_size={arm['kv_page_size']}",
        f"--kv_pages={arm.get('kv_pages', 0)}",
        f"--decode_attention={arm['decode_attention']}",
        "--num_requests=1", "--arrival_rate=1",
        f"--seed={seed % (2**31 - 1)}",
        f"--serve_journal={journal}",
    ] + list(arm.get("extra_flags", []))


def to_program_requests(reqs: list[dict]):
    from tpu_hc_bench.serve.arrivals import Request

    return [Request(rid=r["rid"], arrival_s=r["arrival_s"],
                    prompt=r["prompt"], output_len=r["output_len"])
            for r in reqs]


def _first_diff(a, b) -> str:
    import jax

    fa = dict(jax.tree_util.tree_flatten_with_path(a)[0])
    fb = dict(jax.tree_util.tree_flatten_with_path(b)[0])
    for k in sorted(set(fa) | set(fb), key=str):
        if fa.get(k) != fb.get(k):
            return f"{jax.tree_util.keystr(k)}: {fa.get(k)} vs {fb.get(k)}"
    return "tree structures differ"


def run_window(engine, requests, seconds: float, close_at_s: float | None,
               tracer=None, tap=None):
    """Play ``requests``; returns ``(finished requests' records, shed or
    quarantined ones, the engine's summary, wall seconds)``.
    ``close_at_s`` ends the window by the lane's own drain (SIGTERM):
    what is unfinished then is journaled, not failed; without it the same
    drain is the limit on how long an answer is awaited."""
    from tpu_hc_bench.serve.cli import run_serve

    writer = MemWriter()
    limit = close_at_s if close_at_s is not None else seconds + DRAIN_LIMIT_S
    timer = threading.Timer(limit, os.kill, (os.getpid(), signal.SIGTERM))
    timer.daemon = True
    if tracer is not None:
        tracer.arm()
    if tap is not None:
        tap.arm()
    t0 = time.monotonic()
    timer.start()
    try:
        summary = run_serve(engine, to_program_requests(requests), writer)
    finally:
        timer.cancel()
        wall = time.monotonic() - t0
        if tracer is not None:
            tracer.stop()
    records = [r for r in writer.records if r["kind"] == "request"]
    others = [r for r in writer.records
              if r["kind"] in ("shed", "quarantine")]
    return records, others, summary, wall


def build_engine(cfg: dict, mix: dict, seed: int, journal: str, log):
    """The lane's warmed engine for this cell's sizes (its own AOT
    ladder; compiles on a cold cache)."""
    from tpu_hc_bench import flags as flags_mod
    from tpu_hc_bench.serve.cli import build_engine_and_requests

    pcfg = flags_mod.parse_flags(
        serve_flags(cfg["serve_arm"], mix, seed, journal), workload="serve")
    engine, _ = build_engine_and_requests(pcfg, log)
    return engine


def load_weights(engine, cfg: dict, seed: int):
    """The benchmark's weights for ``seed`` in place of whatever the
    engine holds (same tree, same shapes, same type): the old ones are
    dropped first, so the two never sit on the chip together."""
    import jax

    if engine.quant != "off":
        raise RuntimeError("weight injection covers the unquantized arm")
    sig = lambda t: jax.tree.map(                           # noqa: E731
        lambda x: (tuple(x.shape), str(x.dtype)), t)
    want = sig(engine.params)
    engine.params = engine.exec_params = engine.variables = None
    tree = adapters.program_weights(cfg, seed)
    if sig(tree) != want:
        raise RuntimeError(
            "the program's parameter tree no longer matches the "
            "benchmark's adapter (benchmarks/harness/adapters.py): "
            f"{_first_diff(want, sig(tree))}")
    engine.params = engine.exec_params = tree
    engine.variables = {"params": tree}
    return tree


def warm_up(engine, cfg: dict, mix: dict, seed: int) -> None:
    """First executions of every bucket the mix reaches, in set-up."""
    warm = traffic.generator_of(mix).warmup(
        mix, engine.prefill_buckets, engine.cap,
        adapters.family_of(cfg).vocab_size(cfg), seed)
    run_window(engine, warm, 30.0, None)


def window_account(ctx: dict, attempted: int, unfinished: list) -> dict:
    """What the window held, for telling a seed's work from the host's
    time: wall, tokens, requests begun, and steps and host-timed wall of
    prefill and decode; whether a backlog grew
    (``readers.queue_p50_by_thirds``) and, where the lane's drain closed the window, how many
    requests were in flight and how many still queued then."""
    acc = {"wall_s": ctx["window_s"], "tokens": ctx["tokens_done"],
           "begun": attempted}
    thirds = readers.queue_p50_by_thirds(ctx["records"])
    if thirds:
        acc["queue_p50_ms_thirds"] = thirds
    if ctx["summary"].get("drained"):
        in_flight = sum(1 for e in unfinished if e.get("produced"))
        acc["at_close"] = {"in_flight": in_flight,
                           "queued": len(unfinished) - in_flight}
    for kind in ("prefill", "decode"):
        t = readers.bucket_totals(ctx, kind)
        if t:
            acc[kind] = {"steps": t[0], "wall_s": t[3]}
    return acc


def run_cell(cell: dict, cfg: dict, mix: dict, args, t_proc: float,
             dev: dict, workdir: str, rehearsal: bool = False) -> dict:
    import jax

    counter = device.CompileCounter()
    fam = adapters.family_of(cfg)
    log = lambda m: print(f"[serve] {m}", flush=True)     # noqa: E731
    journal = os.path.join(workdir, "journal.json")
    engine = build_engine(cfg, mix, args.seed, journal, log)
    tree = load_weights(engine, cfg, args.seed)
    requests = traffic.generator_of(mix).requests(
        mix, args.seconds, args.seed, fam.vocab_size(cfg))
    warm_up(engine, cfg, mix, args.seed)
    tap = LogitTap(engine, args.seed, args.seconds)
    tracer = None
    if args.trace:
        tracer = tracing.WindowTracer(
            os.path.join(workdir, "trace"), args.seconds)
    setup_s = time.monotonic() - t_proc
    log(f"set-up {setup_s:.1f}s; window: {len(requests)} requests over "
        f"{args.seconds}s, {counter.total} programs compiled or loaded")

    # ---- the window
    close_at = args.seconds if mix.get("close_window_at_seconds") else None
    counter.arm()
    records, others, summary, wall = run_window(
        engine, requests, args.seconds, close_at, tracer, tap)
    compiles = counter.disarm()
    mem_peak = device.memory_peak_bytes(cell["chips"])
    tapped = tap.fetch()
    log(f"memory_stats: {jax.local_devices()[0].memory_stats()}")
    unfinished = []
    if summary.get("drained"):
        with open(journal) as f:
            unfinished = json.load(f)["requests"]
    finished_rids = {r["id"] for r in records}
    by_rid = {r["rid"]: r for r in requests}
    partial_tokens = sum(len(e.get("prefix") or ()) for e in unfinished
                         if e.get("produced"))
    tokens_done = sum(len(r["generated"]) for r in records) + partial_tokens
    if close_at is None:
        # every request was due in the window: one that did not finish by
        # the drain limit never came
        missing = [r for r in requests if r["rid"] not in finished_rids]
    else:
        missing = []
    failed = len(others) + len(missing)
    attempted = (len(requests) if close_at is None
                 else len(records) + len(others)
                 + sum(1 for e in unfinished if e.get("produced")))

    ctx = {
        "cell": cell, "config": cfg, "family": fam, "mix": mix,
        "seconds": args.seconds,
        "window_s": wall, "records": records, "requests": requests,
        "failed": failed, "summary": summary, "tokens_done": tokens_done,
        "compiles_in_window": compiles,
        "peaks": None if rehearsal else device.peaks(dev["kind"]),
        "drain_limit_ms": 1e3 * DRAIN_LIMIT_S, "chips": cell["chips"],
        "setup_s": setup_s, "trace": None,
    }
    log(f"window {wall:.2f}s: {len(records)} finished, {len(unfinished)} "
        f"unfinished at the close, {failed} failed, {tokens_done} tokens, "
        f"{compiles} compiles")

    # ---- free the program's state, then the reference
    sample = checks.sample_with_rows(records, by_rid, tapped)
    max_ctx = engine.max_ctx
    del engine, tree, tap, tapped
    gc.collect()
    if tracer is not None:
        ctx["trace"] = tracer.reduce(cell["chips"])
    numbers = checks.serve_numbers(cfg, args.seed, sample, max_ctx,
                                   mix["output_len"]["max"])
    numbers["requests_unanswered"] = (float(len(missing)), 0.0)
    numbers["answers_short"] = (float(sum(
        1 for r in records
        if len(r["generated"]) != by_rid[r["id"]]["output_len"])), 0.0)
    return {"ctx": ctx, "numbers": numbers, "attempted": attempted,
            "failed": failed, "memory_peak_bytes": mem_peak,
            "setup_s": setup_s,
            "also": {"serve_ttft_p90_ms":
                     readers.request_percentile(ctx, "ttft_ms", 90),
                     "window": window_account(ctx, attempted, unfinished)}}
