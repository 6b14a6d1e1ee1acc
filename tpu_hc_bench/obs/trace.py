"""Reusable perfetto-trace analysis for ``jax.profiler`` traces.

The one home of trace parsing (it began as one-off experiment code in
rounds 4-6).  The load-bearing pieces and their history:

- **Leaf-op extraction with same-tid containment** (``leaf_device_ops``):
  an X event that strictly contains >= 2 other X events *on its own
  (pid, tid) track* is a container (step marker, jit program envelope,
  region) and would double-count its children — attribution wants leaf
  ops only.  Containment is tested WITHIN one track on purpose (round
  6, ADVICE r5): a genuinely long leaf on one track merely
  *overlapping* short ops on a sibling track (a concurrent DMA/stream
  track) is real device time, not a container, and a cross-tid test
  silently dropped it.  The >= 2 threshold keeps identical-interval op
  pairs, which "contain" each other once.
- **Op classification** (``classify``): substring rules whose ORDER
  matters (collectives before "reduce", casts before "conv", ...), each
  ordering forced by a real miscount — see the inline comments.
- **Step reconstruction + bucket attribution** (``summarize_trace``):
  new here.  Steps come from the profiler's step track when present,
  else from top-level container envelopes; each step's device time is
  attributed into compute / collective / host-transfer buckets, and
  idle-bubble is the wall span no device track covers.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
from collections import defaultdict

# The step-attribution buckets, in display order.  "host-transfer" is
# host<->device traffic (infeed/outfeed and host-named DMA); on-device
# data movement (copies, transposes, relayouts) is device work and
# stays in "compute".  "idle-bubble" is wall time inside a step that NO
# device track covers — the device waiting on the host or a dependency
# stall.
BUCKETS = ("compute", "collective", "host-transfer", "idle-bubble")


# ---------------------------------------------------------------------
# loading


def find_trace_file(path: str) -> str:
    """Resolve a trace dir (or direct file path) to the newest
    ``*.trace.json.gz`` under it."""
    if os.path.isfile(path):
        return path
    paths = glob.glob(f"{path}/**/*.trace.json.gz", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no trace.json.gz under {path}")
    return sorted(paths)[-1]


def load_events(path: str) -> list[dict]:
    """Load the perfetto ``traceEvents`` list from a trace dir or file."""
    f = find_trace_file(path)
    opener = gzip.open if f.endswith(".gz") else open
    with opener(f, "rt") as fh:
        return json.load(fh)["traceEvents"]


def device_pids(events: list[dict]) -> set:
    """Pids whose process_name marks a device (TPU/GPU) track."""
    return {
        e["pid"] for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
        and any(k in str(e.get("args", {}).get("name", ""))
                for k in ("TPU", "GPU", "/device:"))
    }


def _device_tracks(events: list[dict]) -> dict[tuple, list[dict]]:
    """Positive-duration X events on device pids, grouped per (pid, tid)
    track and start-sorted (ties broken longest-first so containers sort
    before the children they start with)."""
    pids = device_pids(events)
    if not pids:
        # fail as loudly as a missing trace: an attribution table
        # silently built from zero device events reads as "no hot ops"
        raise RuntimeError(
            "trace has no TPU/GPU device track — did the run fall back "
            "to CPU?")
    by_track: dict[tuple, list] = defaultdict(list)
    for e in events:
        if (e.get("ph") == "X" and e.get("pid") in pids
                and e.get("dur", 0) > 0):
            by_track[(e["pid"], e.get("tid", 0))].append(e)
    for evs in by_track.values():
        evs.sort(key=lambda e: (e["ts"], -e.get("dur", 0)))
    return by_track


def _is_container(evs: list[dict], i: int) -> bool:
    """Does start-sorted ``evs[i]`` strictly contain >= 2 later events on
    its own track?  (The same-tid containment rule — module docstring.)"""
    e = evs[i]
    end = e["ts"] + e["dur"]
    contained = 0
    j = i + 1
    n = len(evs)
    # events are start-sorted: scan candidates starting inside
    # [ts, end) — leaves exit immediately, containers after 2
    while j < n and evs[j]["ts"] < end and contained < 2:
        if evs[j]["ts"] + evs[j].get("dur", 0) <= end:
            contained += 1
        j += 1
    return contained >= 2


def _split_tracks(
    tracks: dict[tuple, list[dict]], skip_tracks: set | None = None,
) -> tuple[list[dict], dict[tuple, list[dict]]]:
    """ONE containment scan over all tracks: ``(leaves,
    containers_by_track)``.  Every consumer (op aggregation, step
    reconstruction, bucket attribution) shares this split — on a real
    trace the scan is the dominant cost and must not run twice."""
    leaves: list[dict] = []
    containers: dict[tuple, list[dict]] = {}
    for key, evs in tracks.items():
        if skip_tracks and key in skip_tracks:
            continue
        cs: list[dict] = []
        for i, e in enumerate(evs):
            (cs if _is_container(evs, i) else leaves).append(e)
        containers[key] = cs
    return leaves, containers


def _aggregate(leaves: list[dict]) -> tuple[dict[str, float],
                                            dict[str, int]]:
    ops: dict[str, float] = defaultdict(float)
    counts: dict[str, int] = defaultdict(int)
    for e in leaves:
        ops[e["name"]] += e["dur"]
        counts[e["name"]] += 1
    return dict(ops), dict(counts)


def leaf_device_ops(
    events: list[dict], skip_tracks: set | None = None,
) -> tuple[dict[str, float], dict[str, int]]:
    """Aggregate leaf device-op durations (us) + raw event counts.

    Containers (same-tid containment rule) are excluded; counts are raw
    event counts over all traced steps and device pids — divide by the
    traced-step count for per-step instruction counts.
    ``skip_tracks``: (pid, tid) keys to ignore entirely (the step-marker
    track, whose envelopes live alone on their own track and would
    otherwise be kept as giant "leaves").
    """
    leaves, _ = _split_tracks(_device_tracks(events), skip_tracks)
    return _aggregate(leaves)


def leaf_intervals(events: list[dict]) -> list[tuple[str, float, float]]:
    """``(name, start_us, end_us)`` for every leaf device op, the
    step-marker track excluded — the interval-level view
    ``obs.efficiency.collective_overlap`` needs to tell an *exposed*
    collective (device otherwise idle) from one hidden behind concurrent
    compute on a sibling track."""
    tracks = _device_tracks(events)
    st = _step_track(events, tracks)
    leaves, _ = _split_tracks(tracks, {st} if st is not None else None)
    return [(e["name"], e["ts"], e["ts"] + e["dur"]) for e in leaves]


def device_op_times(trace_dir: str) -> tuple[dict[str, float],
                                             dict[str, int]]:
    """Aggregate device-track op durations (us) + event counts from the
    newest perfetto trace under ``trace_dir``.

    The profiler's step-marker track (when present) is excluded: its
    digit-named envelopes each span a whole step and would otherwise
    land in the attribution table as giant "elementwise/other" leaves.
    """
    events = load_events(trace_dir)
    tracks = _device_tracks(events)
    st = _step_track(events, tracks)
    leaves, _ = _split_tracks(tracks, {st} if st is not None else None)
    return _aggregate(leaves)


# ---------------------------------------------------------------------
# op classification


def classify(name: str) -> str:
    """Op class from the trace event name (XLA instruction name)."""
    n = name.lower()
    # order matters — later checks use substrings the earlier classes
    # also contain:
    #   collectives first ("all-reduce" would otherwise hit "reduce");
    #   reductions before conv ("convert_reduce_fusion" contains "conv"
    #   but its work is the reduction, the cast is fused in);
    #   casts/relayouts before conv ("bitcast_convert"/"convert" contain
    #   "conv" but move/cast bytes, no MXU work)
    if any(k in n for k in ("all-reduce", "allreduce", "all-gather",
                            "allgather", "reduce-scatter", "all-to-all",
                            "collective", "permute", "psum")):
        return "collective"
    if any(k in n for k in ("reduce", "norm", "softmax")):
        return "reduce/norm"
    # select-and-scatter is max-pool BACKWARD (a windowed reduction, not
    # routing) — must be caught before the gather/sort class below would
    # claim its "scatter" substring
    if "select-and-scatter" in n:
        return "pool-bwd"
    # routing/permutation work (MoE dispatch, embedding lookups): sorts,
    # gathers, scatters — split out from elementwise/other so the ragged
    # MoE and ncf attributions can see it (plain "gather" lands here;
    # "all-gather" was already caught by the collective class above)
    if any(k in n for k in ("sort", "gather", "scatter", "cumsum", "iota")):
        return "gather/sort"
    if any(k in n for k in ("copy", "transpose", "reshape", "bitcast",
                            "convert", "concatenate", "slice", "pad")):
        return "data-movement"
    if "conv" in n:
        return "conv"
    if "dot" in n or "matmul" in n or "einsum" in n:
        return "matmul"
    if any(k in n for k in ("infeed", "outfeed", "barrier", "sync")):
        return "infra"
    return "elementwise/other"


def bucket_of(name: str) -> str:
    """Step-attribution bucket for one leaf op (see ``BUCKETS``)."""
    cls = classify(name)
    if cls == "collective":
        return "collective"
    if cls == "infra" or "host" in name.lower():
        return "host-transfer"
    return "compute"


# ---------------------------------------------------------------------
# step reconstruction + bucket attribution


@dataclasses.dataclass
class StepBuckets:
    """One reconstructed step: wall span + per-bucket device time (us).

    Bucket sums can exceed ``dur_us`` when several device tracks run
    concurrently (compute overlapping a DMA stream is real device time
    on both); ``idle_us`` is the part of the span NO track covers.
    """

    index: int
    start_us: float
    dur_us: float
    buckets: dict[str, float]

    @property
    def idle_us(self) -> float:
        return self.buckets.get("idle-bubble", 0.0)


@dataclasses.dataclass
class TraceSummary:
    steps: list[StepBuckets]
    totals: dict[str, float]        # per-bucket us summed over steps
    step_source: str                # "step-track" | "envelopes" | "span"

    def fractions(self) -> dict[str, float]:
        total = sum(self.totals.values())
        if not total:
            return {b: 0.0 for b in self.totals}
        return {b: v / total for b, v in self.totals.items()}


def _step_track(events: list[dict],
                tracks: dict[tuple, list[dict]]) -> tuple | None:
    """The profiler's step-marker track, if one exists.

    Preferred: a device-pid track whose thread_name metadata says
    "Steps" (the XLA profiler convention).  Fallback: a track whose
    events are ALL digit-named (step numbers) — some converter versions
    drop the thread_name record.
    """
    named = {
        (e["pid"], e.get("tid", 0))
        for e in events
        if e.get("ph") == "M" and e.get("name") == "thread_name"
        and "step" in str(e.get("args", {}).get("name", "")).lower()
    }
    for key in tracks:
        if key in named:
            return key
    digit_tracks = [
        key for key, evs in tracks.items()
        if len(evs) >= 1 and all(e["name"].strip().isdigit() for e in evs)
    ]
    if digit_tracks:
        return max(digit_tracks, key=lambda k: len(tracks[k]))
    return None


def _interval_union(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by a set of [start, end) intervals."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _spans_from(
    tracks: dict[tuple, list[dict]], st: tuple | None,
    containers_by_track: dict[tuple, list[dict]],
) -> tuple[list[tuple[float, float]], str]:
    """Per-step [start, end) wall spans from an already-split trace.

    Source is one of:
      - ``"step-track"``: the profiler's dedicated step-number track;
      - ``"envelopes"``: top-level same-tid container events on the
        busiest track (jit program envelopes — one per dispatched step);
      - ``"span"``: no structure found; one span covering all device
        activity (bucket totals stay right, per-step resolution is lost).
    """
    if st is not None:
        spans = [(e["ts"], e["ts"] + e["dur"]) for e in tracks[st]]
        return sorted(spans), "step-track"
    # envelope fallback: top-level containers on the track holding them
    best: list[tuple[float, float]] = []
    for cs in containers_by_track.values():
        # top-level only: drop containers nested inside an earlier one
        # (cs is start-sorted because the track was)
        spans, covered_end = [], -float("inf")
        for e in cs:
            ts, end = e["ts"], e["ts"] + e["dur"]
            if ts >= covered_end:
                spans.append((ts, end))
                covered_end = end
        if len(spans) > len(best):
            best = spans
    if best:
        return best, "envelopes"
    lo = min(e["ts"] for evs in tracks.values() for e in evs)
    hi = max(e["ts"] + e["dur"] for evs in tracks.values() for e in evs)
    return [(lo, hi)], "span"


def step_spans(events: list[dict]) -> tuple[list[tuple[float, float]], str]:
    """Reconstruct per-step [start, end) wall spans from a trace."""
    tracks = _device_tracks(events)
    st = _step_track(events, tracks)
    _, containers = _split_tracks(tracks,
                                  {st} if st is not None else None)
    return _spans_from(tracks, st, containers)


def summarize_trace(events: list[dict]) -> TraceSummary:
    """Per-step bucket attribution for a loaded trace.

    Each leaf op's duration is clipped to the step spans it overlaps and
    summed into its bucket; idle-bubble is each span's wall time no
    device track covers.  The step-marker track (when present) defines
    the spans and is excluded from attribution — its envelopes are not
    device work.  One track split serves leaves and spans alike.
    """
    tracks = _device_tracks(events)
    st = _step_track(events, tracks)
    leaves, containers = _split_tracks(tracks,
                                       {st} if st is not None else None)
    spans, source = _spans_from(tracks, st, containers)
    # one start-sorted sweep instead of re-scanning every leaf per span
    # (spans are sorted and disjoint by construction): j tracks the
    # first leaf not entirely before the current span; real traces hold
    # ~1e5 leaves over tens of spans, where O(steps x leaves) hurts
    leaves.sort(key=lambda e: e["ts"])
    n = len(leaves)
    j = 0
    steps: list[StepBuckets] = []
    for idx, (lo, hi) in enumerate(spans):
        while j < n and leaves[j]["ts"] + leaves[j]["dur"] <= lo:
            j += 1
        buckets = {b: 0.0 for b in BUCKETS}
        busy: list[tuple[float, float]] = []
        k = j
        while k < n and leaves[k]["ts"] < hi:
            e = leaves[k]
            k += 1
            s, t = max(e["ts"], lo), min(e["ts"] + e["dur"], hi)
            if t <= s:
                continue
            buckets[bucket_of(e["name"])] += t - s
            busy.append((s, t))
        buckets["idle-bubble"] = max(0.0, (hi - lo) - _interval_union(busy))
        steps.append(StepBuckets(index=idx, start_us=lo, dur_us=hi - lo,
                                 buckets=buckets))
    totals = {b: sum(s.buckets[b] for s in steps) for b in BUCKETS}
    return TraceSummary(steps=steps, totals=totals, step_source=source)


def summarize_trace_dir(trace_dir: str) -> TraceSummary:
    return summarize_trace(load_events(trace_dir))


# ---------------------------------------------------------------------
# formatting — shared by the driver's post-run summary and the CLI


def format_summary(summary: TraceSummary, per_step: bool = True,
                   title: str = "trace summary") -> list[str]:
    """Human-readable bucket table."""
    lines = [f"{title}: {len(summary.steps)} step(s) "
             f"(boundaries: {summary.step_source})"]
    frac = summary.fractions()
    total = sum(summary.totals.values())
    lines.append(f"{'bucket':>15s} {'us':>12s} {'frac':>7s}")
    for b in BUCKETS:
        lines.append(f"{b:>15s} {summary.totals[b]:12.0f} "
                     f"{frac.get(b, 0.0):6.1%}")
    lines.append(f"{'total':>15s} {total:12.0f}")
    if per_step and len(summary.steps) > 1:
        lines.append("per-step (us): "
                     + " ".join(f"{s.dur_us:.0f}" for s in summary.steps))
    return lines


def diff_buckets(a: dict[str, float], b: dict[str, float],
                 label_a: str = "a", label_b: str = "b") -> list[str]:
    """Bucket-level delta table: the "collective +40%, compute flat" view."""
    lines = [f"{'bucket':>15s} {label_a:>12s} {label_b:>12s} {'delta':>8s}"]
    for bucket in sorted(set(a) | set(b),
                         key=lambda k: -(b.get(k, 0.0) + a.get(k, 0.0))):
        va, vb = a.get(bucket, 0.0), b.get(bucket, 0.0)
        if va:
            delta = f"{(vb - va) / va:+7.1%}"
        elif vb:
            delta = "    new"
        else:
            delta = "      -"
        lines.append(f"{bucket:>15s} {va:12.0f} {vb:12.0f} {delta:>8s}")
    return lines
