"""The reduction from the profiler's xplane trace to device metrics:
busy union, idle share, per-operation durations, exposed collective time
and the idle gaps charged to what the program says it was doing.

Planes named ``/device:TPU:<n>`` are chips; on each, the line ``XLA Ops``
holds one event per executed operation (start and duration in
nanoseconds).  ``while`` / ``conditional`` bodies nest inside their
parent's event, so the busy time is the UNION of intervals, never a sum.
Host planes hold the PROGRAM's own spans on the same clock (``hc:<name>``:
``tpu_hc_bench.obs.timeline`` writes every live span and every phase of the
serve loop into an open trace), which is how a gap gets its name.  The
spans nest (``decode`` holds ``decode_dispatch`` and ``decode_wait``), so a
moment belongs to the INNERMOST span open then.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "hc:"
NO_SPAN = "no span open"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|send|recv)", re.I)
# control-flow wrappers whose duration is their body's: they are skipped
# in per-name sums (their bodies are listed) but kept in the busy union
WRAPPERS = re.compile(r"^(while|conditional|call)(\.|$|-)")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".txt"):
        with open(path) as f:
            return ProfileData.from_serialized_xspace(
                ProfileData.text_proto_to_serialized_xspace(f.read()))
    return ProfileData.from_file(path)


def device_ops(profile) -> dict[int, list[tuple[str, float, float]]]:
    """chip id -> ``[(name, start_s, end_s)]`` of its ``XLA Ops`` line."""
    out: dict[int, list] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            evs = [(e.name, e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9)
                   for e in line.events]
            out.setdefault(int(m.group(1)), []).extend(evs)
    return out


def host_spans(profile, prefix: str = SPAN_PREFIX
               ) -> list[tuple[str, float, float]]:
    """``(name, start_s, end_s)`` of every event of the host planes whose
    name starts with ``prefix`` (the program's ``hc:`` spans), prefix
    stripped, by start."""
    out = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name[len(prefix):], e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9))
    return sorted(out, key=lambda s: s[1])


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


SHAPE = re.compile(r"= \(?([a-z0-9]+\[[0-9,]*\])")


def clean_name(name: str) -> str:
    """``%copy.348 = f32[24,16,641,16,128]{...} copy(...)`` ->
    ``copy.348:f32[24,16,641,16,128]``: the operation's own name in its
    program and the shape of its (first) result, so one operation's calls
    sum, two operations stay apart and the reader sees what was moved."""
    head = name.lstrip("%").split(" ")[0]
    m = SHAPE.search(name)
    return f"{head}:{m.group(1)}" if m else head


def is_custom_call(name: str) -> bool:
    """A Mosaic (Pallas) kernel shows as a ``custom-call`` operation."""
    return " custom-call(" in name


def _kind(name: str) -> str:
    head = name.lstrip("%")
    if COLLECTIVE.match(head):
        return "collective"
    return "wrapper" if WRAPPERS.match(head) else "compute"


def reduce_trace(profile, chips: int | None = None) -> dict:
    """Everything the per-layer readers and the result line take from a
    trace.  Times are seconds; per-chip figures are averaged over the
    chips that ran anything."""
    ops = device_ops(profile)
    if chips:
        ops = {k: v for k, v in sorted(ops.items())[:chips]}
    ops = {k: v for k, v in ops.items() if v}
    if not ops:
        raise ValueError("the trace holds no device operation")
    t0 = min(s for evs in ops.values() for _, s, _ in evs)
    t1 = max(e for evs in ops.values() for _, _, e in evs)
    window = t1 - t0
    busy_s, exposed_s, coll_s = [], [], []
    by_name: dict[str, float] = {}
    kernels: dict[str, float] = {}
    gaps: list[tuple[float, float]] = []
    for chip, evs in sorted(ops.items()):
        busy = union((s, e) for _, s, e in evs)
        busy_s.append(total(busy))
        kinds = [_kind(n) for n, _, _ in evs]
        coll = union((s, e) for (_, s, e), k in zip(evs, kinds)
                     if k == "collective")
        comp = union((s, e) for (_, s, e), k in zip(evs, kinds)
                     if k == "compute")
        coll_s.append(total(coll))
        exposed_s.append(total(subtract(coll, comp)))
        for (n, s, e), k in zip(evs, kinds):
            if k == "wrapper":
                continue
            key = clean_name(n)
            by_name[key] = by_name.get(key, 0.0) + (e - s) / len(ops)
            if is_custom_call(n):
                kernels[key] = kernels.get(key, 0.0) + (e - s) / len(ops)
        if chip == min(ops):
            edges = [(t0, t0)] + busy + [(t1, t1)]
            gaps = [(a[1], b[0]) for a, b in zip(edges, edges[1:])
                    if b[0] > a[1]]
    n = len(ops)
    return {
        "window_s": window, "t0": t0, "chips": n,
        "busy_s": sum(busy_s) / n,
        "collective_s": sum(coll_s) / n,
        "collective_exposed_s": sum(exposed_s) / n,
        "ops": by_name,
        "custom_calls": kernels,
        "gaps": gaps,
    }


def innermost_points(spans) -> list[tuple[float, str]]:
    """Change points ``(t, label)``: from ``t`` until the next point the
    innermost open span (the one that started last among those open) is
    ``label``.  ``spans`` are sorted by start, so the greatest open index
    is the innermost."""
    edges = sorted([(s, 0, i) for i, (_, s, _) in enumerate(spans)]
                   + [(e, 1, i) for i, (_, _, e) in enumerate(spans)])
    open_now: set[int] = set()
    points: list[tuple[float, str]] = []
    for t, closing, i in edges:
        (open_now.discard if closing else open_now.add)(i)
        label = spans[max(open_now)][0] if open_now else NO_SPAN
        if points and points[-1][0] == t:
            points[-1] = (t, label)
        elif not points or points[-1][1] != label:
            points.append((t, label))
    return points


def charge_gaps(gaps, spans) -> dict[str, dict]:
    """span name -> idle seconds ``began_in_s`` / ``split_s`` and the
    number of gaps that began in it.  Each gap is charged twice: whole, to
    the innermost span open when it began, and split, second by second,
    among the innermost spans open while it lasted.  A gap begins while
    the host still waits for the program that just ended, so ``began in``
    reads ``decode_wait`` where ``split`` shows who held the device up
    afterwards."""
    points = innermost_points(spans)
    times = [t for t, _ in points]
    out: dict[str, dict] = {}

    def row(k):
        return out.setdefault(
            points[k][1] if k >= 0 else NO_SPAN,
            {"began_in_s": 0.0, "gaps": 0, "split_s": 0.0})

    for g0, g1 in gaps:
        k = bisect.bisect_right(times, g0) - 1
        first = row(k)
        first["began_in_s"] += g1 - g0
        first["gaps"] += 1
        t = g0
        while k + 1 < len(times) and times[k + 1] < g1:
            row(k)["split_s"] += times[k + 1] - t
            k, t = k + 1, times[k + 1]
        row(k)["split_s"] += g1 - t
    return out


def name_gaps(gaps, spans, top: int = 10) -> list[list]:
    """The result line's ``idle_gaps``: the idle seconds split among the
    program's innermost spans open while each gap lasted (``arrival_wait``,
    ``decode_dispatch``, ``retire``), the largest first."""
    split = {k: v["split_s"] for k, v in charge_gaps(gaps, spans).items()
             if v["split_s"] > 0}
    return [[k, v] for k, v in sorted(split.items(),
                                      key=lambda kv: -kv[1])[:top]]


def top_ops(by_name: dict, top: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(by_name.items(),
                                      key=lambda kv: -kv[1])[:top]]


def kernel_time(by_name: dict, pattern: str) -> float:
    """Summed duration of the operations whose name matches."""
    rx = re.compile(pattern)
    return sum(v for k, v in by_name.items() if rx.search(k))
