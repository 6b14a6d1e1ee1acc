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

A 6 s trace of a fast serve lane holds millions of events, so nothing here
walks them in Python but the one pass that reads them: the events of a
chip become arrays (start, end, an integer id a distinct raw name), names
are cleaned and classified once a DISTINCT name, intervals are merged by
a running maximum over sorted arrays, per-name seconds are a ``bincount``
and the idle gaps are charged by ``searchsorted``.
"""

from __future__ import annotations

import array
import glob
import os
import re
from typing import NamedTuple

import numpy as np

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


def parse(serialized: bytes):
    """The profile of a serialized trace (what a profiler session's
    ``stop()`` returns, and what an ``.xplane.pb`` file holds)."""
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(serialized)


def load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".txt"):
        with open(path) as f:
            return parse(ProfileData.text_proto_to_serialized_xspace(f.read()))
    return ProfileData.from_file(path)


class Ops(NamedTuple):
    """One chip's ``XLA Ops`` events: the distinct raw names in order of
    first appearance, and per event the index of its name and its start
    and end in seconds."""
    names: list
    ids: np.ndarray
    start: np.ndarray
    end: np.ndarray


def device_ops(profile) -> dict[int, Ops]:
    """chip id -> the events of its ``XLA Ops`` line, as arrays."""
    raw: dict[int, tuple] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        index, ids, start, dur = raw.setdefault(
            int(m.group(1)),
            ({}, array.array("q"), array.array("d"), array.array("d")))
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                ids.append(index.setdefault(e.name, len(index)))
                start.append(e.start_ns)
                dur.append(e.duration_ns)
    out = {}
    for chip, (index, ids, start, dur) in raw.items():
        start_ns = np.frombuffer(start, dtype=np.float64)
        out[chip] = Ops(list(index), np.frombuffer(ids, dtype=np.int64),
                        start_ns * 1e-9,
                        (start_ns + np.frombuffer(dur, np.float64)) * 1e-9)
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


def _pairs(intervals) -> np.ndarray:
    if not isinstance(intervals, np.ndarray):
        intervals = list(intervals)
    return np.asarray(intervals, dtype=np.float64).reshape(-1, 2)


def union(intervals) -> np.ndarray:
    """The ``(start, end)`` pairs merged: an ``[n, 2]`` array, by start,
    of intervals that neither overlap nor touch."""
    iv = _pairs(intervals)
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    # sorted by start, the running maximum of the ends is the end of the
    # merged interval so far: a new one begins where a start lies past it
    reach = np.maximum.accumulate(iv[:, 1])
    first = np.concatenate([[True], iv[1:, 0] > reach[:-1]])
    last = np.concatenate([first[1:], [True]])
    return np.column_stack([iv[first, 0], reach[last]])


def total(intervals) -> float:
    iv = _pairs(intervals)
    return float((iv[:, 1] - iv[:, 0]).sum())


def subtract(a, b) -> np.ndarray:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    a, b = _pairs(a), _pairs(b)
    if not len(a) or not len(b):
        return a
    # between two neighbouring boundaries of either set nothing changes:
    # a stretch is kept where its start lies inside ``a`` and outside ``b``
    cuts = np.unique(np.concatenate([a.ravel(), b.ravel()]))
    lo, hi = cuts[:-1], cuts[1:]

    def inside(iv):
        k = np.searchsorted(iv[:, 0], lo, side="right") - 1
        return (k >= 0) & (lo < iv[np.maximum(k, 0), 1])

    keep = inside(a) & ~inside(b)
    return np.column_stack([lo[keep], hi[keep]])


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


def _spans_of(ops: Ops, mask) -> np.ndarray:
    return np.column_stack([ops.start[mask], ops.end[mask]])


def _add_by_key(acc: np.ndarray, key, seconds, n: int) -> np.ndarray:
    """``acc`` grown to ``n`` keys, plus the seconds summed a key."""
    return (np.pad(acc, (0, n - len(acc)))
            + np.bincount(key, seconds, minlength=n))


def reduce_trace(profile, chips: int | None = None) -> dict:
    """Everything the per-layer readers and the result line take from a
    trace.  Times are seconds; per-chip figures are averaged over the
    chips that ran anything.  ``gaps`` is an ``[n, 2]`` array: the idle
    intervals of the first chip inside the traced window, by start."""
    ops = device_ops(profile)
    if chips:
        ops = dict(sorted(ops.items())[:chips])
    ops = {k: v for k, v in sorted(ops.items()) if len(v.ids)}
    if not ops:
        raise ValueError("the trace holds no device operation")
    t0 = min(float(o.start.min()) for o in ops.values())
    t1 = max(float(o.end.max()) for o in ops.values())
    busy_s, exposed_s, coll_s = [], [], []
    keys: dict[str, int] = {}       # cleaned name -> place, by appearance
    by_key, kernel_by_key = np.zeros(0), np.zeros(0)
    gaps = np.zeros((0, 2))
    for chip, o in ops.items():
        busy = union(_spans_of(o, slice(None)))
        busy_s.append(total(busy))
        # a name is classified and cleaned once, not once an event;
        # wrappers get no key (their bodies are listed)
        kinds = [_kind(n) for n in o.names]
        kind = np.array(kinds)[o.ids]
        coll = union(_spans_of(o, kind == "collective"))
        comp = union(_spans_of(o, kind == "compute"))
        coll_s.append(total(coll))
        exposed_s.append(total(subtract(coll, comp)))
        key = np.array([-1 if k == "wrapper"
                        else keys.setdefault(clean_name(n), len(keys))
                        for n, k in zip(o.names, kinds)])[o.ids]
        kernel = np.array([is_custom_call(n) for n in o.names])[o.ids]
        took = o.end - o.start
        listed = key >= 0
        by_key = _add_by_key(by_key, key[listed], took[listed], len(keys))
        kernel &= listed
        kernel_by_key = _add_by_key(kernel_by_key, key[kernel],
                                    took[kernel], len(keys))
        if chip == min(ops):
            a = np.concatenate([[t0], busy[:, 1]])
            b = np.concatenate([busy[:, 0], [t1]])
            gaps = np.column_stack([a, b])[b > a]
    n = len(ops)
    return {
        "window_s": t1 - t0, "t0": t0, "chips": n,
        "busy_s": sum(busy_s) / n,
        "collective_s": sum(coll_s) / n,
        "collective_exposed_s": sum(exposed_s) / n,
        "ops": {k: float(by_key[i]) / n for k, i in keys.items()},
        "custom_calls": {k: float(kernel_by_key[i]) / n
                         for k, i in keys.items() if kernel_by_key[i] > 0},
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
    afterwards.  ``gaps`` do not overlap and come by start."""
    points = innermost_points(spans)
    times = np.array([t for t, _ in points], dtype=np.float64)
    # stretch j of the clock: before the first change point (0, under no
    # span), then from point j - 1 to point j, the last one without end
    names = [NO_SPAN] + [label for _, label in points]
    g = _pairs(gaps)
    g0, g1 = g[:, 0], g[:, 1]
    first = np.searchsorted(times, g0, side="right")
    last = np.maximum(np.searchsorted(times, g1, side="left"), first)
    n = len(names)

    def sum_at(where, seconds):
        return np.bincount(where, seconds, minlength=n).astype(np.float64)

    began = sum_at(first, g1 - g0)
    count = np.bincount(first, minlength=n)
    # a gap inside one stretch is that stretch's; another leaves its head
    # to the first, its tail to the last, and holds those between whole
    one = first == last
    split = sum_at(first[one], (g1 - g0)[one])
    f, l = first[~one], last[~one]
    split += sum_at(f, times[f] - g0[~one]) if len(f) else 0.0
    split += sum_at(l, g1[~one] - times[l - 1]) if len(l) else 0.0
    whole = np.cumsum(np.bincount(f + 1, minlength=n + 1)
                      - np.bincount(l, minlength=n + 1))[:n] > 0
    inner = slice(1, max(1, len(times)))
    split[inner] += np.where(whole[inner], np.diff(times), 0.0)
    touched = whole | (count > 0)
    touched[last] = True
    out: dict[str, dict] = {}
    for j in np.flatnonzero(touched):
        row = out.setdefault(
            names[j], {"began_in_s": 0.0, "gaps": 0, "split_s": 0.0})
        row["began_in_s"] += float(began[j])
        row["gaps"] += int(count[j])
        row["split_s"] += float(split[j])
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
