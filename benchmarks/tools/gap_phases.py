#!/usr/bin/env python3
"""Names the device's idle gaps of a kept trace by what the PROGRAM says
it was doing: the ``hc:`` spans that ``tpu_hc_bench.obs.timeline`` writes
into the profiler's trace (the serve loop's phases among them).

    BENCH_KEEP_TRACE=1 python3 benchmarks/run.py --workload W ... --trace 1
    python3 benchmarks/tools/gap_phases.py .bench_work/trace [--chips N]

The trace is reduced by the harness's own ``xplane.reduce_trace`` and each
idle gap of its first chip charged by ``xplane.charge_gaps`` (whole, to
the innermost ``hc:`` span open when it began; and split among the
innermost spans open while it lasted: the ``split`` column is what a
traced run's result line carries as ``idle_gaps``).  Here beside it: how
many gaps began in each phase, and each phase's spans and seconds inside
the traced window.  Prints a table and, last, one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH_DIR]

from harness import xplane  # noqa: E402


def span_totals(spans, t0: float, t1: float) -> dict[str, list]:
    """phase -> [spans, seconds] inside the traced window."""
    out: dict[str, list] = {}
    for name, s, e in spans:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            acc = out.setdefault(name, [0, 0.0])
            acc[0] += 1
            acc[1] += e - s
    return out


def report(profile, chips: int | None = None) -> dict:
    red = xplane.reduce_trace(profile, chips)
    spans = xplane.host_spans(profile)
    idle = xplane.total(red["gaps"])
    phases = xplane.charge_gaps(red["gaps"], spans)
    totals = span_totals(spans, red["t0"], red["t0"] + red["window_s"])
    for name, (n, secs) in totals.items():
        phases.setdefault(
            name, {"began_in_s": 0.0, "gaps": 0, "split_s": 0.0}
        ).update(spans=n, span_s=secs)
    named = idle - phases.get(xplane.NO_SPAN, {}).get("split_s", 0.0)
    return {"window_s": red["window_s"], "busy_s": red["busy_s"],
            "idle_s": idle, "gaps": len(red["gaps"]),
            "idle_named_share": named / idle if idle else None,
            "phases": phases}


def table(rep: dict) -> list[str]:
    lines = [f"traced {rep['window_s']:.3f} s, busy {rep['busy_s']:.3f} s, "
             f"idle {rep['idle_s']:.4f} s in {rep['gaps']} gaps; "
             f"under a span: "
             + ("-" if rep["idle_named_share"] is None
                else f"{100 * rep['idle_named_share']:.1f}%"),
             f"{'phase':<18}{'idle split s':>13}{'share %':>9}"
             f"{'began in s':>12}{'gaps':>6}{'spans':>7}{'span s':>9}"]
    idle = rep["idle_s"] or 1.0
    for name, p in sorted(rep["phases"].items(),
                          key=lambda kv: -kv[1]["split_s"]):
        lines.append(
            f"{name:<18}{p['split_s']:>13.4f}{100 * p['split_s'] / idle:>9.1f}"
            f"{p['began_in_s']:>12.4f}{p['gaps']:>6}"
            f"{p.get('spans', 0):>7}{p.get('span_s', 0.0):>9.3f}")
    return lines


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="a kept trace directory, or an "
                    ".xplane.pb / .xplane.txt file")
    ap.add_argument("--chips", type=int, default=None)
    args = ap.parse_args()
    path = (xplane.find_xplane(args.trace) if os.path.isdir(args.trace)
            else args.trace)
    rep = report(xplane.load(path), args.chips)
    print("\n".join(table(rep)))
    print(json.dumps(rep))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
