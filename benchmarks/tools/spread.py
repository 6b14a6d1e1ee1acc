#!/usr/bin/env python3
"""Reads the two sets ``tools/sets.sh`` wrote and prints, for each metric,
both sets' medians and spreads by both rules: ``spread``, first to third
quartile by ``statistics.quantiles(n=4)`` as a share of the median (what
the contract sets a bound from; the wider of the two is what the check
holds a bound's looseness against, at eight times), and the check's own
for tightness, of the set WITHOUT the run farthest from its median:
``check_spread`` (the quartiles' distance again) and ``check_range`` (the
whole range of what is left: never smaller, the reading ISSUE 32 sizes
cells by).  The mean of the two sets' is what the check refuses as too
noisy where it passes HALF the metric's bound (``*_mean_over_half_bound``
above 1); it has read cells 3-4x wider than a builder's sets did (PR 27,
PR 29), so aim well under it.  Last, the second median against the
first.

    python3 benchmarks/tools/spread.py chiprun_out/sets/<cell>.t0.jsonl
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import spec, stats  # noqa: E402


def main() -> int:
    bounds = {m["name"]: m["bound"]
              for m in spec.load_benchmark()["end_to_end"]}
    for path in sys.argv[1:]:
        rows = [json.loads(l) for l in open(path) if l.strip()]
        print(path, "runs:", len(rows), "rc:", [r["rc"] for r in rows],
              "correct:", [r["result"] and r["result"]["correct"]
                           for r in rows])
        names = sorted({m for r in rows if r["result"]
                        for m in r["result"]["metrics"]})
        for name in names:
            by_set = {}
            for r in rows:
                if r["result"] and name in r["result"]["metrics"]:
                    by_set.setdefault(r["set"], []).append(
                        r["result"]["metrics"][name]["value"])
            line = {"metric": name}
            for s, vals in sorted(by_set.items()):
                # the first run of a checkout compiles: its set-up is
                # recorded apart
                v = vals[1:] if name == "setup_s" and s == "A" else vals
                line[s] = {"values": [round(x, 4) for x in vals],
                           "median": statistics.median(v),
                           "spread": stats.iqr_share(v) if len(v) > 1
                           else None,
                           "check_spread": None, "check_range": None}
                if len(v) > 3:
                    kept = stats.without_farthest(v)
                    line[s].update(check_spread=stats.iqr_share(kept),
                                   check_range=stats.range_share(kept))
            sets = [x for x in line.values() if isinstance(x, dict)]
            sp = [x["spread"] for x in sets if x["spread"] is not None]
            line["wider_spread"] = max(sp) if sp else None
            for rule in ("check_spread", "check_range"):
                ck = [x[rule] for x in sets if x[rule] is not None]
                if ck and bounds.get(name) and name != "setup_s":
                    line[rule + "_mean"] = statistics.mean(ck)
                    line[rule + "_mean_over_half_bound"] = (
                        line[rule + "_mean"] / (0.5 * bounds[name]))
            if "A" in by_set and "B" in by_set:
                line["B_over_A"] = line["B"]["median"] / line["A"]["median"]
            print(json.dumps(line))
        comp = {}
        for r in rows:
            for k, v in ((r["result"] or {}).get("compared") or {}).items():
                comp.setdefault(k, []).append(v["value"])
        print(json.dumps({"compared_max": {k: max(v) for k, v in comp.items()
                                           if all(isinstance(x, (int, float)) for x in v)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
