#!/usr/bin/env python3
"""Reads the two sets ``tools/sets.sh`` wrote and prints, for each metric,
both sets' medians and spreads (first to third quartile by
``statistics.quantiles(n=4)``, as a share of the median), the wider
spread, and the second median against the first.

    python3 benchmarks/tools/spread.py chiprun_out/sets/<cell>.t0.jsonl
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import stats  # noqa: E402


def main() -> int:
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
                           else None}
            sp = [x["spread"] for x in line.values()
                  if isinstance(x, dict) and x["spread"] is not None]
            line["wider_spread"] = max(sp) if sp else None
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
