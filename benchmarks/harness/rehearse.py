"""The CPU rehearsal: every step of a cell's run — flags, engine or
driver, the benchmark's weights and traffic, the window, the reference
comparison — at a tiny size with ``JAX_PLATFORMS=cpu``.  It prints counts
only (requests, tokens, steps, compiles) and nothing under the name of a
device metric; the timed path itself fails off the chip.  The tiny sizes
are the family's (``tiny_config``, ``shrink_program``) and the
generator's (``tiny``)."""

from __future__ import annotations

import importlib
import json
import os
import time

from harness import adapters, checks, traffic

TINY_SECONDS = 3.0


def tiny_config(cfg: dict) -> dict:
    return adapters.family_of(cfg).tiny_config(cfg)


def tiny_mix(mix: dict) -> dict:
    return traffic.generator_of(mix).tiny(mix)


def run(cell: dict, cfg: dict, mix: dict, args, workdir: str,
        result: dict | None = None) -> int:
    import jax

    if jax.default_backend() != "cpu":
        print("rehearsal: set JAX_PLATFORMS=cpu (the chip is for the "
              "timed path)")
        return 2
    cfg, mix = tiny_config(cfg), tiny_mix(mix)
    adapters.family_of(cfg).shrink_program(cfg)
    args.seconds = min(args.seconds, TINY_SECONDS)
    args.trace = 0
    os.makedirs(workdir, exist_ok=True)
    dev = {"platform": "cpu", "kind": "cpu", "count": jax.device_count()}
    lane = importlib.import_module("harness." + mix["lane"] + "_lane")
    out = lane.run_cell(cell, cfg, mix, args, time.monotonic(), dev, workdir,
                        rehearsal=True)
    if result is not None:
        result.update(correct=checks.verdict(out["numbers"]),
                      numbers=out["numbers"], ctx=out["ctx"])
    ctx = out["ctx"]
    counts = {"attempted": out["attempted"], "failed": out["failed"],
              "compiles_in_window": ctx["compiles_in_window"]}
    for key in ("tokens_done", "steps", "examples"):
        if key in ctx:
            counts[key] = ctx[key]
    if "records" in ctx:
        counts["requests_finished"] = len(ctx["records"])
    for line in checks.report_lines(out["numbers"]):
        print(line)
    print(json.dumps({"rehearsal": cell["name"], "platform": "cpu",
                      "correct": checks.verdict(out["numbers"]),
                      "counts": counts}))
    return 0
