#!/usr/bin/env python3
"""``benchmarks/run.py`` of the checkout it is started in, asked for more
than a check needs (``tools/call_tracing.sh`` calls it):

- BOTH groups of metrics in the result line, whatever ``--trace`` says:
  the serve loop's fold is read in every run, so an untraced run can say
  ``serve.host_turn_ms`` and a traced one ``serve_tpot_p90_ms``;
- a ``[probe]`` line with the engine's ``loop_phases`` beside the window's
  wall;
- with ``PROBE_RECORDER_OFF`` set, ``--flight_recorder=off`` among the
  lane's flags.

``--brief <file>`` prints the few numbers of such a run's output that a
call's 24 kB of tail should show.
"""

from __future__ import annotations

import json
import os
import sys

KEEP = ("serve_tpot_p90_ms", "serve_tokens_per_s", "train_examples_per_s",
        "setup_s", "serve.host_turn_ms", "serve.host_turn_ms.sat",
        "serve.queue_unseen_p90_ms", "serve.queue_wait_p90_ms",
        "serve.decode_step_wall_ms", "serve.decode_step_wall_ms.sat",
        "device.idle_share.serve", "device.idle_share.sat",
        "device.idle_share.train", "device.compiles_in_window",
        "kernel.flash_attention_roofline", "train.step_mfu")


def brief(path: str) -> dict:
    with open(path) as f:
        lines = f.read().splitlines()
    res = json.loads(lines[-1])
    m = res["metrics"]
    out = {"correct": res["correct"], "n_metrics": len(m),
           **{k: round(m[k]["value"], 4) for k in KEEP if k in m}}
    for ln in lines:
        if not ln.startswith("[probe] "):
            continue
        p = json.loads(ln[len("[probe] "):])
        if p["loop_phases"]:
            total = sum(v["wall_s"] for v in p["loop_phases"].values())
            out["phases_over_window"] = round(total / p["window_s"], 5)
            out["phase_ms_per_step"] = {
                k: round(1e3 * v["wall_s"] / p["decode_steps"], 3)
                for k, v in p["loop_phases"].items()}
    return out


def main() -> int:
    if sys.argv[1:2] == ["--brief"]:
        try:
            print(json.dumps(brief(sys.argv[2])))
        except (OSError, IndexError, ValueError, KeyError) as e:
            print(f"no result line ({e!r})")
        return 0
    root = os.getcwd()
    sys.path[:0] = [os.path.join(root, "benchmarks"), root]
    import run
    from harness import serve_lane, spec

    groups, read = spec.metrics_for, spec.read_metrics
    spec.metrics_for = lambda bench, group, cell: (
        groups(bench, "end_to_end", cell) + groups(bench, "per_layer", cell))

    def read_and_note(metrics, ctx):
        s = ctx.get("summary") or {}
        print("[probe] " + json.dumps({
            "window_s": ctx.get("window_s"),
            "loop_wall_s": s.get("loop_wall_s"),
            "decode_steps": s.get("decode_steps"),
            "loop_phases": s.get("loop_phases")}), flush=True)
        return read(metrics, ctx)

    spec.read_metrics = read_and_note
    if os.environ.get("PROBE_RECORDER_OFF"):
        flags = serve_lane.serve_flags
        serve_lane.serve_flags = (
            lambda *a: flags(*a) + ["--flight_recorder=off"])
    return run.main()


if __name__ == "__main__":
    raise SystemExit(main())
