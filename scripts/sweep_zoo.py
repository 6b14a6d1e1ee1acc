"""Measure every zoo model on the local chip(s); emit one JSON line each.

The reference records one number per (model, batch, fabric) run in a tee'd
log (run-tf-sing-ucx-openmpi.sh:9-12); this sweep automates the matrix the
way an operator would drive it, writing ``sweep_results.jsonl`` for
BASELINE.md.

The matrix itself (the best-known per-member configs that used to live
here as ``DEFAULT_MATRIX``/``EXTRA_FLAGS``) now lives in
``tpu_hc_bench.tune.space.SEED_CONFIGS`` — one copy shared by this
sweep, the autotuner's search space, and the pruner's HBM model — and
the subprocess launch/timeout/exit-contract/parse logic is
``tpu_hc_bench.tune.runner.run_one``, shared with the successive-halving
search.  Usage:

    python scripts/sweep_zoo.py [--out FILE] [--models a,b,c]

    # re-validate the tuned registry rows for this hardware instead of
    # the seeded matrix (the autotuner's regression loop)
    python scripts/sweep_zoo.py --from_registry [--hardware KEY]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="sweep_results.jsonl")
    ap.add_argument("--models", default=None,
                    help="comma list; default = full matrix")
    ap.add_argument("--warmup", type=int, default=25)
    ap.add_argument("--batches", type=int, default=60)
    ap.add_argument("--from_registry", action="store_true",
                    help="sweep the tuned-config registry rows for this "
                         "hardware (tpu_hc_bench.tune) instead of the "
                         "seeded best-known matrix")
    ap.add_argument("--hardware", default=None,
                    help="registry hardware key (default: the live "
                         "backend's, honoring TPU_HC_TUNE_HW)")
    args = ap.parse_args()

    from tpu_hc_bench.tune import registry as registry_mod
    from tpu_hc_bench.tune import runner as runner_mod
    from tpu_hc_bench.tune import space as space_mod

    wanted = set(args.models.split(",")) if args.models else None

    # (model, batch, extra flags, provenance) rows to run
    if args.from_registry:
        # from a child: this process launches the sweep's children and
        # must never hold the chip itself
        hardware = args.hardware or registry_mod.hardware_key_from_child()
        rows = registry_mod.load_rows(hardware)
        if not rows:
            print(f"no tuned rows for hardware {hardware!r} "
                  f"({registry_mod.registry_path(hardware)}) — run "
                  f"`python -m tpu_hc_bench.tune search` first",
                  file=sys.stderr)
            raise SystemExit(1)
        matrix = []
        for model in sorted(rows):
            if wanted is not None and model not in wanted:
                continue
            try:
                c = space_mod.Candidate.make(
                    model, dict(rows[model]["overrides"]),
                    dict(rows[model].get("base") or {}))
            except ValueError as e:
                # one stale row (lever renamed since the search) must
                # not block re-validating every other member; the
                # tuned-config-staleness lint is the loud gate
                print(f"skipping {model}: {e} (stale registry row?)",
                      file=sys.stderr)
                continue
            matrix.append((model, c.batch_size, c.to_flags(), "registry"))
    else:
        matrix = []
        for model, batch in space_mod.seed_matrix():
            if wanted is not None and model not in wanted:
                continue
            matrix.append((model, batch,
                           space_mod.seed_extra_flags(model), "seed"))

    with open(args.out, "a") as f:
        for model, batch, flags, source in matrix:
            rec = runner_mod.run_one(model, batch, flags,
                                     warmup=args.warmup,
                                     batches=args.batches)
            rec["config_source"] = source
            f.write(json.dumps(rec) + "\n")
            f.flush()
            print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
