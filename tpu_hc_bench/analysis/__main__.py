"""CLI: ``python -m tpu_hc_bench.analysis``.

Runs the lint passes (and, per model, the world=2 compiled-HLO
collective count) and compares the findings against the checked-in
baseline; exits non-zero on any finding the baseline does not accept —
the CI lint gate.

Examples::

    # one member: lints + definition-site collective counts
    JAX_PLATFORMS=cpu python -m tpu_hc_bench.analysis --model resnet50

    # the whole zoo's lints + the repo source passes, JSON to a file
    JAX_PLATFORMS=cpu python -m tpu_hc_bench.analysis --all --json out.json

    # per-file passes restricted to sources `git diff` names (repo-scope
    # passes still see the whole tree) — the cheap pre-push loop
    JAX_PLATFORMS=cpu python -m tpu_hc_bench.analysis --all --changed-only

    # show what accepting the current tree WOULD change (exit 1 if
    # anything), then actually rewrite it (atomic tmp->fsync->rename)
    JAX_PLATFORMS=cpu python -m tpu_hc_bench.analysis baseline
    JAX_PLATFORMS=cpu python -m tpu_hc_bench.analysis baseline --update

The collective count lowers the member's real world=2 train step on a
2-virtual-device CPU mesh (identical program to a two-process run; see
``hlo.lower_world_step_hlo``), so ``--collectives`` runs want
``JAX_PLATFORMS=cpu`` and take compile time; ``--no-collectives`` skips
them for lint-only runs.
"""

from __future__ import annotations

import argparse
import os
import sys


def _configure_cpu(world: int) -> None:
    # must precede any jax device use; the compat shim reroutes the
    # option to XLA_FLAGS on old stacks
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", world)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tpu_hc_bench.analysis",
        description="static analysis + lint gate over the model zoo")
    ap.add_argument("command", nargs="?", choices=["baseline"],
                    help="subcommand: `baseline` diffs this run's "
                         "findings against the committed baseline "
                         "(exit 1 on any change); `baseline --update` "
                         "rewrites it atomically")
    ap.add_argument("--update", action="store_true",
                    help="(baseline) actually rewrite the baseline "
                         "file instead of dry-running the diff")
    ap.add_argument("--changed-only", action="store_true",
                    help="restrict per-file passes to python sources "
                         "changed vs HEAD (plus untracked); repo-scope "
                         "passes still see the whole tree")
    ap.add_argument("--model", action="append", default=[],
                    help="zoo member to analyze (repeatable)")
    ap.add_argument("--all", action="store_true",
                    help="analyze every zoo member + repo sources")
    ap.add_argument("--batch", type=int, default=2,
                    help="per-device batch for the lowered step "
                         "(collective counts are batch-invariant)")
    ap.add_argument("--world", type=int, default=2,
                    help="virtual device count for the lowered step")
    ap.add_argument("--collectives", dest="collectives",
                    action="store_true", default=None,
                    help="count collectives in the compiled world=N HLO "
                         "(default: on for --model, off for --all)")
    ap.add_argument("--no-collectives", dest="collectives",
                    action="store_false")
    ap.add_argument("--json", metavar="PATH",
                    help="write the full JSON report here ('-' = stdout)")
    ap.add_argument("--baseline", metavar="PATH",
                    help="baseline findings file (default: checked-in)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from this run's findings")
    args = ap.parse_args(argv)

    from tpu_hc_bench.models import list_models

    models = list(args.model)
    if args.all:
        models = list_models()
    if not models and not args.all and args.command != "baseline":
        ap.error("pass --model NAME (repeatable), --all, or the "
                 "`baseline` subcommand")
    if args.update and args.command != "baseline":
        ap.error("--update belongs to the `baseline` subcommand")
    count_collectives = args.collectives
    if count_collectives is None:
        count_collectives = bool(args.model) and not args.all

    if count_collectives:
        _configure_cpu(args.world)

    import collections
    import time

    from tpu_hc_bench.analysis import hlo, lints, registry, report

    t0 = time.monotonic()
    files = None
    if args.changed_only:
        root = __import__("pathlib").Path(__file__).resolve().parents[2]
        files = registry.changed_python_files(root)
        if files is None:
            print("--changed-only: git unavailable, falling back to "
                  "the full tree", file=sys.stderr)
        else:
            print(f"--changed-only: {len(files)} changed python "
                  f"source(s)", file=sys.stderr)

    findings = []
    collectives: dict[str, dict[str, int]] = {}
    suppressed: collections.Counter = collections.Counter()
    findings.extend(lints.lint_repo_sources(files=files,
                                            counters=suppressed))
    for name in models:
        print(f"-- {name}", file=sys.stderr)
        findings.extend(lints.lint_model(name))
        if count_collectives:
            text = hlo.lower_world_step_hlo(name, batch=args.batch,
                                            world=args.world)
            collectives[name] = hlo.collective_counts(text)
    wall_s = time.monotonic() - t0

    rep = report.Report(findings=findings, collectives=collectives,
                        suppressed=dict(suppressed), wall_s=wall_s)
    if args.json == "-":
        sys.stdout.write(rep.to_json())
    elif args.json:
        with open(args.json, "w") as f:
            f.write(rep.to_json())

    # human summary: stderr when stdout is the JSON stream
    out = sys.stderr if args.json == "-" else sys.stdout
    for name, counts in sorted(collectives.items()):
        total = sum(counts.values())
        print(f"{name} world={args.world} optimized-HLO collectives "
              f"(definition sites, async pairs folded): {total}  {counts}",
              file=out)

    if args.command == "baseline":
        path = args.baseline or report.BASELINE_PATH
        # a partial (--model) run only ADDS keys; erasing other models'
        # accepted findings requires the full --all picture
        merge = set() if args.all else report.load_baseline(path)
        gating = {f.key for f in findings
                  if f.severity in ("error", "warning")} | merge
        before = report.load_baseline(path)
        added, removed = sorted(gating - before), sorted(before - gating)
        for k in added:
            print(f"+ {k}", file=out)
        for k in removed:
            print(f"- {k}", file=out)
        if not args.update:
            if added or removed:
                print(f"baseline DIFF: +{len(added)} -{len(removed)} "
                      f"key(s); rerun with `baseline --update` to "
                      f"accept", file=out)
                return 1
            print(f"baseline up to date: {path} "
                  f"({len(before)} accepted keys)", file=out)
            return 0
        gating_findings = [f for f in findings
                           if f.severity in ("error", "warning")]
        report.save_baseline(gating_findings, path, merge=merge)
        print(f"baseline updated: {path} (+{len(added)} "
              f"-{len(removed)}, {len(gating)} accepted keys)", file=out)
        return 0

    if args.update_baseline:
        path = args.baseline or report.BASELINE_PATH
        merge = set() if args.all else report.load_baseline(path)
        added, removed = report.save_baseline(findings, path, merge=merge)
        for k in added:
            print(f"+ {k}", file=out)
        for k in removed:
            print(f"- {k}", file=out)
        print(f"baseline updated: {path} "
              f"({len({f.key for f in findings} | merge)} accepted keys)",
              file=out)
        return 0

    baseline = report.load_baseline(args.baseline or report.BASELINE_PATH)
    regressions = report.compare_to_baseline(findings, baseline)
    for f in regressions:
        print(f.render(), file=sys.stderr)
    if regressions:
        print(f"{len(regressions)} finding(s) not in baseline "
              f"(accept with `baseline --update` or suppress with "
              f"`# tpu-hc: disable=<lint>`)", file=sys.stderr)
        return 1
    n_info = sum(1 for f in findings if f.severity == "info")
    n_sup = sum(suppressed.values())
    print(f"analysis clean: {len(findings)} finding(s), all accepted "
          f"({n_info} info, {n_sup} suppressed) in {wall_s:.1f}s",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
