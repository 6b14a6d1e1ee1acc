"""The sliding-window attention layers' share of the device's busy time:
traced seconds of the operations under the program's ``swa`` scope, in
prefill and decode programs alike, / the trace's busy seconds, %.  The
whole split by part goes to stderr.  Nothing on a program without the
scope."""
import sys

from families import mellum2


def read(ctx):
    parts = mellum2.part_seconds(ctx)
    if not parts or "swa" not in parts or not ctx["trace"]["busy_s"]:
        return None
    busy = ctx["trace"]["busy_s"]
    print("[metric] device seconds by named part: "
          + ", ".join(f"{k} {v:.4f}" for k, v in sorted(parts.items()))
          + f"; busy {busy:.4f}", file=sys.stderr)
    return 100.0 * parts["swa"] / busy
