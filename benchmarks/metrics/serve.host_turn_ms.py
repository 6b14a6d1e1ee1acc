"""The host's own time per decode step, dispatches included: the loop's
wall (the engine's ``loop_phases`` fold, which tiles it) less the phases
in which the host only waits (``prefill_wait`` and ``decode_wait`` for
the device, ``arrival_wait`` for a request), / decode steps."""

WAITS = ("prefill_wait", "decode_wait", "arrival_wait")


def read(ctx):
    phases = (ctx.get("summary") or {}).get("loop_phases")
    steps = ((phases or {}).get("decode_wait") or {}).get("count")
    if not steps:
        return None
    host_s = sum(p["wall_s"] for name, p in phases.items()
                 if name not in WAITS)
    return 1e3 * host_s / steps
