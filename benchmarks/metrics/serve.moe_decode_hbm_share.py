"""The routed experts' decode traffic against the memory's peak: the bytes
the traced window's decode steps MUST move for the experts (the bfloat16
matrices of every expert the active rows picked, at the window's mean of
``moe_experts_hit`` a step, and the routers: ``families/mellum2.
moe_decode_bytes``) / (the traced seconds of the decode programs'
operations under the ``moe`` scope x the peak bytes/s), %.  Decode steps
in the traced window = the window's rate of steps x the trace's length.
Nothing where the program counts no experts hit."""
from families import mellum2
from harness import readers


def read(ctx):
    hit = (ctx.get("summary") or {}).get("moe_experts_hit")
    parts = mellum2.part_seconds(ctx, kinds=("decode",))
    t = readers.bucket_totals(ctx, "decode")
    if not hit or not parts or not parts.get("moe") or not t or not t[0]:
        return None
    steps = t[0]
    steps_traced = ctx["trace"]["window_s"] * steps / ctx["window_s"]
    need = steps_traced * mellum2.moe_decode_bytes(ctx["config"],
                                                   hit / steps)
    return 100.0 * need / (parts["moe"] * ctx["peaks"]["hbm_bytes_per_s"])
