"""Shape-derived FLOPs (forward + backward, nothing recomputed) x examples/s
/ (chips x bf16 peak), %."""


def read(ctx):
    if not ctx.get("examples"):
        return None
    per_example = ctx["family"].train_step_flops_per_example(
        ctx["config"], ctx["mix"])
    rate = ctx["examples"] / ctx["window_s"] / ctx["chips"]
    return 100.0 * per_example * rate / ctx["peaks"]["bf16_flops"]
