"""All examples of all steps completed in the window / window wall (ended
by block_until_ready on the last step) / chips."""


def read(ctx):
    if not ctx.get("examples"):
        return None
    return ctx["examples"] / ctx["window_s"] / ctx["chips"]
