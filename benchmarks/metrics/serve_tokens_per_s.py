"""All output tokens produced in the window / window wall."""


def read(ctx):
    if not ctx.get("tokens_done"):
        return None
    return ctx["tokens_done"] / ctx["window_s"]
