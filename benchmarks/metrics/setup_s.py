"""Process start to the first timed step or request, seconds."""


def read(ctx):
    return ctx["setup_s"]
