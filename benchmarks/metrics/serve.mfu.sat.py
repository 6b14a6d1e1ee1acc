"""All tokens processed in the window as a share of the bf16 peak."""
from harness import readers


def read(ctx):
    return readers.serve_mfu(ctx)
