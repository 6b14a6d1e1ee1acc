"""1 - union of busy intervals / traced window, %."""
from harness import readers


def read(ctx):
    return readers.idle_share(ctx)
