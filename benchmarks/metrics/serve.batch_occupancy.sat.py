"""Active rows / bucket rows over the decode steps of the window, %."""
from harness import readers


def read(ctx):
    return readers.batch_occupancy(ctx)
