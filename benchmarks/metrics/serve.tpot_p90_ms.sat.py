"""The burst cell's per-request TPOT p90: recorded, decides nothing."""
from harness import readers


def read(ctx):
    return readers.tpot_percentile(ctx, 90)
