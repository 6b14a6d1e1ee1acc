"""Exact 90th percentile, over every request of the window with >= 2
tokens, of (last token - first token) / (tokens - 1); a request that
failed or never finished counts as the worst."""
from harness import readers


def read(ctx):
    return readers.tpot_percentile(ctx, 90)
