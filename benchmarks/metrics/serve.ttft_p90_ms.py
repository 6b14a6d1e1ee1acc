"""p90 of time to first token, from the due time."""
from harness import readers


def read(ctx):
    return readers.request_percentile(ctx, "ttft_ms", 90)
