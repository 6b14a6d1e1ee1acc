"""Median host-timed wall of a request's own prefill."""
from harness import readers


def read(ctx):
    return readers.request_percentile(ctx, "prefill_ms", 50)
