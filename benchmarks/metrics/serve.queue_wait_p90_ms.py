"""p90 of arrival -> admission, from the request records."""
from harness import readers


def read(ctx):
    return readers.request_percentile(ctx, "queue_ms", 90)
