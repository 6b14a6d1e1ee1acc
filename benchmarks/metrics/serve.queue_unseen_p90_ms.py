"""p90 of arrival -> the loop's first look at the request
(``queue_unseen_ms``, the part of ``queue_ms`` that is no wait for a
resource), from the request records."""
from harness import readers


def read(ctx):
    records = ctx.get("records")
    if not records or "queue_unseen_ms" not in records[0]:
        return None
    return readers.request_percentile(ctx, "queue_unseen_ms", 90)
