"""The windowed flash forward's share of its roofline: the least time the
traced prefills' window layers need — for each prefill's padded bucket
the larger of the band's operations over the bf16 peak
(``families/mellum2.window_band_flops``) and its bytes over the memory's
(``window_band_bytes``: buckets under ~1k are memory-bound), in every
window layer — / the summed device seconds of the ``flash_window_fwd``
events, %.  A prefill counts by the share of its time (queue end to first
token, from its request's record) that lies in the traced interval
(``harness/tracing``: from ``TRACE_START_FRACTION`` of the window, for
the trace's length); its bucket is the smallest of the program's prefill
buckets that holds the prompt.  None where the trace holds no such
kernel."""
from families import mellum2
from harness import flops, tracing, xplane

# the kernel's events: its custom calls, named by the program
KERNEL = r"^flash_window_fwd(\.\d+)?:"


def read(ctx):
    tr = ctx.get("trace")
    records = ctx.get("records")
    util = (ctx.get("summary") or {}).get("bucket_util") or {}
    buckets = sorted(int(k.split("@")[1]) for k in util
                     if k.startswith("prefill@"))
    if not tr or not records or not buckets:
        return None
    t_kernel = xplane.kernel_time(tr["custom_calls"], KERNEL)
    if t_kernel <= 0:
        return None
    lo = tracing.TRACE_START_FRACTION * ctx["seconds"]
    hi = lo + tr["window_s"]
    layers = mellum2.params(ctx["config"])["window_layers"]
    need = 0.0
    for r in records:
        if r.get("queue_ms") is None:
            continue
        start = r["arrival_s"] + r["queue_ms"] / 1e3
        end = r["arrival_s"] + r["ttft_ms"] / 1e3
        inside = max(0.0, min(end, hi) - max(start, lo))
        if inside <= 0 or end <= start:
            continue
        bucket = next(b for b in buckets if b >= r["prompt_len"])
        least, _ = flops.roofline_seconds(
            mellum2.window_band_flops(ctx["config"], bucket),
            mellum2.window_band_bytes(ctx["config"], bucket), ctx["peaks"])
        need += (inside / (end - start)) * layers * least
    return 100.0 * need / t_kernel
