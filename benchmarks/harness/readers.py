"""Arithmetic shared by the metric readers under ``benchmarks/metrics/``:
each reader is a few lines that pick its inputs out of the run's context
and call one of these.  A reader that finds nothing to read returns None.

The context (``ctx``) of a run: ``cell``, ``config``, ``family`` (the
configuration's family module: its operation counts), ``mix``,
``seconds``, ``window_s``, ``setup_s``, ``chips``, ``peaks``,
``compiles_in_window``, ``trace`` (the xplane reduction, traced runs
only); serve cells add ``records`` (the engine's per-request records),
``failed``, ``summary`` (its run summary, for ``bucket_util``),
``tokens_done``, ``drain_limit_ms``; train cells add ``steps``,
``examples``, ``program_result`` (the driver's own step readings).
"""

from __future__ import annotations

from harness import flops, stats


def _tail(values, ctx, q: float):
    """Exact percentile of per-request ``values``; a failed or unanswered
    request counts as the worst (the drain limit)."""
    values = stats.with_failures(values, ctx.get("failed", 0))
    if not values:
        return None
    return stats.finite_or_worst(stats.percentile(values, q),
                                 ctx["drain_limit_ms"])


def request_percentile(ctx, field: str, q: float):
    """Of one per-request field over every request of the window."""
    records = ctx.get("records")
    if not records:
        return None
    return _tail([r[field] for r in records], ctx, q)


def queue_p50_by_thirds(records) -> list[float] | None:
    """Whether a backlog grew: the median queue wait, ms, of the first
    and of the last third of the finished requests, by arrival."""
    recs = sorted(records, key=lambda r: r["arrival_s"])
    if not recs:
        return None
    third = max(1, len(recs) // 3)
    return [stats.percentile([r["queue_ms"] for r in part], 50)
            for part in (recs[:third], recs[-third:])]


def tpot_values(records) -> list[float]:
    """Per request with >= 2 tokens: (last token - first token) /
    (tokens - 1), ms."""
    return [(r["e2e_ms"] - r["ttft_ms"]) / (r["output_len"] - 1)
            for r in records if r["output_len"] >= 2]


def tpot_percentile(ctx, q: float):
    records = ctx.get("records")
    if not records:
        return None
    return _tail(tpot_values(records), ctx, q)


def bucket_totals(ctx, kind: str):
    """(steps, rows, active rows, wall s) summed over the ``kind@<b>``
    rows of the engine's ``bucket_util``."""
    util = (ctx.get("summary") or {}).get("bucket_util") or {}
    rows = [u for k, u in util.items() if k.startswith(kind + "@")]
    if not rows:
        return None
    return (sum(u["steps"] for u in rows), sum(u["rows"] for u in rows),
            sum(u["active_rows"] for u in rows),
            sum(u["wall_s"] for u in rows))


def batch_occupancy(ctx):
    t = bucket_totals(ctx, "decode")
    return None if not t or not t[1] else 100.0 * t[2] / t[1]


def decode_step_wall_ms(ctx):
    """Host-timed wall of a decode step, the host turn included: never a
    kernel time."""
    t = bucket_totals(ctx, "decode")
    return None if not t or not t[0] else 1e3 * t[3] / t[0]


def decode_mfu(ctx):
    """2 x matmul parameters x rows decoded / (decode wall x bf16 peak),
    %.  The serve arm is float32 and the chip publishes no float32 peak:
    the share is of the bfloat16 peak, which it cannot pass."""
    t = bucket_totals(ctx, "decode")
    if not t or not t[3] or not t[2]:
        return None
    fl = ctx["family"].decode_flops_per_token(ctx["config"]) * t[2]
    return 100.0 * fl / (t[3] * ctx["peaks"]["bf16_flops"])


def serve_mfu(ctx):
    """2 x matmul parameters x every token processed in the window,
    prompt and output, plus attention, / (window x bf16 peak), %."""
    records = ctx.get("records")
    if not records:
        return None
    fl = sum(ctx["family"].sequence_forward_flops(
        ctx["config"], r["prompt_len"] + r["output_len"]) for r in records)
    return 100.0 * fl / (ctx["window_s"] * ctx["peaks"]["bf16_flops"])


def idle_share(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


# the kernel's events: Mosaic custom calls, named by the flax scope that
# issues them (``MultiHeadAttention_0.<n>``) until the program gives the
# kernel a name of its own
FLASH_KERNEL = r"MultiHeadAttention|flash_attention"


def flash_attention_roofline(ctx):
    """Needs a trace with the kernel's events and whole steps in it: the
    calls per step (one forward and one backward per layer) times the
    steps the traced window held."""
    from harness import xplane

    tr = ctx.get("trace")
    if not tr or not ctx.get("examples"):
        return None
    t_kernel = xplane.kernel_time(tr["custom_calls"], FLASH_KERNEL)
    if t_kernel <= 0:
        return None
    z = ctx["family"].attention_calls(ctx["config"], ctx["mix"])
    call = flops.flash_attention_call(z["batch"], z["seq"], z["heads"],
                                      z["head_dim"])
    fwd, _ = flops.roofline_seconds(call["fwd_flops"], call["fwd_bytes"],
                                    ctx["peaks"])
    bwd, _ = flops.roofline_seconds(call["bwd_flops"], call["bwd_bytes"],
                                    ctx["peaks"])
    steps_traced = tr["window_s"] / (ctx["window_s"] / ctx["steps"])
    least = steps_traced * z["layers"] * (fwd + bwd)
    return 100.0 * least / t_kernel
