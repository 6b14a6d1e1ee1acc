"""Open-loop serving traffic, *stratified by the seed*: the window's
prompt lengths, output lengths and inter-arrival gaps are the fixed
quantiles of their distributions, and the seed decides only their order
and the token ids.  Every seed then offers the same number of requests,
the same tokens and the same burstiness, so a tail read over the window
carries no sampling error of the trace (what refused PR 22's chat cell).

Parameters of a mix: ``requests_per_s`` x the window = the number of
requests; ``arrival_span_fraction`` of the window holds every arrival
(exponential gaps, scaled to sum to it); ``prompt_len`` / ``output_len``
lognormal (``median``, ``sigma``) clipped to ``min``-``max``;
``max_in_flight``; ``close_window_at_seconds`` (a backlog: the window is
closed by the lane's own drain); ``order_block`` (optional).

Without ``order_block`` the seed shuffles each multiset over the whole
window: right where the window serves every request.  A backlog's window
ends inside the trace, so a full shuffle would let the seed choose WHICH
requests are served (more short outputs first = more prefills per token:
the check read 0.7% of spread in tokens/s from that).  With
``order_block`` = b the order is a fixed, even spread of both multisets
(any run of consecutive requests is a fair sample of the mix) and the
seed shuffles only inside blocks of b: every prefix of whole blocks is
the same work for every seed."""

from __future__ import annotations

import copy
from statistics import NormalDist

import numpy as np

from harness.traffic import seed_rng


def _mid_quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_quantiles(n: int, median: float, sigma: float,
                        lo: int, hi: int) -> np.ndarray:
    """The ``n`` mid-quantiles of lognormal(median, sigma), rounded and
    clipped to ``[lo, hi]`` — a fixed multiset, ascending."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf(float(p)) for p in _mid_quantiles(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(
        np.int64)


def exponential_gap_quantiles(n: int, span_s: float) -> np.ndarray:
    """The ``n`` mid-quantiles of an exponential gap, scaled so that the
    gaps sum to ``span_s`` exactly: Poisson burstiness, fixed total."""
    g = -np.log1p(-_mid_quantiles(n))
    return g * (span_s / g.sum())


# two strides that are irrational to each other: the golden ratio's
# fraction and sqrt(2)'s
_STRIDES = (0.6180339887498949, 0.41421356237309503)


def _spread_ranks(n: int, stride: float) -> np.ndarray:
    """A fixed order of ``range(n)`` in which every run of consecutive
    places holds ranks spread evenly over the whole range: the rank of
    ``frac(place * stride)``, a low-discrepancy sequence."""
    return np.argsort(np.argsort((np.arange(n) * stride) % 1.0))


def _shuffled_in_blocks(values: np.ndarray, block: int,
                        rng: np.random.Generator) -> np.ndarray:
    out = values.copy()
    for a in range(0, len(out), block):
        out[a:a + block] = rng.permutation(out[a:a + block])
    return out


def requests(mix: dict, seconds: float, seed: int,
             vocab_size: int) -> list[dict]:
    """The window's requests: ``{"rid", "arrival_s", "prompt",
    "output_len"}``, sorted by arrival."""
    n = max(1, int(round(mix["requests_per_s"] * seconds)))
    span = seconds * mix["arrival_span_fraction"]
    p, o = mix["prompt_len"], mix["output_len"]
    prompts = lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                  p["max"])
    outputs = lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                  o["max"])
    gaps = exponential_gap_quantiles(n, span)
    # the seed decides the ORDER of each multiset, independently
    block = int(mix.get("order_block") or 0)
    if block:
        prompts = _shuffled_in_blocks(
            prompts[_spread_ranks(n, _STRIDES[0])], block, seed_rng(seed, 1))
        outputs = _shuffled_in_blocks(
            outputs[_spread_ranks(n, _STRIDES[1])], block, seed_rng(seed, 2))
    else:
        prompts = seed_rng(seed, 1).permutation(prompts)
        outputs = seed_rng(seed, 2).permutation(outputs)
    gaps = seed_rng(seed, 3).permutation(gaps)
    # the first request is due at t=0, the last at span - (its own gap)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    tok = seed_rng(seed, 4)
    return [{"rid": i, "arrival_s": float(arrivals[i]),
             "prompt": tok.integers(1, vocab_size, int(prompts[i])).astype(
                 np.int32),
             "output_len": int(outputs[i])}
            for i in range(n)]


def warmup(mix: dict, prefill_buckets, max_in_flight: int,
           vocab_size: int, seed: int) -> list[dict]:
    """One short request per prefill bucket the mix can reach, all due
    at once and padded up to ``max_in_flight`` so every decode bucket on
    the way down runs once too: first executions (program load, buffer
    donation) are paid in set-up, not in the window."""
    p = mix["prompt_len"]
    lens, prev = [], 0
    for b in prefill_buckets:
        top = min(b, p["max"])
        if top >= p["min"] and top > prev:
            lens.append(top)
        prev = b
    reach = len(lens)
    while len(lens) < max_in_flight:
        lens.append(lens[len(lens) % reach])
    tok = seed_rng(seed, 5)
    # outputs 2, 3, ...: one request retires per step, so the batch walks
    # down through every decode bucket
    return [{"rid": i, "arrival_s": 0.0,
             "prompt": tok.integers(1, vocab_size, int(n)).astype(np.int32),
             "output_len": 2 + i}
            for i, n in enumerate(lens)]


def tiny(mix: dict) -> dict:
    mix = copy.deepcopy(mix)
    mix["prompt_len"].update(median=12, min=4, max=32)
    mix["output_len"].update(median=5, min=2, max=8)
    mix["max_in_flight"] = 4
    # a closing mix stays a backlog at the tiny size too, so the lane's
    # drain at the close is rehearsed
    mix["requests_per_s"] = (400.0 if mix.get("close_window_at_seconds")
                             else min(mix["requests_per_s"], 4.0))
    return mix
