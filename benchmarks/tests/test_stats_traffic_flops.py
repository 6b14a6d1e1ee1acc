"""Exact percentiles and worst-case counting; the stratified generator;
FLOP and byte functions against hand-worked values."""

import json
import math
import os

import numpy as np
import pytest

from families import gpt2
from harness import flops, readers, spec, stats, traffic
from harness.generators import stratified_open_loop as open_loop

# the open-loop chat mix as PR 32 read it on the chip (14.5 req/s = 0.8 of
# the knee of 18 req/s, lengths fully shuffled) and left OUT of the
# benchmark for its noise (PERF.md section 7): kept here so that the
# generator's open-loop arm stays held for the cell that comes back
CHAT_OPEN_LOOP = {
    "lane": "serve", "generator": "stratified_open_loop",
    "requests_per_s": 14.5, "arrival_span_fraction": 1.0,
    "prompt_len": {"median": 160, "sigma": 0.8, "min": 16, "max": 512},
    "output_len": {"median": 64, "sigma": 0.6, "min": 8, "max": 128},
    "max_in_flight": 16, "close_window_at_seconds": False}
SERVE_MIXES = ["chat-open-loop", "longprompt-backlog-r2"]
SEEDS = (1, 2, 2**31 + 12345)


def mix_of(name: str) -> dict:
    if name == "chat-open-loop":
        return dict(CHAT_OPEN_LOOP, name=name)
    return traffic.load_mix(name)


def test_percentile_is_nearest_rank_and_exact():
    v = list(range(1, 101))
    assert stats.percentile(v, 90) == 90
    assert stats.percentile(v, 50) == 50
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.percentile([3, 1, 2], 100) == 3
    assert stats.percentile(list(range(1, 11)), 90) == 9
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_failed_requests_count_as_the_worst():
    v = stats.with_failures([10.0] * 89, 11)        # 11% failed
    assert math.isinf(stats.percentile(v, 90))
    assert stats.finite_or_worst(stats.percentile(v, 90), 60000.0) == 60000.0
    v = stats.with_failures([10.0] * 95, 5)         # 5% failed: p90 holds
    assert stats.percentile(v, 90) == 10.0
    ctx = {"records": [{"ttft_ms": 10.0, "e2e_ms": 110.0, "output_len": 11}
                       ] * 8,
           "failed": 2, "drain_limit_ms": 60000.0}
    assert readers.tpot_percentile(ctx, 90) == 60000.0
    ctx["failed"] = 0
    assert readers.tpot_percentile(ctx, 90) == pytest.approx(10.0)


def test_iqr_share_is_statistics_quantiles():
    assert stats.iqr_share([10, 10, 10, 10, 10, 10]) == 0
    assert stats.iqr_share([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)


def test_the_checks_spread_leaves_out_the_run_farthest_from_the_median():
    """PR 29's first set of the Solar cell (PERF.md section 2): 1.25% with
    every run, 0.71% by the quartiles without 49.264, 1.11% by the range
    of what is left."""
    runs = [49.264, 48.533, 48.453, 48.883, 48.346, 48.379]
    kept = stats.without_farthest(runs)
    assert kept == [48.346, 48.379, 48.453, 48.533, 48.883]
    assert stats.iqr_share(runs) == pytest.approx(0.0125, abs=1e-4)
    assert stats.iqr_share(kept) == pytest.approx(0.00713, abs=1e-5)
    assert stats.range_share(kept) == pytest.approx(0.537 / 48.453)
    assert stats.range_share(kept) >= stats.iqr_share(kept)
    # the farthest may lie below the median as well as above it
    assert stats.without_farthest([10, 11, 12, 13, 2]) == [10, 11, 12, 13]


@pytest.mark.parametrize("mix_name", SERVE_MIXES)
def test_every_seed_offers_the_same_multiset_in_another_order(mix_name):
    mix = mix_of(mix_name)
    runs = [open_loop.requests(mix, 51, seed, 50257) for seed in SEEDS]
    lens = [sorted(len(r["prompt"]) for r in run) for run in runs]
    outs = [sorted(r["output_len"] for r in run) for run in runs]
    gaps = [np.sort(np.diff([r["arrival_s"] for r in run])) for run in runs]
    assert lens[0] == lens[1] == lens[2]
    assert outs[0] == outs[1] == outs[2]
    # the gaps between arrivals: the same multiset but for the one gap
    # that closes the span unseen, which the seed picks
    for g in gaps[1:]:
        assert len(g) == len(gaps[0])
        assert len(set(np.round(g, 9)) ^ set(np.round(gaps[0], 9))) <= 2
    assert [len(r["prompt"]) for r in runs[0]] != \
        [len(r["prompt"]) for r in runs[1]]
    assert any((a["prompt"][:4] != b["prompt"][:4]).any()
               for a, b in zip(runs[0], runs[1])
               if len(a["prompt"]) == len(b["prompt"]))
    p = mix["prompt_len"]
    assert p["min"] <= lens[0][0] and lens[0][-1] <= p["max"]
    span = 51 * mix["arrival_span_fraction"]
    assert all(run[-1]["arrival_s"] < span for run in runs)
    assert all(run[0]["arrival_s"] == 0.0 for run in runs)


def test_block_order_gives_every_seed_the_same_work_in_any_prefix():
    """A backlog's window ends inside the trace: whichever prefix it
    holds has to be the same work for every seed, and a fair sample of
    the whole mix."""
    mix = traffic.load_mix("longprompt-backlog-r2")
    b = mix["order_block"]
    runs = [open_loop.requests(mix, 51, seed, 50257) for seed in SEEDS]
    n = len(runs[0])
    for k in range(b, n + 1, b):
        heads = [(sorted(len(r["prompt"]) for r in run[:k]),
                  sorted(r["output_len"] for r in run[:k])) for run in runs]
        assert heads[0] == heads[1] == heads[2]
    whole_p = np.mean([len(r["prompt"]) for r in runs[0]])
    whole_o = np.mean([r["output_len"] for r in runs[0]])
    for a in range(0, n - 32, 8):
        part = runs[0][a:a + 32]
        assert np.mean([len(r["prompt"]) for r in part]) == pytest.approx(
            whole_p, rel=0.1)
        assert np.mean([r["output_len"] for r in part]) == pytest.approx(
            whole_o, rel=0.1)
    # prompt and output lengths are paired as good as independently
    c = np.corrcoef([len(r["prompt"]) for r in runs[0]],
                    [r["output_len"] for r in runs[0]])[0, 1]
    assert abs(c) < 0.15


@pytest.mark.parametrize("mix_name,offered", [
    ("chat-open-loop", 740), ("longprompt-backlog-r2", 1732)])
def test_the_mixes_offer_what_the_cells_say(mix_name, offered):
    """``run_seconds`` x the mix's rate, whatever the seed; the backlog's
    all due in the first tenth of the window, in whole blocks.  PR 32:
    3 x the 577 the lane begins in 51 s, rounded up to a block of 4 (and
    0.8 x the knee of 18 req/s x 51 s in the chat mix it left out)."""
    mix = mix_of(mix_name)
    seconds = spec.load_benchmark()["run_seconds"]
    for seed in SEEDS:
        run = open_loop.requests(mix, seconds, seed, 50257)
        assert len(run) == offered
        assert run[-1]["arrival_s"] < seconds * mix["arrival_span_fraction"]
    assert mix["max_in_flight"] == 16
    assert offered % mix.get("order_block", 1) == 0


@pytest.mark.parametrize("mix_name", SERVE_MIXES)
def test_every_request_fits_the_published_positions(mix_name):
    """Prompt + output inside GPT-2's 1,024 positions for every request
    of every seed: no operation of the traffic can fail on length."""
    mix = mix_of(mix_name)
    positions = gpt2m()["n_positions"]
    for seed in SEEDS:
        run = open_loop.requests(mix, 51, seed, 50257)
        assert max(len(r["prompt"]) + r["output_len"] for r in run) \
            <= positions == 1024
        assert min(r["output_len"] for r in run) >= 2   # a TPOT each


def test_gap_quantiles_sum_to_the_span_whatever_the_seed():
    g = open_loop.exponential_gap_quantiles(128, 51.0)
    assert g.sum() == pytest.approx(51.0)
    assert (np.diff(g) > 0).all()


def test_same_seed_same_inputs():
    mix = mix_of("chat-open-loop")
    a = open_loop.requests(mix, 10, 99, 50257)
    b = open_loop.requests(mix, 10, 99, 50257)
    assert all((x["prompt"] == y["prompt"]).all()
               and x["arrival_s"] == y["arrival_s"] for x, y in zip(a, b))
    m = {"batch_per_chip": 4, "seq_len": 16}
    t1 = gpt2.train_batch({"vocab_size": 100}, m, 3, 1)
    t2 = gpt2.train_batch({"vocab_size": 100}, m, 3, 1)
    assert (t1[0] == t2[0]).all()
    assert len({tuple(r) for r in t1[0]}) == 4      # rows all differ


def test_warmup_touches_every_reachable_prefill_bucket():
    mix = traffic.load_mix("longprompt-backlog-r2")
    w = open_loop.warmup(mix, (8, 16, 32, 64, 128, 256, 512, 1024),
                                16, 50257, 1)
    assert len(w) == 16
    assert {len(r["prompt"]) for r in w} == {256, 512, 896}
    assert sorted(r["output_len"] for r in w) == list(range(2, 18))


def gpt2m():
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "gpt2_medium.json")) as f:
        return json.load(f)


def test_gpt2_medium_parameter_count_is_the_published_one():
    p = gpt2.params(gpt2m())
    assert p["matmul"] == 24 * 12 * 1024 * 1024 + 50257 * 1024
    assert p["total"] == 354_823_168        # openai-community/gpt2-medium


def test_gpt2_medium_flops_by_hand():
    cfg = gpt2m()
    per_token = 2 * (24 * 12 * 1024 ** 2 + 50257 * 1024)
    assert gpt2.decode_flops_per_token(cfg) == per_token == 706_906_112
    # causal attention over 1,024 tokens: 24 layers x 2 matmuls x
    # 2 x 1024 x 1024 x 1024 / 2
    attn = 24 * 2 * 2 * 1024 ** 3 / 2
    assert gpt2.attention_forward_flops(cfg, 1024) == attn
    fwd = 1024 * per_token + attn
    assert gpt2.sequence_forward_flops(cfg, 1024) == fwd
    assert gpt2.train_step_flops_per_example(cfg, {"seq_len": 1024}) == 3 * fwd
    assert 3 * fwd / 1024 == pytest.approx(2.272e9, rel=1e-3)  # per token


def test_flash_attention_call_by_hand():
    c = flops.flash_attention_call(16, 1024, 16, 64)
    mm = 2 * 16 * 16 * 1024 * 1024 * 64 / 2
    assert c["fwd_flops"] == 2 * mm and c["bwd_flops"] == 5 * mm
    assert c["fwd_bytes"] == 4 * 16 * 1024 * 1024 * 2
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.roofline_seconds(c["fwd_flops"], c["fwd_bytes"], peaks)
    assert bound == "compute" and t == pytest.approx(2 * mm / 197e12)
