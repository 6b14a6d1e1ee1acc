"""The sliding-window / full-attention family with softmax-routed experts
(``models/mellum2``) on the serve lane's normal path, at the tiny preset
on the CPU with seeded random weights: prefill then decode through the
full layers' pages and the window layers' ring against the benchmark's
plain reference, contexts past the window and the ring wrapping
mid-page; the window's width held to the reference's; YaRN at the
published sizes; the softmax share path against the reference's experts;
the ring's pages in the cache manager; what the engine counts and
refuses."""

from __future__ import annotations

import copy
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from tpu_hc_bench.models import mellum2 as mm  # noqa: E402
from tpu_hc_bench.serve import decode as decode_mod  # noqa: E402

PAGE, WIDTH = 2, 24
# float32 on both sides, logits of magnitude ~1: the flash kernel and the
# packed read sum in another order than the reference's scores over every
# key (at most ~2e-6 read); a wrong position, rotary or cache row moves
# logits by 1e-2 and more
ATOL = 5e-5


def _config():
    from harness import spec

    return spec.config_of(spec.load_benchmark(), "mellum2_12b_a2_5b_8l")


@pytest.fixture(scope="module")
def tiny():
    """The tiny configuration's weights on both sides: the reference's
    tree and the program's, from one seed, each drawn in one jitted call
    as the harness draws them (a draw outside jit can round a few leaves
    to the other neighbouring bfloat16); float32."""
    from families import mellum2 as fam
    from harness import adapters

    cfg = fam.tiny_config(_config())
    to32 = lambda t: jax.tree.map(                      # noqa: E731
        lambda x: x.astype(jnp.float32), t)
    model = mm.Mellum2LM(dtype=jnp.float32, **fam.program_sizes(cfg))
    return {"cfg": cfg, "ref": fam.reference, "model": model,
            "params": to32(adapters.program_weights(cfg, 7)),
            "ref_params": to32(fam.reference.make_params(cfg, 7)),
            "family": decode_mod.build_family(model)}


def _ring(t):
    return decode_mod.ring_width(t["family"], PAGE, WIDTH)


def _programs(t, rows=3):
    fam = t["family"]
    kv = decode_mod.init_kv_state(fam, 1 + rows * WIDTH, PAGE, jnp.float32,
                                  window_pages=1 + rows * _ring(t))
    return (kv, jax.jit(decode_mod.build_prefill_fn(fam, PAGE, WIDTH)),
            jax.jit(decode_mod.build_decode_fn(fam, PAGE, WIDTH)))


def _table(t, r):
    """Row ``r``'s table: its full pages, then its ring."""
    ring = _ring(t)
    return np.array(list(range(1 + r * WIDTH, 1 + (r + 1) * WIDTH))
                    + list(range(1 + r * ring, 1 + (r + 1) * ring)),
                    np.int32)


def _serve(t, kv, prefill, decode, seqs, plens, bucket=32, rows=3):
    """Prefill each of ``seqs[i][:plens[i]]`` into row ``i + 1`` of a
    ``rows``-row bucket (row 0 inactive), then decode them side by side,
    each to its own end; returns each one's logits from ``plen - 1``
    on."""
    tables = np.zeros((rows, WIDTH + _ring(t)), np.int32)
    out = [[] for _ in seqs]
    for i, (toks, plen) in enumerate(zip(seqs, plens)):
        tables[i + 1] = _table(t, i)
        pad = np.zeros((1, bucket), np.int32)
        pad[0, :plen] = toks[:plen]
        _, lg, kv = prefill(t["params"], kv, pad, np.int32(plen),
                            tables[i + 1])
        out[i].append(np.asarray(lg[0]))
    n = list(plens)
    while any(n[i] < len(s) for i, s in enumerate(seqs)):
        feed = np.zeros((rows,), np.int32)
        lengths = np.zeros((rows,), np.int32)
        on = np.zeros((rows,), bool)
        for i, s in enumerate(seqs):
            if n[i] < len(s):
                feed[i + 1], lengths[i + 1], on[i + 1] = s[n[i]], n[i], True
        _, lg, kv = decode(t["params"], kv, feed, tables, lengths, on)
        for i in np.flatnonzero(on[1:]):
            out[i].append(np.asarray(lg[i + 1]))
            n[i] += 1
    return [np.stack(o) for o in out], kv


def _reference_logits(t, toks, cfg=None):
    cfg = cfg or t["cfg"]
    h = t["ref"].hidden_states(t["ref_params"], toks[None], cfg, "f32")
    return np.asarray(t["ref"].logits_of(t["ref_params"], h[0], "f32"))


@pytest.mark.parametrize("plens,bucket,extra", [
    ((5,), 8, 20),          # a short prompt, the ring wraps twice decoding
    ((13, 4), 16, 17),      # an odd prompt past the window, two rows
    ((23,), 32, 9),         # a prompt whose pages outnumber the ring
    ((32, 9), 32, 6),       # a prompt that fills its bucket
])
def test_prefill_then_decode_equals_the_references_full_forward(
        tiny, plens, bucket, extra):
    """Through the full layers' pages and the window layers' ring against
    the plain reference's one pass over the whole sequence, on logits:
    contexts cross the window's edge (8) and the ring (5 pages of 2)
    wraps in the middle of a page, prefill and decode alike."""
    t = tiny
    rng = np.random.default_rng(sum(plens))
    seqs = [rng.integers(1, 256, p + extra).astype(np.int32) for p in plens]
    kv, prefill, decode = _programs(t)
    got, _ = _serve(t, kv, prefill, decode, seqs, plens, bucket)
    for s, p, g in zip(seqs, plens, got):
        np.testing.assert_allclose(g, _reference_logits(t, s)[p - 1:],
                                   atol=ATOL)


def test_a_window_one_wider_is_caught(tiny):
    """The reference with a window of W + 1 differs from the program past
    the window's edge by far more than the tolerance: the comparison
    holds the window's width, not only its presence."""
    t = tiny
    toks = np.random.default_rng(5).integers(1, 256, 30).astype(np.int32)
    kv, prefill, decode = _programs(t)
    (got,), _ = _serve(t, kv, prefill, decode, [toks], [12], 16)
    wider = copy.deepcopy(t["cfg"])
    wider["sliding_window"] += 1
    off = np.abs(got - _reference_logits(t, toks, wider)[11:]).max()
    assert off > 100 * ATOL


def test_models_own_forward_equals_the_reference(tiny):
    t = tiny
    toks = np.random.default_rng(3).integers(1, 256, (2, 21)).astype(
        np.int32)
    got = t["model"].apply({"params": t["params"]}, jnp.asarray(toks),
                           train=False)
    h = t["ref"].hidden_states(t["ref_params"], toks, t["cfg"], "f32")
    np.testing.assert_allclose(
        got, t["ref"].logits_of(t["ref_params"], h, "f32"), atol=ATOL)


def test_yarn_at_the_published_sizes():
    """low 18 and high 35 at head_dim 128 (the published config's
    arithmetic), and the frequencies as the formula is written here."""
    d, theta = 128, 500000.0
    low, high = mm.yarn_bounds(d, theta, 8192, 32.0, 1.0)
    assert (low, high) == (18, 35)
    f = [theta ** (-2 * i / d) for i in range(d // 2)]
    ramp = [min(max((i - 18) / (35 - 18), 0.0), 1.0) for i in range(d // 2)]
    want = [f[i] / 16 * ramp[i] + f[i] * (1 - ramp[i]) for i in range(d // 2)]
    got = mm.yarn_inv_freq(d, theta, 16.0, 8192, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert got[17] == f[17] and math.isclose(got[40], f[40] / 16)
    model = mm.mellum2_12b_a2_5b_8l()
    assert model.rope(0)[1] == 1.0 and model.rope(3)[1] == 1.2772588722239782
    np.testing.assert_allclose(model.rope(3)[0], want, rtol=1e-12)


@pytest.mark.parametrize("rows", [4, 300])
def test_softmax_share_matches_the_references_experts(tiny, rows):
    """The share path under softmax routing, at 4 rows (every expert over
    every row: ``_dense``) and at 300 (grouped matmuls: ``_ragged``),
    against the reference's every-expert sum, float32."""
    t = tiny
    lp = t["ref_params"]["layers"][0]
    h = jnp.asarray(np.random.default_rng(rows).normal(size=(1, rows, 64)),
                    jnp.float32)
    moe = t["model"].moe_module()
    grouped = "ragged_dot" in str(jax.make_jaxpr(
        lambda p, x: moe.apply(p, x, mutable=["stats"]))(
            {"params": t["params"]["layer_0_moe"]}, h))
    assert grouped == (rows == 300)
    got, _ = moe.apply({"params": t["params"]["layer_0_moe"]}, h,
                       mutable=["stats"])
    z = t["ref"].sizes(t["cfg"])
    with jax.default_matmul_precision("highest"):
        want = t["ref"].moe(h, lp, z, "f32")
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_families_parameter_count_is_the_programs_tree():
    """3,794,966,784 at the published widths (the configuration's
    arithmetic: 8 layers of 417,747,456, the embedding, the head and the
    final norm), held against the program's own tree."""
    from families import mellum2 as fam

    cfg = _config()
    assert fam.params(cfg)["total"] == 3_794_966_784
    model = mm.Mellum2LM(dtype=jnp.bfloat16, **fam.program_sizes(cfg))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes["params"]))
    assert n == fam.params(cfg)["total"]


# ---------------------------------------------------------------------
# the cache manager and the engine


def test_ring_pages_are_fixed_per_row_and_returned_at_finish():
    from tpu_hc_bench.serve import cache as cache_mod

    cm = cache_mod.CacheManager(1 + 2 * 6, 4, 6, ring_pages=1 + 2 * 3,
                                ring_width=3)
    assert cm.table_cols == 9
    a = cm.admit(np.arange(5))
    b = cm.admit(np.arange(9))
    assert len(a.ring) == len(b.ring) == 3 and not set(a.ring) & set(b.ring)
    assert list(a.table[6:]) == a.ring and 0 not in a.ring
    # the ring pool is full: a third request waits for a ring
    assert cm.blocked_on(np.arange(3)) == "pool_starved"
    held = cache_mod.Holding(pages=a.pages, table=a.table, length=5,
                             ring=a.ring)
    cm.release(held)
    assert held.ring == [] and cm.rings.free_pages == 3
    assert cm.blocked_on(np.arange(3)) is None
    c = cm.admit(np.arange(3))
    assert sorted(c.ring) == sorted(a.ring)


def _cfg(**kw):
    from tpu_hc_bench import flags

    base = dict(model="mellum2_tiny", workload="serve",
                arrival_rate=1000.0, num_requests=8, max_prompt_len=16,
                max_output_len=10, max_in_flight=4, kv_page_size=2, seed=0)
    base.update(kw)
    return flags.BenchmarkConfig(**base).resolve()


def test_engine_serves_the_family_and_counts_both_kinds_of_page():
    """bfloat16, the ring pool beside the full pages, the window layers'
    scope in both programs, the experts-hit counter, and ``kv_read``:
    full layers by their length, window layers by the pages their window
    reaches."""
    from tpu_hc_bench.serve import arrivals
    from tpu_hc_bench.serve import engine as engine_mod

    eng = engine_mod.ServeEngine(_cfg(use_fp16=True),
                                 print_fn=lambda m: None)
    kv = eng._kv
    assert set(kv) == {"pages", "window"}
    assert kv["pages"][0].shape[0] == 1 and kv["window"][0].shape[0] == 3
    assert kv["window"][0].dtype == jnp.bfloat16
    assert eng.ring_width == 5 and eng.table_cols == eng.table_width + 5
    assert kv["window"][0].shape[2] == 1 + eng.cap * 5
    steps = []

    class Tap:
        def __init__(self, exe):
            self.exe = exe

        def __call__(self, *a):
            steps.append((a[3].shape[0], np.array(a[4]), np.array(a[5])))
            return self.exe(*a)

    eng.compiled = {k: Tap(v) if k[0] == "decode" else v
                    for k, v in eng.compiled.items()}
    summary = eng.run(
        arrivals.build_requests(eng.cfg, eng.spec.vocab_size),
        clock=engine_mod.VirtualClock({"prefill": 0.004, "decode": 0.003}))
    assert summary["completed"] == 8
    assert summary["post_warmup_compiles"] == 0
    assert {"swa", "gqa", "moe", "head"} == set(
        summary["op_parts"][f"decode@{eng.cap}"].values())
    assert "swa" in summary["op_parts"][f"prefill@{eng.prefill_buckets[-1]}"
                                        ].values()
    # 2 experts of 8 a row a layer: at least 2, at most 8, a layer a step
    assert 4 * 2 * len(steps) <= summary["moe_experts_hit"] <= (
        4 * 8 * len(steps))
    assert "moe_picks" not in summary
    read = rect = 0
    for b, lengths, active in steps:
        full = np.where(active, -(-lengths // 2), 0).sum()
        first = np.maximum(lengths - 8 + 1, 0) // 2
        win = np.where(active & (lengths > 0),
                       (lengths - 1) // 2 - first + 1, 0).sum()
        read += (-(-full // eng.decode_chunk[b]) * eng.decode_chunk[b]
                 + 3 * (-(-win // eng.window_chunk[b])
                        * eng.window_chunk[b]))
        rect += 4 * b * eng.table_width
    assert summary["kv_read"] == {"pages_read": read, "pages_rect": rect}
    assert max(int(l.max()) for _, l, _ in steps) > 8    # past the window


@pytest.mark.parametrize("flag,value,match", [
    ("prefix_cache", "on", "prefix_cache"),
    ("decode_attention", "paged", "decode_attention=paged"),
    ("quant", "int8_w", "quant"),
])
def test_engine_refuses_loudly_what_the_family_does_not_support(
        flag, value, match):
    from tpu_hc_bench.serve import engine as engine_mod

    kw = {flag: value}
    if flag == "prefix_cache":
        kw["kv_reserve"] = "lazy"
    with pytest.raises(ValueError, match=match):
        engine_mod.ServeEngine(_cfg(**kw), print_fn=lambda m: None)
