"""Decode kernels & quantized serving arms (round 18).

Layers under test, cheapest first:

- **ops**: ``paged_decode_attention`` (Pallas flash-decode through the
  page tables, interpret mode on the CPU mesh) pinned against the
  dense-gather ``_softmax_attend`` reference — f32 exact-ish, GQA,
  multi-page blocks, int8-with-scales, the lse fresh-token merge;
  ``fused_residual_norm`` pinned against the Flax modules it replaces.
- **programs**: ``serve.decode``'s prefill/decode builders on
  hand-built two-layer GPT and Llama minis — the paged program's
  logits match the gather reference to f32 tolerance, the int8 arms
  to stated bounds (the zero1-fingerprint style of proof).
- **engine**: ONE session-scoped warmed paged engine on ``moe_tiny``
  (the test_serve discipline: every closed loop in virtual time, no
  driver runs) — token-for-token greedy parity against the model's
  own full-context forward, zero lowering after warmup — plus one
  int8_kv engine for the quantized closed loop.
- **flags / tune space / staleness / dequantize-in-hot-loop lint /
  tune-show journal rendering**: the wiring around the kernels.

Anything paying its own fresh engine on a bigger family (llama parity,
the bench_serve decode-A/B subprocess) is slow-marked.
"""

from __future__ import annotations

import functools
import io
import json
import os
import subprocess
import sys
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_hc_bench import flags
from tpu_hc_bench.analysis import lints
from tpu_hc_bench.models import gpt as gpt_mod
from tpu_hc_bench.models import llama as llama_mod
from tpu_hc_bench.ops.fused_residual_ln import fused_residual_norm
from tpu_hc_bench.ops.paged_attention import paged_decode_attention
from tpu_hc_bench.serve import arrivals
from tpu_hc_bench.serve import decode as decode_mod
from tpu_hc_bench.serve import engine as engine_mod
from tpu_hc_bench.serve import slo
from tpu_hc_bench.tune import prune, space

VCOSTS = {"prefill": 0.004, "decode": 0.003, "classify": 0.002}


def _quiet(_msg):
    pass


def _pool(x):
    """``[..., pages, ps, kvh, d]`` (the reference's token-major order)
    -> the kernel's head-major pool ``[..., kvh, pages, ps, d]``."""
    return jnp.asarray(np.moveaxis(x, -2, -4))


def _gather_reference(q, k_pages, v_pages, tables, lengths):
    """Dense-gather reference in serve.decode._softmax_attend's exact
    convention (page gather -> GQA repeat -> masked f32 softmax)."""
    b, heads, d = q.shape
    pages, ps, kvh, _ = k_pages.shape
    w = tables.shape[1]
    group = heads // kvh
    kc = k_pages[tables].reshape(b, w * ps, kvh, d)
    vc = v_pages[tables].reshape(b, w * ps, kvh, d)
    if group > 1:
        kc = np.repeat(kc, group, axis=2)
        vc = np.repeat(vc, group, axis=2)
    mask = np.arange(w * ps)[None, :] < lengths[:, None]
    out = decode_mod._softmax_attend(
        jnp.asarray(q)[:, None], jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(mask))
    return np.asarray(out)[:, 0]


# --- ops: the paged flash-decode kernel -------------------------------


@pytest.mark.parametrize("b,heads,kvh,d,pages,ps,w,ppb", [
    (3, 4, 4, 16, 10, 4, 3, 1),      # MHA, one page per block
    (2, 8, 2, 32, 12, 8, 4, 2),      # GQA group 4, two pages per block
    (1, 2, 2, 8, 6, 4, 5, 4),        # width not divisible by the block
])
def test_paged_kernel_matches_gather_reference(b, heads, kvh, d, pages,
                                               ps, w, ppb):
    rng = np.random.default_rng(b * 100 + ppb)
    q = rng.standard_normal((b, heads, d)).astype(np.float32)
    kp = rng.standard_normal((pages, ps, kvh, d)).astype(np.float32)
    vp = rng.standard_normal((pages, ps, kvh, d)).astype(np.float32)
    tables = rng.integers(0, pages, (b, w)).astype(np.int32)
    lengths = rng.integers(1, w * ps + 1, (b,)).astype(np.int32)
    out = paged_decode_attention(
        jnp.asarray(q), _pool(kp), _pool(vp),
        jnp.asarray(tables), jnp.asarray(lengths), pages_per_block=ppb)
    want = _gather_reference(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)


def test_paged_kernel_lse_merges_fresh_token():
    """softmax over [cache, fresh] == the kernel's output mixed with
    the fresh value through sigmoid(s_new - lse) — the identity the
    paged decode program's scatter-after-attend ordering rests on."""
    rng = np.random.default_rng(7)
    b, heads, d, pages, ps, w = 2, 4, 16, 8, 4, 3
    q = rng.standard_normal((b, heads, d)).astype(np.float32)
    kp = rng.standard_normal((pages, ps, heads, d)).astype(np.float32)
    vp = rng.standard_normal((pages, ps, heads, d)).astype(np.float32)
    tables = rng.integers(0, pages, (b, w)).astype(np.int32)
    lengths = rng.integers(1, w * ps, (b,)).astype(np.int32)
    kf = rng.standard_normal((b, heads, d)).astype(np.float32)
    vf = rng.standard_normal((b, heads, d)).astype(np.float32)

    out, lse = paged_decode_attention(
        jnp.asarray(q), _pool(kp), _pool(vp),
        jnp.asarray(tables), jnp.asarray(lengths), return_lse=True)
    s_new = np.einsum("bhd,bhd->bh", q, kf) / d ** 0.5
    w_new = np.asarray(jax.nn.sigmoid(jnp.asarray(
        s_new - np.asarray(lse))))
    got = (np.asarray(out) * (1 - w_new)[..., None]
           + vf * w_new[..., None])

    # reference: dense softmax over the cache rows PLUS the fresh token
    kc = kp[tables].reshape(b, w * ps, heads, d)
    vc = vp[tables].reshape(b, w * ps, heads, d)
    mask = np.arange(w * ps)[None, :] < lengths[:, None]
    s = np.einsum("bhd,bkhd->bhk", q, kc) / d ** 0.5
    s = np.where(mask[:, None, :], s, -1e30)
    s_full = np.concatenate([s, s_new[:, :, None]], axis=-1)
    p = np.asarray(jax.nn.softmax(jnp.asarray(s_full), axis=-1))
    v_full = np.concatenate([vc, vf[:, None]], axis=1)
    want = np.einsum("bhk,bkhd->bhd", p, v_full)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_paged_kernel_int8_within_tolerance():
    """int8 pages + per-page scales dequantized inside the kernel stay
    within quantization tolerance of the f32 reference (and are exact
    against the explicitly dequantized pool)."""
    rng = np.random.default_rng(3)
    L, pages, ps, kvh, d, b, heads, w = 3, 8, 4, 2, 16, 2, 4, 3
    kf = rng.standard_normal((L, pages, ps, kvh, d)).astype(np.float32)
    vf = rng.standard_normal((L, pages, ps, kvh, d)).astype(np.float32)
    ks = np.maximum(np.abs(kf).reshape(L, pages, -1).max(-1) / 127, 1e-8)
    vs = np.maximum(np.abs(vf).reshape(L, pages, -1).max(-1) / 127, 1e-8)
    kq = np.round(kf / ks[..., None, None, None]).astype(np.int8)
    vq = np.round(vf / vs[..., None, None, None]).astype(np.int8)
    q = rng.standard_normal((b, heads, d)).astype(np.float32)
    tables = rng.integers(0, pages, (b, w)).astype(np.int32)
    lengths = rng.integers(1, w * ps + 1, (b,)).astype(np.int32)
    out = paged_decode_attention(
        jnp.asarray(q), _pool(kq), _pool(vq),
        jnp.asarray(tables), jnp.asarray(lengths), layer=1,
        k_scales=jnp.asarray(ks.astype(np.float32)),
        v_scales=jnp.asarray(vs.astype(np.float32)),
        pages_per_block=2)
    # exact against the dequantized pool...
    kdq = kq[1].astype(np.float32) * ks[1][:, None, None, None]
    vdq = vq[1].astype(np.float32) * vs[1][:, None, None, None]
    np.testing.assert_allclose(
        np.asarray(out), _gather_reference(q, kdq, vdq, tables, lengths),
        atol=2e-5)
    # ...and within int8 tolerance of the unquantized truth
    want = _gather_reference(q, kf[1], vf[1], tables, lengths)
    assert np.abs(np.asarray(out) - want).max() < 0.1


def test_paged_kernel_validation_loud():
    z = jnp.zeros
    with pytest.raises(ValueError, match="kv_heads"):
        paged_decode_attention(
            z((1, 3, 8)), z((2, 4, 4, 8)), z((2, 4, 4, 8)),
            z((1, 2), jnp.int32), z((1,), jnp.int32))
    with pytest.raises(ValueError, match="scales"):
        paged_decode_attention(
            z((1, 2, 8)), z((2, 4, 4, 8), jnp.int8),
            z((2, 4, 4, 8), jnp.int8),
            z((1, 2), jnp.int32), z((1,), jnp.int32))


# --- the gather arm: the pool read and written where it rests ---------


def _random_pool(rng, layers, kvh, pages, ps, d, lanes=128):
    """A pool as the programs keep it: ``[L, kvh, pages, ps, lanes]``,
    head_dim zero-padded to the lane tile."""
    pool = np.zeros((layers, kvh, pages, ps, lanes), np.float32)
    pool[..., :d] = rng.standard_normal((layers, kvh, pages, ps, d))
    return pool


@pytest.mark.parametrize("group", [1, 4])
def test_head_major_attend_matches_softmax_attend(group):
    """``_gather_rows`` + ``_attend_rows`` == ``_softmax_attend`` over
    rows gathered plainly with numpy indexing, fresh token appended,
    GQA by repeat: the arithmetic the gather arm stated before it read
    the pool head-major."""
    rng = np.random.default_rng(40 + group)
    layers, kvh, d, pages, ps, b, w = 3, 2, 16, 11, 4, 5, 3
    heads, layer = kvh * group, 1
    kp = _random_pool(rng, layers, kvh, pages, ps, d)
    vp = _random_pool(rng, layers, kvh, pages, ps, d)
    tables = rng.integers(0, pages, (b, w)).astype(np.int32)
    lengths = rng.integers(0, w * ps + 1, (b,)).astype(np.int32)
    lengths[0], lengths[1] = 0, w * ps      # empty and full caches
    q = rng.standard_normal((b, heads, d)).astype(np.float32)
    k_new = rng.standard_normal((b, kvh, d)).astype(np.float32)
    v_new = rng.standard_normal((b, kvh, d)).astype(np.float32)

    got = decode_mod._attend_rows(
        jnp.asarray(q),
        decode_mod._gather_rows(jnp.asarray(kp), layer, jnp.asarray(tables)),
        decode_mod._gather_rows(jnp.asarray(vp), layer, jnp.asarray(tables)),
        jnp.asarray(k_new), jnp.asarray(v_new), jnp.asarray(lengths))

    def dense(pool, fresh):     # [b, span + 1, heads, d]
        rows = pool[layer][:, tables][..., :d]      # [kvh, b, w, ps, d]
        rows = rows.transpose(1, 2, 3, 0, 4).reshape(b, w * ps, kvh, d)
        rows = np.concatenate([rows, fresh[:, None]], axis=1)
        return jnp.asarray(np.repeat(rows, group, axis=2))

    mask = np.concatenate(
        [np.arange(w * ps)[None, :] < lengths[:, None],
         np.ones((b, 1), bool)], axis=1)
    want = decode_mod._softmax_attend(
        jnp.asarray(q)[:, None], dense(kp, k_new), dense(vp, v_new),
        jnp.asarray(mask))[:, 0]
    assert got.shape == (b, heads, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)


def test_decode_row_write_matches_scatter_bitwise():
    """``_write_pool`` a row at a time == the ``.at[:, :, page,
    offset].set`` it replaced, bit for bit on every page a request owns
    (inactive rows all land on the trash page 0, which nobody reads)."""
    rng = np.random.default_rng(5)
    layers, kvh, d, pages, ps, b = 2, 3, 16, 9, 4, 6
    pool = _random_pool(rng, layers, kvh, pages, ps, d)
    new = rng.standard_normal((layers, b, kvh, d)).astype(np.float32)
    active = np.array([True, False, True, True, False, True])
    page_idx = np.where(active, [3, 7, 8, 1, 2, 5], 0).astype(np.int32)
    offset = np.array([0, 1, 3, 2, 1, 3], np.int32)
    rows = decode_mod._pool_rows(jnp.asarray(new), pool.shape[-1])
    want = jnp.asarray(pool).at[:, :, page_idx, offset].set(rows)
    got = decode_mod._write_pool(jnp.asarray(pool), rows[:, :, :, None],
                                 jnp.asarray(page_idx), jnp.asarray(offset))
    np.testing.assert_array_equal(np.asarray(got)[:, :, 1:],
                                  np.asarray(want)[:, :, 1:])
    # and it did write: the active rows hold the fresh K/V
    np.testing.assert_array_equal(
        np.asarray(got)[:, :, 3, 0, :d], new[:, 0])
    np.testing.assert_array_equal(
        np.asarray(got)[:, :, 2], pool[:, :, 2])    # inactive: untouched


@pytest.mark.parametrize("s,length,shared", [
    (16, 13, 0),        # partial last page, pad pages -> trash
    (16, 16, 0),        # the bucket exactly full
    (16, 10, 2),        # a cache hit: the first two slots zeroed
    (6, 5, 0),          # a bucket that is no whole number of pages
    (2, 2, 0),          # a bucket under one page
])
def test_prompt_page_write_matches_scatter_bitwise(s, length, shared):
    """``_write_prompt_pages`` == the per-position scatter it replaced
    on every row a request owns: positions < ``length`` in the pages
    its write table names, bit for bit; every other page but the trash
    page keeps what it held."""
    rng = np.random.default_rng(s * 100 + length)
    layers, kvh, d, pages, ps, w = 2, 3, 16, 12, 4, 5
    pool = _random_pool(rng, layers, kvh, pages, ps, d)
    new = rng.standard_normal((layers, s, kvh, d)).astype(np.float32)
    table = rng.permutation(np.arange(1, pages))[:w].astype(np.int32)
    table[:shared] = 0
    pos = np.arange(s)
    page_idx = np.where(pos < length,
                        table[np.clip(pos // ps, 0, w - 1)], 0)
    want = np.asarray(jnp.asarray(pool).at[:, :, page_idx, pos % ps].set(
        decode_mod._pool_rows(jnp.asarray(new), pool.shape[-1])))
    got = np.asarray(decode_mod._write_prompt_pages(
        jnp.asarray(pool), jnp.asarray(new), jnp.asarray(table),
        jnp.int32(length)))
    owned = {int(p) for p in page_idx if p}
    assert len(owned) == -(-length // ps) - shared
    for slot, page in enumerate(table):
        if page not in owned:
            continue
        n = min(ps, length - slot * ps)     # the rows up to `length`
        np.testing.assert_array_equal(got[:, :, page, :n],
                                      want[:, :, page, :n])
    others = [p for p in range(1, pages) if p not in owned]
    np.testing.assert_array_equal(got[:, :, others], pool[:, :, others])


# --- ops: fused residual + norm ---------------------------------------


def test_fused_residual_layernorm_matches_flax():
    import flax.linen as nn

    rng = np.random.default_rng(1)
    res = rng.standard_normal((3, 5, 64)).astype(np.float32)
    x = rng.standard_normal((3, 5, 64)).astype(np.float32)
    gamma = rng.standard_normal(64).astype(np.float32)
    beta = rng.standard_normal(64).astype(np.float32)
    y, o = fused_residual_norm(
        jnp.asarray(res), jnp.asarray(x), jnp.asarray(gamma),
        jnp.asarray(beta))
    want_y = res + x
    want_o = nn.LayerNorm().apply(
        {"params": {"scale": gamma, "bias": beta}}, jnp.asarray(want_y))
    np.testing.assert_allclose(np.asarray(y), want_y, atol=1e-6)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               atol=1e-5)


def test_fused_residual_rmsnorm_matches_llama():
    rng = np.random.default_rng(2)
    res = rng.standard_normal((4, 32)).astype(np.float32)
    x = rng.standard_normal((4, 32)).astype(np.float32)
    gamma = rng.standard_normal(32).astype(np.float32)
    y, o = fused_residual_norm(
        jnp.asarray(res), jnp.asarray(x), jnp.asarray(gamma),
        kind="rmsnorm")
    want_o = llama_mod.RMSNorm().apply(
        {"params": {"scale": gamma}}, jnp.asarray(res + x))
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               atol=1e-5)
    with pytest.raises(ValueError, match="beta"):
        fused_residual_norm(jnp.asarray(res), jnp.asarray(x),
                            jnp.asarray(gamma), kind="layernorm")
    with pytest.raises(ValueError, match="kind"):
        fused_residual_norm(jnp.asarray(res), jnp.asarray(x),
                            jnp.asarray(gamma), kind="batchnorm")


# --- programs: mini-family prefill/decode parity ----------------------


def _mini_model(kind: str):
    if kind == "gpt":
        # dense FFN: the GPTLM branch moe_tiny (MoE) never covers
        return gpt_mod.GPTLM(vocab_size=64, hidden=32, num_layers=2,
                             heads=2, ffn=64, max_len=32)
    return llama_mod.LlamaLM(vocab_size=64, hidden=32, num_layers=2,
                             heads=4, num_kv_heads=2, ffn=64, max_len=32)


@functools.lru_cache(maxsize=None)
def _decode_logits(kind: str, attention: str, quant: str,
                   block_pages: int = 0, steps: int = 2):
    """Prefill two prompts then run ``steps`` decode steps feeding a
    FIXED token stream (not argmax, so arms stay aligned bit-for-bit on
    inputs); returns the stacked per-step logits [steps, b, vocab].
    Cached: three tolerance tests per family share one gather/off
    reference run (tier-1 wall budget)."""
    model = _mini_model(kind)
    family = decode_mod.build_family(model, quant=quant)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    exec_params = (decode_mod.quantize_weights(family, params)
                   if quant == "int8_w" else params)
    page_size, w, b = 4, 4, 2
    kv = decode_mod.init_kv_state(family, 1 + b * w, page_size,
                                  jnp.float32, quant=quant)
    # jit: one compile per arm instead of an eager retrace per call
    # (the module's wall rides the tier-1 budget)
    prefill = jax.jit(decode_mod.build_prefill_fn(
        family, page_size, w, quant=quant))
    decode = jax.jit(decode_mod.build_decode_fn(
        family, page_size, w, attention=attention, quant=quant,
        block_pages=block_pages))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 64, n).astype(np.int32) for n in (5, 3)]
    tables = np.arange(1, 1 + b * w, dtype=np.int32).reshape(b, w)
    lengths = np.zeros((b,), np.int32)
    last = np.zeros((b,), np.int32)
    for i, prompt in enumerate(prompts):
        toks = np.zeros((1, 8), np.int32)
        toks[0, :len(prompt)] = prompt
        tok, _, kv = prefill(exec_params, kv, toks,
                             np.int32(len(prompt)), tables[i])
        lengths[i] = len(prompt)
        last[i] = int(np.asarray(tok)[0])
    feed = rng.integers(1, 64, (steps, b)).astype(np.int32)
    out = []
    for t in range(steps):
        _, logits, kv = decode(exec_params, kv, feed[t], tables,
                               lengths, np.ones((b,), bool))
        out.append(np.asarray(logits))
        lengths += 1
    return np.stack(out)


# the llama mini rides the slow lane like test_serve's llama engine
# parity: the default lane keeps one family (dense-GPT — the branch
# moe_tiny's engine pin never covers) per the tier-1 wall budget, and
# the llama program twins run under --runslow
_FAMILY_KINDS = ["gpt", pytest.param("llama", marks=pytest.mark.slow)]


@pytest.mark.parametrize("kind", _FAMILY_KINDS)
def test_paged_program_matches_gather_program(kind):
    """The paged decode program (kernel attention + lse fresh-token
    merge + fused residual norms) reproduces the gather reference's
    logits to f32 tolerance, greedy argmax identical — for BOTH
    families, dense-GPT (layernorm) and Llama (rmsnorm/GQA/RoPE).
    (Multi-page blocks are pinned at the kernel level above; re-running
    the whole program per block size would re-buy the same coverage
    against the tier-1 wall budget.)"""
    ref = _decode_logits(kind, "gather", "off")
    got = _decode_logits(kind, "paged", "off")
    np.testing.assert_allclose(got, ref, atol=2e-4)
    assert (got.argmax(-1) == ref.argmax(-1)).all()


@pytest.mark.parametrize("kind", _FAMILY_KINDS)
def test_int8_kv_program_within_tolerance(kind):
    """int8 KV pool (per-page scales written at prefill/append,
    consumed inside the kernel): logits within the stated bound of the
    f32 reference — |diff| <= 5% of the reference's logit range."""
    ref = _decode_logits(kind, "gather", "off")
    got = _decode_logits(kind, "paged", "int8_kv")
    bound = 0.05 * (ref.max() - ref.min())
    assert np.abs(got - ref).max() <= bound, (
        np.abs(got - ref).max(), bound)


@pytest.mark.parametrize("kind", _FAMILY_KINDS)
def test_int8_w_program_within_tolerance(kind):
    """Per-channel int8 weights dequantized at the matmul: same 5%%-of-
    range bound.  The gather arm suffices — the scale-fused einsum
    path is attention-kernel-independent by construction."""
    ref = _decode_logits(kind, "gather", "off")
    got = _decode_logits(kind, "gather", "int8_w")
    bound = 0.05 * (ref.max() - ref.min())
    assert np.abs(got - ref).max() <= bound, (
        np.abs(got - ref).max(), bound)


def test_int8_append_ignores_recycled_page_garbage():
    """Regression: the allocator never scrubs freed pages, so a page
    recycled from a retired request still holds the previous occupant's
    int8 rows and scale.  The append's requantize amax must only see
    THIS request's own rows (positions <= the append offset) — stale
    rows would otherwise inflate the fresh token's quantization scale
    arbitrarily (reads stay masked; precision is what's at stake)."""
    L, pages, ps, kvh, d = 1, 3, 4, 1, 4
    pages_q = jnp.zeros((L, kvh, pages, ps, d), jnp.int8)
    # page 2: previous occupant left full-range int8 rows at a scale
    # 1000x the new request's values
    pages_q = pages_q.at[0, :, 2].set(127)
    scales = jnp.ones((L, pages), jnp.float32).at[0, 2].set(100.0)
    new = jnp.full((L, 1, kvh, d), 0.125, jnp.float32)  # tiny fresh K
    out_q, out_sc = decode_mod._append_quantized(
        pages_q, scales, jnp.array([2], jnp.int32),
        jnp.array([0], jnp.int32), new)
    # scale reflects ONLY the fresh row, not the 12700.0 stale garbage
    assert float(out_sc[0, 2]) == pytest.approx(0.125 / 127.0)
    got = np.asarray(out_q[0, :, 2, 0], np.float32) * float(out_sc[0, 2])
    np.testing.assert_allclose(got, 0.125, rtol=0.02)
    # stale rows were zeroed, not requantized garbage
    assert (np.asarray(out_q[0, :, 2, 1:]) == 0).all()


def test_regress_fingerprint_back_compat_with_pre_r18_history():
    """Regression: adding decode_attention/quant to the fingerprint
    must not orphan pre-round-18 serve history — records without the
    keys normalize to the arms those runs effectively ran (gather/off),
    so a fresh default-arm run still compares against them while a
    paged run gets its own bucket."""
    from tpu_hc_bench.obs import regress

    old = {"metric": "m", "unit": "u", "extra": {"arrival_rate": 16.0}}
    fresh = {"metric": "m", "unit": "u",
             "extra": {"arrival_rate": 16.0,
                       "decode_attention": "gather", "quant": "off"}}
    paged = {"metric": "m", "unit": "u",
             "extra": {"arrival_rate": 16.0,
                       "decode_attention": "paged", "quant": "off"}}
    assert regress.fingerprint(old) == regress.fingerprint(fresh)
    assert regress.fingerprint(paged) != regress.fingerprint(fresh)


def test_quantize_weights_structure_and_roundtrip():
    model = _mini_model("gpt")
    family = decode_mod.build_family(model, quant="int8_w")
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    qp = decode_mod.quantize_weights(family, params)
    leaf = qp["layer_0"]["MultiHeadAttention_0"]["qkv"]["kernel"]
    assert set(leaf) == {"q", "scale"} and leaf["q"].dtype == jnp.int8
    # per-output-channel scale: one per (3, heads, d) output element
    assert leaf["scale"].shape == leaf["q"].shape[1:]
    # dequantized weight within half-step of the original everywhere
    w = params["layer_0"]["MultiHeadAttention_0"]["qkv"]["kernel"]
    deq = leaf["q"].astype(jnp.float32) * leaf["scale"]
    step = np.asarray(leaf["scale"])[None]
    assert (np.abs(np.asarray(deq) - np.asarray(w))
            <= 0.5 * step + 1e-8).all()
    # untouched leaves are the SAME objects (shared, not copied)
    assert qp["wte"]["embedding"] is params["wte"]["embedding"]
    assert (qp["layer_0"]["ln1"]["scale"]
            is params["layer_0"]["ln1"]["scale"])


# --- engine: the warmed paged arms ------------------------------------


@pytest.fixture(scope="session")
def paged_cfg():
    return flags.BenchmarkConfig(
        model="moe_tiny", workload="serve",
        arrival_rate=50.0, num_requests=8,
        max_prompt_len=8, max_output_len=4,
        max_in_flight=2, kv_page_size=4, seed=0,
        decode_attention="paged").resolve()


@pytest.fixture(scope="session")
def paged_engine(paged_cfg):
    return engine_mod.ServeEngine(paged_cfg, print_fn=_quiet)


class _TokenTap:
    """Minimal writer capturing request records' generated tokens."""

    enabled = False

    def __init__(self):
        self.tokens = {}

    def event(self, kind, **kw):
        if kind == "request":
            self.tokens[kw["id"]] = kw["generated"]

    def close(self):
        pass


@pytest.fixture(scope="session")
def paged_run(paged_cfg, paged_engine):
    reqs = arrivals.build_requests(paged_cfg,
                                   paged_engine.spec.vocab_size)
    tap = _TokenTap()
    summary = paged_engine.run(reqs, batching="continuous", writer=tap,
                               clock=engine_mod.VirtualClock(VCOSTS))
    return {"summary": summary, "tokens": tap.tokens, "requests": reqs}


def test_paged_engine_completes_with_frozen_ladder(paged_engine,
                                                   paged_run):
    s = paged_run["summary"]
    assert s["completed"] == s["requests"] == 8
    assert s["decode_attention"] == "paged" and s["quant"] == "off"
    assert s["decode_block_pages"] == 1      # paged arm reports blocks
    assert s["post_warmup_compiles"] == 0
    assert s["decode_steps"] > 0
    before = (paged_engine.lower_count, set(paged_engine.compiled))
    paged_engine.run(paged_run["requests"], batching="continuous",
                     clock=engine_mod.VirtualClock(VCOSTS))
    assert (paged_engine.lower_count, set(paged_engine.compiled)) \
        == before


def test_paged_engine_matches_full_forward(paged_engine, paged_run):
    """Token-for-token greedy parity: the paged Pallas decode (kernel
    attention + int32 page-table reads + fused norms) reproduces the
    model's own full-context forward — the moe/gpt family's pin; the
    llama twin is slow-marked below."""
    from tpu_hc_bench.models import create_model

    ref_model, _ = create_model(
        "moe_tiny", dtype=jnp.float32, seq_len=paged_engine.max_ctx,
        moe_impl="ragged")
    fwd = jax.jit(lambda v, t: ref_model.apply(v, t, train=False))
    requests = {r.rid: r for r in paged_run["requests"]}
    checked = 0
    for rid, want in paged_run["tokens"].items():
        if checked >= 3:
            break
        seq = list(np.asarray(requests[rid].prompt))
        got = []
        for _ in range(len(want)):
            toks = np.zeros((1, paged_engine.max_ctx), np.int32)
            toks[0, :len(seq)] = seq
            logits = fwd(paged_engine.variables, jnp.asarray(toks))
            nxt = int(np.asarray(logits)[0, len(seq) - 1].argmax())
            got.append(nxt)
            seq.append(nxt)
        assert got == want, f"request {rid}: {got} != {want}"
        checked += 1
    assert checked == 3


@pytest.mark.slow
def test_int8_kv_engine_closed_loop(paged_cfg):
    """The quantized closed loop: int8 pool + per-page scales through
    prefill/append/kernel-read, every request completes and the ladder
    stays frozen.  Slow-marked: it pays a fresh engine warmup, and the
    int8_kv numerics are already pinned in the default lane at program
    level (prefill + append + kernel read, both families)."""
    cfg = flags.BenchmarkConfig(
        **{**paged_cfg.__dict__, "translations": {},
           "explicit_flags": None, "tuned_config": None,
           "quant": "int8_kv"})
    eng = engine_mod.ServeEngine(cfg, print_fn=_quiet)
    assert eng.compile_record["quant"] == "int8_kv"
    reqs = arrivals.build_requests(cfg, eng.spec.vocab_size)
    s = eng.run(reqs, batching="continuous",
                clock=engine_mod.VirtualClock(VCOSTS))
    assert s["completed"] == 8 and s["post_warmup_compiles"] == 0
    assert s["quant"] == "int8_kv"
    # int8 pool state: pages int8, scales per (layer, page)
    kp, vp, ks, vs = eng._kv
    assert kp.dtype == jnp.int8 and vp.dtype == jnp.int8
    assert ks.shape == (eng.family.num_layers, eng.num_pages)


def test_classify_member_rejects_decode_knobs():
    cfg = flags.BenchmarkConfig(
        model="trivial", workload="serve",
        decode_attention="paged").resolve()
    with pytest.raises(ValueError, match="classify"):
        engine_mod.ServeEngine(cfg, print_fn=_quiet)


# --- flags ------------------------------------------------------------


def test_decode_flag_validity_matrix():
    def cfg(**kw):
        return flags.BenchmarkConfig(model="moe_tiny",
                                     workload="serve", **kw)

    with pytest.raises(ValueError, match="decode_attention"):
        cfg(decode_attention="dense").resolve()
    with pytest.raises(ValueError, match="quant"):
        cfg(quant="fp8").resolve()
    with pytest.raises(ValueError, match="paged"):
        cfg(quant="int8_kv").resolve()                # gather + int8_kv
    with pytest.raises(ValueError, match="decode_block_pages"):
        cfg(decode_block_pages=2).resolve()           # gather + blocks
    with pytest.raises(ValueError, match="decode_block_pages"):
        cfg(decode_attention="paged", decode_block_pages=-1).resolve()
    ok = cfg(decode_attention="paged", quant="int8_kv",
             decode_block_pages=2).resolve()
    assert "decode_attention=paged" in " ".join(ok.summary_lines())


def test_decode_flags_rejected_in_train_lane():
    with pytest.raises(ValueError, match="serving-lane"):
        flags.parse_flags(["--model", "trivial", "--quant", "int8_w"])
    with pytest.raises(ValueError, match="serving-lane"):
        flags.BenchmarkConfig(model="trivial",
                              decode_attention="paged").resolve()


# --- tune space / registry staleness / journal rendering --------------


def test_serve_levers_grow_kernel_arms():
    for lever in ("decode_attention", "quant", "decode_block_pages"):
        assert lever in space.SERVE_LEVERS
    sp = space.serve_member_space("moe_tiny")
    keys = {c.key for c in sp}
    assert "decode_attention=paged,max_in_flight=8" in keys
    assert ("decode_attention=paged,max_in_flight=8,quant=int8_kv"
            in keys)
    assert ("decode_attention=paged,decode_block_pages=2,"
            "max_in_flight=8" in keys)
    assert "max_in_flight=8,quant=int8_w" in keys
    # every generated combination survives flag-time resolve (int8_kv
    # and block pages only ever ride the paged arm)
    res = prune.static_prune(sp)
    assert [s.journal_record() for s in res.skipped] == []
    # classify members get no decode-kernel levers
    assert not any("decode_attention" in c.key or "quant" in c.key
                   for c in space.serve_member_space("trivial"))


def test_staleness_lint_flags_lane_crossed_kernel_levers(tmp_path):
    (tmp_path / "hw.json").write_text(json.dumps({
        "hardware": "hw", "members": {
            # training row spelling a serve kernel lever: lane-crossed
            "trivial": {"overrides": {"decode_attention": "paged"}},
            # @serve row with the kernel levers: legitimate
            "moe_tiny@serve": {"overrides": {
                "decode_attention": "paged", "quant": "int8_kv",
                "decode_block_pages": 2}},
        }}))
    found = lints.check_tuned_registry(tmp_path)
    locs = {f.location.split(":", 1)[1] for f in found}
    assert "trivial/decode_attention" in locs
    assert not any(loc.startswith("moe_tiny@serve") for loc in locs)


def test_tune_show_renders_kernel_levers_in_journal_rows():
    from tpu_hc_bench.tune.__main__ import _render_journal

    journal = {
        "model": "moe_tiny", "hardware": "cpu-test-w1",
        "status": "FINISHED", "spent_s": 10.0, "budget_s": 60.0,
        "skipped": [],
        "measurements": {
            "decode_attention=paged,decode_block_pages=2": {
                "0": {"score": 123.4, "wall_s": 1.0}},
            "quant=int8_kv,decode_attention=paged": {
                "0": {"score": 150.0, "peak_hbm_bytes": 2 ** 20}},
        },
    }
    buf = io.StringIO()
    with redirect_stdout(buf):
        _render_journal(journal)
    text = buf.getvalue()
    assert "decode_attention=paged,decode_block_pages=2" in text
    assert "score 123.4" in text
    assert "quant=int8_kv" in text and "peak 1.0 MiB" in text


# --- the dequantize-in-hot-loop lint ----------------------------------


DEQUANT_BAD = """
def decode(k_pages_q, scales, tables, x):
    for l in range(4):
        kc = k_pages_q[l][tables].astype(jnp.float32) * scales[l]
        x = x @ kc
    return x
"""

DEQUANT_SCALE_FUSED = """
def decode(params, x):
    for l in range(4):
        w = params[l]
        x = jnp.einsum("bh,hf->bf", x,
                       w["q"].astype(jnp.float32)) * w["scale"]
    return x
"""

DEQUANT_SCAN_BAD = """
def step(carry, w_int8):
    y = carry @ (w_int8.astype(jnp.float32) * 0.5)
    return y, y

out = jax.lax.scan(step, x0, ws)
"""


def test_dequant_lint_flags_dense_dequant_in_loop():
    found = lints.lint_source_text(DEQUANT_BAD, filename="x.py")
    assert [f.lint for f in found] == [lints.DEQUANT_HOT]
    assert found[0].severity == "error"
    assert "scale-fused" in found[0].message


def test_dequant_lint_accepts_scale_fused_matmul():
    found = [f for f in lints.lint_source_text(
        DEQUANT_SCALE_FUSED, filename="x.py")
        if f.lint == lints.DEQUANT_HOT]
    assert found == []


def test_dequant_lint_covers_scan_bodies():
    found = [f for f in lints.lint_source_text(
        DEQUANT_SCAN_BAD, filename="x.py")
        if f.lint == lints.DEQUANT_HOT]
    assert len(found) == 1
    # the same expression OUTSIDE any loop body never flags
    free = DEQUANT_SCAN_BAD.replace("out = jax.lax.scan(step, x0, ws)",
                                    "")
    assert not [f for f in lints.lint_source_text(free, filename="x.py")
                if f.lint == lints.DEQUANT_HOT]


def test_dequant_lint_suppression_and_query_name_exempt():
    sup = DEQUANT_BAD.replace(
        "* scales[l]",
        "* scales[l]  # thb:lint-ok[dequantize-in-hot-loop]")
    assert not [f for f in lints.lint_source_text(sup, filename="x.py")
                if f.lint == lints.DEQUANT_HOT]
    # a bare `q` is the attention query convention, not a quantized
    # buffer — the paged decode program's own s_new math must not flag
    query = """
def f(q, kf):
    for l in range(2):
        s = q.astype(jnp.float32) * kf.astype(jnp.float32)
    return s
"""
    assert not [f for f in lints.lint_source_text(query,
                                                  filename="x.py")
                if f.lint == lints.DEQUANT_HOT]


def test_repo_sources_dequant_clean():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    found = []
    for sub in ("tpu_hc_bench/ops", "tpu_hc_bench/serve"):
        base = os.path.join(repo, sub)
        for name in sorted(os.listdir(base)):
            if name.endswith(".py"):
                found.extend(lints.lint_file(os.path.join(base, name)))
    found = [f for f in found if f.lint == lints.DEQUANT_HOT]
    assert found == [], [f.message for f in found]


# --- obs: serve diff / slo rendering ----------------------------------


def test_serve_diff_notes_kernel_arm_changes():
    a = {"p99_e2e_ms": 10.0, "decode_attention": "gather",
         "quant": "off", "aot_decode_temp_bytes": 800000}
    b = {"p99_e2e_ms": 9.0, "decode_attention": "paged",
         "quant": "int8_kv", "aot_decode_temp_bytes": 700000}
    text = "\n".join(slo.serve_diff_lines(a, b))
    assert "decode-attention arm differs: gather -> paged" in text
    assert "quant arm differs: off -> int8_kv" in text
    assert "aot dec temp B" in text


def test_slo_lines_render_decode_arm():
    fold = {"completed": 8, "requests": 8, "batching": "continuous",
            "arrival": "poisson", "arrival_rate": 8.0,
            "decode_attention": "paged", "quant": "int8_kv",
            "decode_block_pages": 2,
            "aot_decode_temp_bytes": 2 ** 20}
    text = "\n".join(slo.slo_lines(fold))
    assert "attention=paged quant=int8_kv block_pages=2" in text
    assert "AOT temp 1.0 MiB" in text


# --- slow lane --------------------------------------------------------


@pytest.mark.slow
def test_llama_paged_engine_matches_full_forward():
    """The llama twin of the default-lane moe parity pin: RoPE per-row
    positions, GQA through the kernel's grouped grid, SwiGLU, rmsnorm
    fusion — token-for-token against the full-context forward (pays
    its own engine warmup, hence slow)."""
    from tpu_hc_bench.models import create_model

    cfg = flags.BenchmarkConfig(
        model="llama_tiny", workload="serve", arrival_rate=50.0,
        num_requests=3, max_prompt_len=8, max_output_len=4,
        max_in_flight=2, kv_page_size=4, seed=0,
        decode_attention="paged").resolve()
    eng = engine_mod.ServeEngine(cfg, print_fn=_quiet)
    reqs = arrivals.build_requests(cfg, eng.spec.vocab_size)
    tap = _TokenTap()
    s = eng.run(reqs, batching="continuous", writer=tap,
                clock=engine_mod.VirtualClock(VCOSTS))
    assert s["completed"] == 3 and s["post_warmup_compiles"] == 0

    ref_model, _ = create_model(
        "llama_tiny", dtype=jnp.float32, seq_len=eng.max_ctx)
    requests = {r.rid: r for r in reqs}
    for rid, want in tap.tokens.items():
        seq = list(np.asarray(requests[rid].prompt))
        got = []
        for _ in range(len(want)):
            toks = np.zeros((1, eng.max_ctx), np.int32)
            toks[0, :len(seq)] = seq
            logits = ref_model.apply(
                eng.variables, jnp.asarray(toks), train=False)
            nxt = int(np.asarray(logits)[0, len(seq) - 1].argmax())
            got.append(nxt)
            seq.append(nxt)
        assert got == want, f"request {rid}: {got} != {want}"
    assert len(tap.tokens) == 3


@pytest.mark.slow
def test_bench_serve_decode_ab_harness(tmp_path):
    """The decode-kernel A/B subprocess e2e at a scale where the dense
    gather's temporaries dominate: paged temp bytes down, token
    parity, zero post-warmup compiles on every arm (the r18
    acceptance shape; the committed artifact is
    artifacts/bench_decode_ab_r18.json)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "scripts/bench_serve.py", "--mode", "decode",
         "--max_prompt_len", "64", "--max_output_len", "32",
         "--max_in_flight", "16", "--kv_page_size", "16",
         "--num_requests", "12", "--arrival_rate", "30",
         "--metrics_root", str(tmp_path / "ab")],
        capture_output=True, text=True, env=env, timeout=570,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.loads(proc.stdout)
    v = rec["extra"]["verdict"]
    assert v["paged_temp_lt_gather"]
    assert v["paged_token_parity"]
    assert v["zero_post_warmup_compiles"] and v["all_completed"]
    assert rec["extra"]["arms"]["paged+int8_kv"]["aot_decode_args_bytes"] \
        < rec["extra"]["arms"]["gather+off"]["aot_decode_args_bytes"]
