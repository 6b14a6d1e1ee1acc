"""The hybrid Mamba-2 / softmax-attention family (``models/granite4h``)
on the serve lane's normal path, at the tiny preset on the CPU with
seeded random weights: the chunked SSD form against the token-by-token
recurrence, prefill then decode through both pools against the
benchmark's plain reference, inert padding, slot hygiene, what the
engine refuses for this family — and the mixer-generic hybrid programs
against the parent's KDA-only ones on the Solar family, bit for bit."""

from __future__ import annotations

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

from tpu_hc_bench.models import granite4h as gh  # noqa: E402
from tpu_hc_bench.models import solar_open2 as so  # noqa: E402
from tpu_hc_bench.serve import decode as decode_mod  # noqa: E402

PAGE, WIDTH = 4, 8
# float32 on both sides, logits of magnitude ~1: the chunked SSD sums in
# another order than the reference's token-by-token recurrence, and the
# gated norm divides by the RMS of y * SiLU(z), which is small at a few
# positions and magnifies that order's rounding (at most 4.2e-5 read,
# most positions under 3e-6; the model's own full forward reads the same
# at the same positions); a wrong cache row or tail moves logits by 1e-2
ATOL = 1e-4


def _recurrence_inputs(s, heads=3, hp=8, n=16, seed=1):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape),   # noqa: E731
                                   jnp.float32)
    dt = jnp.asarray(rng.uniform(0, 0.5, (s, heads)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 4, heads), jnp.float32)
    return f(s, heads, hp), f(s, n), f(s, n), dt, A, f(heads, hp, n)


def _token_by_token(x, B, C, dt, A, h0):
    def body(h, xs):
        return gh.ssd_step(h, *xs, A)

    h, y = jax.lax.scan(body, h0, (x, B, C, dt))
    return y, h


@pytest.mark.parametrize("s,chunk", [(48, 48), (48, 16), (48, 8), (40, 16),
                                     (7, 64)])
def test_chunked_ssd_equals_the_recurrence(s, chunk):
    """Outputs and state, one to six chunks, a length that is no multiple
    of the chunk (``ssd_sequence`` pads with inert positions), from a
    state that is not zero."""
    x, B, C, dt, A, h0 = _recurrence_inputs(s)
    y_ref, h_ref = _token_by_token(x, B, C, dt, A, h0)
    y, h = gh.ssd_sequence(x, B, C, dt, A, h0, chunk)
    np.testing.assert_allclose(y, y_ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(h, h_ref, atol=2e-5, rtol=2e-5)


def test_padded_positions_are_inert():
    """``dt`` = 0 past the prompt: the state after a padded bucket is the
    state after the prompt alone, bit for bit (decay 1, no input)."""
    x, B, C, dt, A, h0 = _recurrence_inputs(32)
    n = 19
    valid = jnp.arange(32) < n
    _, h_pad = gh.ssd_chunked(x, B, C, jnp.where(valid[:, None], dt, 0.0),
                              A, h0, chunk=8)
    _, h_ref = _token_by_token(x[:n], B[:n], C[:n], dt[:n], A, h0)
    np.testing.assert_allclose(h_pad, h_ref, atol=2e-5, rtol=2e-5)
    h_one, _ = gh.ssd_step(h_ref, x[n], B[n], C[n], jnp.zeros_like(dt[n]),
                           A)
    np.testing.assert_array_equal(h_one, h_ref)


# ---------------------------------------------------------------------
# the programs against the benchmark's plain reference


@pytest.fixture(scope="module")
def tiny():
    """The tiny configuration's weights on both sides: the reference's
    tree and the program's, from one seed; float32 (the CPU multiplies
    float32 as float32, so both sides compute the same arithmetic)."""
    from harness import spec
    from families import granite4h as fam

    cfg = fam.tiny_config(spec.config_of(spec.load_benchmark(),
                                         "granite_4_0_h_micro"))
    ref = fam.reference
    to32 = lambda t: jax.tree.map(                      # noqa: E731
        lambda x: x.astype(jnp.float32), t)
    model = gh.GraniteHybridLM(dtype=jnp.float32, **fam.program_sizes(cfg))
    params = to32(fam.program_tree(ref.leaf_values(cfg, 7), cfg))
    return {"cfg": cfg, "ref": ref, "model": model, "params": params,
            "ref_params": to32(ref.make_params(cfg, 7)),
            "family": decode_mod.build_family(model)}


def _programs(t, slots=4, pages=1 + 3 * WIDTH):
    fam = t["family"]
    kv = decode_mod.init_kv_state(fam, pages, PAGE, jnp.float32,
                                  slots=slots)
    return (kv, jax.jit(decode_mod.build_prefill_fn(fam, PAGE, WIDTH)),
            jax.jit(decode_mod.build_decode_fn(fam, PAGE, WIDTH)))


def _table(first_page, slot):
    return np.array(list(range(first_page, first_page + WIDTH)) + [slot],
                    np.int32)


def _serve_one(t, kv, prefill, decode, toks, plen, table, bucket=16,
               row=1, rows=2):
    """Prefill ``toks[:plen]`` then decode the rest one token a step in
    row ``row`` of a ``rows``-row bucket; returns the logits of every
    position from ``plen - 1`` on, and the cache."""
    pad = np.zeros((1, bucket), np.int32)
    pad[0, :plen] = toks[:plen]
    _, lg, kv = prefill(t["params"], kv, pad, np.int32(plen), table)
    out = [np.asarray(lg[0])]
    for n in range(plen, len(toks)):
        tables = np.zeros((rows, WIDTH + 1), np.int32)
        tables[row] = table
        feed = np.zeros((rows,), np.int32)
        feed[row] = toks[n]
        lengths = np.zeros((rows,), np.int32)
        lengths[row] = n
        on = np.zeros((rows,), bool)
        on[row] = True
        _, lg, kv = decode(t["params"], kv, feed, tables, lengths, on)
        out.append(np.asarray(lg[row]))
    return np.stack(out), kv


def _reference_logits(t, toks):
    h = t["ref"].hidden_states(t["ref_params"], toks[None], t["cfg"], "f32")
    return np.asarray(t["ref"].logits_of(t["ref_params"], h[0], "f32"))


@pytest.mark.parametrize("plen,bucket", [(5, 16), (11, 16), (16, 16),
                                         (23, 32)])
def test_prefill_then_decode_equals_the_references_full_forward(
        tiny, plen, bucket):
    """Through both pools (pages for the attention layer, a slot for the
    Mamba-2 layers) against the plain reference's one pass over the whole
    sequence, on logits: prompts shorter than their bucket and one that
    fills it, one to four SSD chunks of 8, decode across chunk edges
    (the table holds 32 positions: prompt + 9 fits)."""
    t = tiny
    toks = np.random.default_rng(plen).integers(
        1, 256, plen + 9).astype(np.int32)
    kv, prefill, decode = _programs(t)
    got, _ = _serve_one(t, kv, prefill, decode, toks, plen, _table(1, 2),
                        bucket=bucket)
    np.testing.assert_allclose(got, _reference_logits(t, toks)[plen - 1:],
                               atol=ATOL)


def test_models_own_forward_equals_the_reference(tiny):
    t = tiny
    toks = np.random.default_rng(3).integers(1, 256, (2, 21)).astype(
        np.int32)
    got = t["model"].apply({"params": t["params"]}, jnp.asarray(toks),
                           train=False)
    h = t["ref"].hidden_states(t["ref_params"], toks, t["cfg"], "f32")
    want = t["ref"].logits_of(t["ref_params"], h, "f32")
    np.testing.assert_allclose(got, want, atol=ATOL)


def test_a_padded_bucket_serves_what_the_prompts_own_length_does(tiny):
    """The same prompt in a bucket it fills and in one twice as long (two
    more chunks of inert positions): the same state in its slot and the
    same logits after it."""
    t = tiny
    toks = np.random.default_rng(8).integers(1, 256, 22).astype(np.int32)
    kv, prefill, decode = _programs(t)
    snug, kv_snug = _serve_one(t, kv, prefill, decode, toks, 16,
                               _table(1, 1), bucket=16)
    kv, _, _ = _programs(t)
    wide, kv_wide = _serve_one(t, kv, prefill, decode, toks, 16,
                               _table(1, 1), bucket=32)
    np.testing.assert_allclose(wide, snug, atol=1e-6)
    for leaf in ("h", "conv"):
        np.testing.assert_allclose(kv_wide["state"][leaf][:, 1],
                                   kv_snug["state"][leaf][:, 1], atol=1e-6)


@pytest.mark.parametrize("dirty", ["slot_reused_after_finish",
                                   "trash_slot_written"])
def test_a_slot_leaves_no_trace_in_another_requests_logits(tiny, dirty):
    """A request's logits are bitwise what a fresh cache gives, whatever
    its slot held before (another request's finished state) and whatever
    inactive rows wrote to the trash slot meanwhile: the prefill writes
    the state from zero."""
    t = tiny
    rng = np.random.default_rng(11)
    toks = rng.integers(1, 256, 20).astype(np.int32)
    other = rng.integers(1, 256, 20).astype(np.int32)
    kv, prefill, decode = _programs(t)
    clean, _ = _serve_one(t, kv, prefill, decode, toks, 9, _table(1, 1))
    kv, _, _ = _programs(t)
    if dirty == "slot_reused_after_finish":
        _, kv = _serve_one(t, kv, prefill, decode, other, 12, _table(1, 1))
    else:
        # rows 0 and 2 inactive beside it: they name slot 0 and page 0
        _, kv = _serve_one(t, kv, prefill, decode, other, 12,
                           _table(9, 2), rows=4)
        assert float(jnp.abs(kv["state"]["h"][:, 0]).max()) == 0.0
    again, _ = _serve_one(t, kv, prefill, decode, toks, 9, _table(1, 1))
    np.testing.assert_array_equal(again, clean)


# ---------------------------------------------------------------------
# the mixer-generic hybrid programs against the parent's KDA-only ones


def _parent_prefill(family, table_width):
    """``serve/decode._build_hybrid_prefill_fn`` as PR 34 left it (the
    oracle of the refactor: KDA only, ``so.*`` called by name)."""
    from tpu_hc_bench.parallel.sequence import dense_attention

    m = family.model
    group = family.heads // family.kv_heads
    n = m.kda_heads * m.kda_head_dim
    kv_index = {l: i for i, l in enumerate(family.kv_layers)}
    st_index = {l: i for i, l in enumerate(family.state_layers)}

    def prefill(params, kv, tokens, length, table):
        k_pages, v_pages = kv["pages"]
        S, conv = kv["state"]["S"], kv["state"]["conv"]
        s = tokens.shape[1]
        slot = table[table_width]
        valid = jnp.arange(s) < length
        x = family.embed_prefill(params, tokens)
        new_k, new_v = {}, {}
        for l in range(family.num_layers):
            p_l = family.layer_params(params, l)
            u = family.attn_norm(p_l, x)
            if l in kv_index:
                q, k, v = so.gqa_inputs(p_l["mixer"], u, family.heads,
                                        family.kv_heads)
                new_k[l], new_v[l] = k[0], v[0]
                ctx = dense_attention(
                    q, jnp.repeat(k, group, axis=2),
                    jnp.repeat(v, group, axis=2), causal=True)
                x = x + so.gqa_output(p_l["mixer"], ctx, u)
            else:
                li = st_index[l]
                tail0 = jnp.zeros((1, m.conv_kernel - 1, 3 * n), u.dtype)
                q, k, v, g, beta, padded = so.kda_inputs(
                    p_l["mixer"], u, tail0, m.kda_heads, m.neg_eigval)
                g = jnp.where(valid[None, :, None, None], g, 0.0)
                beta = jnp.where(valid[None, :, None], beta, 0.0)
                o, s_end = so.kda_sequence(
                    q[0], k[0], v[0], g[0], beta[0],
                    jnp.zeros(S.shape[2:], jnp.float32))
                x = x + so.kda_output(p_l["mixer"], o[None], u, m.eps)
                S = jax.lax.dynamic_update_slice(
                    S, s_end[None, None], (li, slot, 0, 0, 0))
                tail = jax.lax.dynamic_slice_in_dim(
                    padded[0], length, m.conv_kernel - 1, axis=0)
                conv = jax.lax.dynamic_update_slice(
                    conv, tail[None, :, None].astype(conv.dtype),
                    (li, 0, slot, 0))
            y, _ = family.ffn(p_l, family.ffn_norm(p_l, x))
            x = x + y
        x_last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
        logits = family.head(params, x_last)[:, 0]
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        kn = jnp.stack([new_k[l] for l in family.kv_layers])
        vn = jnp.stack([new_v[l] for l in family.kv_layers])
        pages = (decode_mod._write_prompt_pages(k_pages, kn,
                                                table[:table_width], length),
                 decode_mod._write_prompt_pages(v_pages, vn,
                                                table[:table_width], length))
        return next_token, logits, {
            "pages": pages, "state": {"S": S, "conv": conv}}

    return prefill


def _parent_decode(family, table_width, page_size):
    """``serve/decode._build_hybrid_decode_fn`` as PR 34 left it, with
    ``build_decode_fn``'s page write of the gather arm."""
    m = family.model
    kv_index = {l: i for i, l in enumerate(family.kv_layers)}
    st_index = {l: i for i, l in enumerate(family.state_layers)}

    def scatter_new(kv, tables, lengths, active, kn, vn):
        rows = jnp.arange(lengths.shape[0])
        page_idx = jnp.where(
            active, tables[rows, jnp.clip(lengths // page_size, 0,
                                          table_width - 1)], 0)
        offset = lengths % page_size
        k_pages, v_pages = kv
        lanes = k_pages.shape[-1]
        return tuple(
            decode_mod._write_pool(pool, decode_mod._pool_rows(
                new, lanes)[:, :, :, None], page_idx, offset)
            for pool, new in ((k_pages, kn), (v_pages, vn)))

    def decode(params, kv, tokens, tables, lengths, active):
        k_pages, v_pages = kv["pages"]
        S, conv = kv["state"]["S"], kv["state"]["conv"]
        n_slots = S.shape[1]
        tabs = tables[:, :table_width]
        chunk = decode_mod.chunk_pages(k_pages, *tabs.shape)
        pairs = decode_mod._pack_pairs(tabs, lengths, active,
                                       k_pages.shape[3], chunk)
        slots = jnp.where(active, tables[:, table_width], 0)
        x = family.embed_decode(params, tokens, lengths)
        new_k, new_v = {}, {}
        held = jnp.zeros((), jnp.int32)

        def to_slots(rows):
            return jnp.zeros((n_slots,) + rows.shape[1:],
                             rows.dtype).at[slots].set(rows)

        for l in range(family.num_layers):
            p_l = family.layer_params(params, l)
            u = family.attn_norm(p_l, x)
            if l in kv_index:
                q, k, v = so.gqa_inputs(p_l["mixer"], u, family.heads,
                                        family.kv_heads)
                new_k[l], new_v[l] = k[:, 0], v[:, 0]
                q, pairs = jax.lax.optimization_barrier((q, pairs))
                ctx = decode_mod._attend_packed(
                    q[:, 0], k_pages, v_pages, kv_index[l], pairs,
                    k[:, 0], v[:, 0], chunk)
                x = x + so.gqa_output(p_l["mixer"], ctx[:, None], u)
            else:
                li = st_index[l]
                q, k, v, g, beta, padded = so.kda_inputs(
                    p_l["mixer"], u, jnp.swapaxes(conv[li][:, slots], 0, 1),
                    m.kda_heads, m.neg_eigval)
                g = jnp.where(active[:, None, None], g[:, 0], 0.0)
                beta = jnp.where(active[:, None], beta[:, 0], 0.0)
                s_l = jax.lax.dynamic_index_in_dim(S, li, 0, False)
                s_l, o = so.kda_step(
                    s_l, to_slots(q[:, 0]), to_slots(k[:, 0]),
                    to_slots(v[:, 0]), to_slots(g), to_slots(beta))
                S = jax.lax.dynamic_update_index_in_dim(S, s_l, li, 0)
                for t in range(m.conv_kernel - 1):
                    conv = conv.at[li, t, slots].set(
                        padded[:, 1 + t].astype(conv.dtype))
                x = x + so.kda_output(p_l["mixer"], o[slots][:, None], u,
                                      m.eps)
            y, picks = family.ffn(p_l, family.ffn_norm(p_l, x))
            x = x + y
            held = held + jnp.sum(jnp.where(active, picks[:, 0], 0))
        logits = family.head(params, x)[:, 0]
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        pages = scatter_new(
            kv["pages"], tabs, lengths, active,
            jnp.stack([new_k[l] for l in family.kv_layers]),
            jnp.stack([new_v[l] for l in family.kv_layers]))
        return (jnp.concatenate([next_tokens, held[None]]), logits,
                {"pages": pages, "state": {"S": S, "conv": conv}})

    return decode


def test_solar_programs_are_bitwise_the_parents():
    """Solar-tiny through the mixer-generic prefill and decode and through
    PR 34's KDA-only ones: the same tokens, logits, counter and cache,
    bit for bit, over two prefills and five decode steps of three rows
    (one inactive)."""
    model = so.solar_open2_tiny(dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(4), jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    fam = decode_mod.build_family(model)
    kv0 = decode_mod.init_kv_state(fam, 1 + 3 * WIDTH, PAGE, jnp.float32,
                                   slots=4)
    sides = {
        "change": (jax.jit(decode_mod.build_prefill_fn(fam, PAGE, WIDTH)),
                   jax.jit(decode_mod.build_decode_fn(fam, PAGE, WIDTH))),
        "parent": (jax.jit(_parent_prefill(fam, WIDTH)),
                   jax.jit(_parent_decode(fam, WIDTH, PAGE)))}
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 256, 16).astype(np.int32) for _ in range(2)]
    lens = [11, 7]
    outs = {}
    for side, (prefill, decode) in sides.items():
        kv, seen = kv0, []
        tables = np.zeros((3, WIDTH + 1), np.int32)
        for r, (p, n) in enumerate(zip(prompts, lens)):
            tables[r] = _table(1 + r * WIDTH, r + 1)
            tok, lg, kv = prefill(params, kv, p[None], np.int32(n),
                                  tables[r])
            seen += [tok, lg]
        lengths = np.array(lens + [0], np.int32)
        feed = np.array([3, 5, 0], np.int32)
        on = np.array([True, True, False])
        for _ in range(5):
            toks, lg, kv = decode(params, kv, feed, tables, lengths, on)
            seen += [toks, lg]
            feed = np.asarray(toks[:3]) * on
            lengths = lengths + on
        outs[side] = seen + jax.tree.leaves(kv)
    assert len(outs["change"]) == len(outs["parent"])
    for a, b in zip(outs["change"], outs["parent"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------
# the engine


def _cfg(**kw):
    from tpu_hc_bench import flags

    base = dict(model="granite4h_tiny", workload="serve",
                arrival_rate=1000.0, num_requests=8, max_prompt_len=16,
                max_output_len=6, max_in_flight=4, kv_page_size=4, seed=0)
    base.update(kw)
    return flags.BenchmarkConfig(**base).resolve()


def test_engine_serves_the_family_in_bfloat16_and_counts_its_pools():
    from tpu_hc_bench.serve import arrivals
    from tpu_hc_bench.serve import engine as engine_mod

    eng = engine_mod.ServeEngine(_cfg(use_fp16=True),
                                 print_fn=lambda m: None)
    kv = eng._kv
    assert set(kv) == {"pages", "state"} and set(kv["state"]) == {"h",
                                                                 "conv"}
    # pages for the ONE attention layer, cap + 1 slots for the three others
    assert kv["pages"][0].shape[0] == 1
    assert kv["pages"][0].dtype == jnp.bfloat16
    assert kv["state"]["h"].shape == (3, eng.cap + 1, 4, 32, 16)
    assert kv["state"]["h"].dtype == jnp.float32
    assert kv["state"]["conv"].shape == (3, 3, eng.cap + 1, 160)
    assert kv["state"]["conv"].dtype == jnp.bfloat16
    assert eng.params["layer_0_mixer"]["in_proj"].dtype == jnp.bfloat16
    assert eng.params["layer_0_mixer"]["A_log"].dtype == jnp.float32
    summary = eng.run(
        arrivals.build_requests(eng.cfg, eng.spec.vocab_size),
        clock=engine_mod.VirtualClock({"prefill": 0.004, "decode": 0.003}))
    assert summary["completed"] == 8
    assert summary["post_warmup_compiles"] == 0
    assert summary["state_pool_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(kv["state"]))
    assert 0 < summary["state_slots"] <= summary["state_slot_steps"]
    assert "moe_picks" not in summary
    assert {"ssm", "gqa", "mlp", "head"} == set(
        summary["op_parts"][f"decode@{eng.cap}"].values())
    # every decode program is counted; interpreted here, the kernel is no
    # custom call (what the chip's compiler makes of it:
    # tests/test_tpu_compile.py)
    assert summary["ssd_kernel_calls"] == {
        f"decode@{b}": 0 for b in eng.batch_buckets}


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
