"""Pallas flash attention vs the dense XLA reference.

Runs in Pallas interpreter mode on the CPU backend (ops.flash_attention
auto-detects).  Small block sizes force multi-block grids so the online
softmax accumulation and the padding/masking paths are all exercised.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_hc_bench.ops.flash_attention import flash_attention, tile_plan
from tpu_hc_bench.parallel import sequence as seq


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache_entries():
    """The interpreted kernels compile in about the second at which the
    suite's persistent cache starts keeping a program, so now and then
    one of them would be written to the shared directory while another
    worker's serve test counts that directory's entries
    (``post_warmup_compiles``): this file writes none."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _qkv(b=2, s=64, h=2, d=16, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_dense(causal):
    q, k, v = _qkv()
    ref = seq.dense_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("s", [24, 40])
def test_forward_unaligned_seq_pads(s):
    """Sequence lengths not divisible by the block: pad + mask path."""
    q, k, v = _qkv(s=s)
    ref = seq.dense_attention(q, k, v)
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_dense(causal):
    q, k, v = _qkv(b=1, s=32, h=2, d=8)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8)
        return jnp.sum(o * jnp.cos(o))        # non-trivial cotangent

    def loss_dense(q, k, v):
        o = seq.dense_attention(q, k, v, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), rtol=1e-4, atol=1e-4,
            err_msg=f"d{name} mismatch",
        )


def test_grads_unaligned_seq():
    """Padded rows/keys must contribute zero gradient."""
    q, k, v = _qkv(b=1, s=20, h=1, d=8)
    f = lambda fn: lambda *a: jnp.sum(fn(*a) ** 2)
    g_flash = jax.grad(f(lambda q, k, v: flash_attention(
        q, k, v, block_q=8, block_k=8)), argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(f(seq.dense_attention), argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=1e-4, atol=1e-4)


def test_bf16_forward():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    ref = seq.dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32))
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=0.05, atol=0.05)


def test_local_attention_flash_dispatch():
    q, k, v = _qkv(s=16)
    ref = seq.dense_attention(q, k, v)
    out = seq.local_attention(q, k, v, impl="flash")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_with_flash_inner(devices):
    """Flash as the local attention inside Ulysses sequence parallelism."""
    from jax.sharding import Mesh, PartitionSpec as P

    q, k, v = _qkv(s=32, h=4)
    ref = seq.dense_attention(q, k, v)
    mesh = Mesh(np.array(devices[:2]), (seq.SEQ_AXIS,))
    spec = P(None, seq.SEQ_AXIS)
    mapped = jax.jit(jax.shard_map(
        lambda q, k, v: seq.ulysses_attention(
            q, k, v, attn_fn=functools.partial(
                flash_attention, block_q=16, block_k=16)),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    ))
    np.testing.assert_allclose(np.asarray(mapped(q, k, v)), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_bert_flash_matches_dense():
    """Same params, both attention impls: identical logits."""
    from tpu_hc_bench.models.bert import bert_tiny_mlm

    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 1024)
    dense = bert_tiny_mlm()
    flash = bert_tiny_mlm(attention_impl="flash")
    params = dense.init(jax.random.PRNGKey(0), tokens, train=False)
    out_d = dense.apply(params, tokens, train=False)
    out_f = flash.apply(params, tokens, train=False)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the causal triangle inside a block (PR 34): sub-tiles wholly above the
# diagonal are never computed, those wholly below it build no mask
# ---------------------------------------------------------------------------

# (sq, sk, block, sub_tile, causal, dtype)
_TRIANGLE_CASES = [
    pytest.param(64, 64, 64, 16, True, jnp.float32, id="one-block-4x4"),
    pytest.param(96, 96, 32, 8, True, jnp.float32, id="grid-and-tile-skip"),
    pytest.param(50, 50, 64, 16, True, jnp.float32, id="sq-pads-in-block"),
    pytest.param(100, 100, 32, 8, True, jnp.float32, id="pads-last-block"),
    pytest.param(72, 40, 32, 8, True, jnp.float32, id="sq-longer-than-sk"),
    pytest.param(16, 64, 64, 16, True, jnp.float32, id="keys-past-queries"),
    pytest.param(32, 96, 32, 8, True, jnp.float32, id="key-blocks-past-q"),
    pytest.param(40, 72, 32, 8, False, jnp.float32, id="noncausal-sq-ne-sk"),
    pytest.param(64, 64, 64, 16, True, jnp.bfloat16, id="bf16"),
]


def _triangle_inputs(sq, sk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(sq + sk), 3)
    q = jax.random.normal(ks[0], (1, sq, 2, 8), dtype)
    k = jax.random.normal(ks[1], (1, sk, 2, 8), dtype)
    v = jax.random.normal(ks[2], (1, sk, 2, 8), dtype)
    return q, k, v


def _f32(xs):
    return tuple(x.astype(jnp.float32) for x in xs)


@pytest.mark.parametrize("sq,sk,block,sub,causal,dtype", _TRIANGLE_CASES)
def test_triangle_forward_matches_dense(sq, sk, block, sub, causal, dtype):
    q, k, v = _triangle_inputs(sq, sk, dtype)
    tol = 1e-5 if dtype == jnp.float32 else 0.05
    ref = seq.dense_attention(*_f32((q, k, v)), causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=block,
                          block_k=block, sub_tile=sub)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("sq,sk,block,sub,causal,dtype", _TRIANGLE_CASES)
def test_triangle_grads_match_dense(sq, sk, block, sub, causal, dtype):
    q, k, v = _triangle_inputs(sq, sk, dtype)
    tol = 1e-4 if dtype == jnp.float32 else 0.1

    def loss(fn):
        def f(q, k, v):
            o = fn(q, k, v).astype(jnp.float32)
            return jnp.sum(o * jnp.cos(o))
        return f

    g_flash = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=block, block_k=block,
        sub_tile=sub)), argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss(lambda q, k, v: seq.dense_attention(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(*_f32((q, k, v)))
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        assert gf.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(gf, np.float32), np.asarray(gd), rtol=tol, atol=tol,
            err_msg=f"d{name} mismatch")


def _count_by_elements(plan):
    """(computed, masked) sub-tiles of the padded rectangle, element by
    element: computed = holds a visible element, masked = and a hidden
    one."""
    computed = masked = 0
    for q0 in range(0, plan.sq_p, plan.sub_q):
        for k0 in range(0, plan.sk_p, plan.sub_k):
            qs = np.arange(q0, q0 + plan.sub_q)[:, None]
            ks = np.arange(k0, k0 + plan.sub_k)[None]
            vis = qs >= ks if plan.causal else np.ones(
                (plan.sub_q, plan.sub_k), bool)
            computed += bool(vis.any())
            masked += bool(vis.any() and not vis.all())
    return computed, masked


@pytest.mark.parametrize("sq,sk,bq,bk,sub,causal", [
    (64, 64, 64, 64, 16, True), (96, 96, 32, 32, 8, True),
    (50, 50, 64, 64, 16, True), (72, 40, 16, 32, 8, True),
    (96, 96, 32, 64, 16, True), (96, 96, 64, 32, 16, True),
    (100, 40, 32, 16, None, True), (40, 72, 32, 32, 8, False),
])
def test_tile_plan_counts_what_holds_a_visible_element(sq, sk, bq, bk, sub,
                                                       causal):
    """The three kernels' counts (two span rules: by key for the forward
    and ``bwd_dq``, by query for ``bwd_dkv``) against the mask itself."""
    plan = tile_plan(sq, sk, bq, bk, causal, sub_tile=sub)
    want = _count_by_elements(plan)
    assert plan.fwd == plan.bwd_dq == plan.bwd_dkv == want
    assert plan.rect == (plan.sq_p // plan.sub_q) * (plan.sk_p // plan.sub_k)


def test_tile_plan_train_cell_shape_computes_the_triangle():
    """Seq 1,024 at the default blocks is one grid step a head: the skip
    has to happen inside it, and only the diagonal's sub-tiles mask."""
    plan = tile_plan(1024, 1024, causal=True)
    assert (plan.block_q, plan.block_k) == (1024, 1024)
    assert plan.rect == 16 and plan.fwd[0] < 12
    diagonal = plan.block_q // plan.sub_q
    for computed, masked in (plan.fwd, plan.bwd_dq, plan.bwd_dkv):
        assert computed == plan.fwd[0] and masked == diagonal
    assert plan.computed_share == plan.fwd[0] / plan.rect


@pytest.mark.parametrize("sq,sk", [(1024, 1024), (512, 512), (2048, 1024),
                                   (1000, 1000)])
def test_tile_plan_noncausal_is_one_tile_a_block(sq, sk):
    """``causal=False`` does the work it did before PR 34: every block of
    the grid, each as one tile, none with a causal mask."""
    plan = tile_plan(sq, sk, causal=False)
    assert (plan.sub_q, plan.sub_k) == (plan.block_q, plan.block_k)
    assert (plan.block_q, plan.block_k) == (min(1024, sq), min(1024, sk))
    blocks = (plan.sq_p // plan.block_q) * (plan.sk_p // plan.block_k)
    assert plan.fwd == plan.bwd_dq == plan.bwd_dkv == (blocks, 0)
    assert plan.rect == blocks


@pytest.mark.parametrize("s", [2048, 8192])
def test_tile_plan_long_sequences_compute_no_more_than_the_grid_skip(s):
    """Before PR 34 a 1024x1024 block ran whole iff ``(i + 1) * 1024 >
    j * 1024``; the plan may compute no more elements than that."""
    plan = tile_plan(s, s, causal=True)
    n = s // 1024
    before = sum(1 for i in range(n) for j in range(n) if i + 1 > j)
    for computed, _ in (plan.fwd, plan.bwd_dq, plan.bwd_dkv):
        assert computed * plan.sub_q * plan.sub_k <= before * 1024 * 1024
    assert plan.fwd[0] * plan.sub_q * plan.sub_k < 0.6 * s * s


def test_tile_plan_wide_heads_keep_their_key_block_clamp():
    plan = tile_plan(2048, 2048, causal=True, head_dim=256)
    assert plan.block_k == 512 and plan.block_q == 1024


def test_pallas_calls_keep_flash_attention_in_their_names():
    """The benchmark's roofline reader finds the kernel's events in the
    device trace by ``benchmarks/harness/readers.py::FLASH_KERNEL``; a
    renamed call makes ``kernel.flash_attention_roofline`` read nothing."""
    import re

    q, k, v = _qkv(b=1, s=32, h=1, d=8)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    names = re.findall(r"\bname=(\w+)", str(jaxpr))
    calls = [n for n in names if "flash" in n or "Attention" in n]
    assert sorted(set(calls)) == [
        "flash_attention_bwd_dkv", "flash_attention_bwd_dq",
        "flash_attention_fwd"], names
    assert all(re.search(r"MultiHeadAttention|flash_attention", n)
               for n in calls)


@pytest.mark.parametrize("model,impl,want", [
    ("gpt2_medium", "flash", (10, 16, 4)),    # the train cells' shape
    ("bert_tiny", "flash", (1, 1, 0)),        # not causal: one tile a block
    ("gpt2_medium", "dense", None),           # no flash kernel runs
])
def test_train_driver_reads_the_plan_of_the_shape_it_runs(model, impl, want):
    """The run header's ``flash tiles:`` line and the result's
    ``flash_tile_share`` come from ``tile_plan`` at the model's own
    sequence length and head width."""
    from tpu_hc_bench import flags
    from tpu_hc_bench.models import create_model
    from tpu_hc_bench.train import driver

    cfg = flags.BenchmarkConfig(model=model, attention_impl=impl)
    net, spec = create_model(model, attention_impl=impl)
    plan = driver._flash_tile_plan(cfg, net, spec)
    if want is None:
        assert plan is None
    else:
        assert (plan.fwd[0], plan.rect, plan.fwd[1]) == want
        assert plan.computed_share == want[0] / want[1]


# ---------------------------------------------------------------------------
# the sliding window (forward only: the serve lane's prefill)


@pytest.mark.parametrize("s,window,block,sub", [
    (37, 8, 16, 8),       # window under a block, several blocks
    (70, 20, 32, 8),      # window crosses one block edge
    (70, 5, 16, 8),       # window under a sub-tile
    (100, 64, 32, 16),    # window over a block: a band of three
    (50, 100, 16, 8),     # window past the sequence: the causal square
    (33, 1, 16, 8),       # a query sees itself alone
    (300, 40, 1024, None),  # the default blocks: one block, 256 sub-tiles
])
def test_window_forward_matches_masked_dense(s, window, block, sub):
    q, k, v = _qkv(b=2, s=s, h=3, d=16, seed=s)
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=block, block_k=block, sub_tile=sub)
    np.testing.assert_allclose(
        got, seq.dense_attention(q, k, v, causal=True, window=window),
        atol=2e-6, rtol=2e-6)


def _count_band(plan):
    """(computed, masked) sub-tiles of the band, element by element."""
    computed = masked = 0
    for q0 in range(0, plan.sq_p, plan.sub_q):
        for k0 in range(0, plan.sk_p, plan.sub_k):
            qs = np.arange(q0, q0 + plan.sub_q)[:, None]
            ks = np.arange(k0, k0 + plan.sub_k)[None]
            vis = (qs >= ks) & (ks > qs - plan.window)
            computed += bool(vis.any())
            masked += bool(vis.any() and not vis.all())
    return computed, masked


@pytest.mark.parametrize("s,window,block,sub", [
    (37, 8, 16, 8), (70, 20, 32, 8), (100, 64, 32, 16), (50, 100, 16, 8),
    (8192, 1024, 1024, 256), (4096, 1024, 1024, 256), (512, 1024, 1024, 256),
])
def test_window_plan_computes_only_the_band(s, window, block, sub):
    """The windowed forward's count against the mask itself: no sub-tile
    wholly below the band is computed, and the grid is the band."""
    plan = tile_plan(s, s, block, block, True, sub_tile=sub, window=window)
    assert plan.fwd == _count_band(plan)
    n = plan.sq_p // plan.block_q
    assert plan.band == min(n, -(-(window - 1) // plan.block_k) + 1)
    with pytest.raises(ValueError, match="forward only"):
        plan.bwd_dkv


def test_window_none_leaves_the_train_cells_plan_as_it_was():
    """``tile_plan`` as the parent computed it at the train cells' shape
    and at longer ones (read off the tree before the window argument)."""
    for (s, d), fields, counts, offsets in [
            ((1024, 64), (1024, 1024, 256, 256, 1024, 1024, True),
             (10, 4), [0]),
            ((4096, 64), (1024, 1024, 256, 256, 4096, 4096, True),
             (136, 16), [0, 1023]),
            ((8192, 128), (1024, 1024, 256, 256, 8192, 8192, True),
             (528, 32), [0, 1023])]:
        plan = tile_plan(s, s, causal=True, head_dim=d)
        assert tuple(plan)[:7] == fields and plan.window is None
        assert plan.fwd == plan.bwd_dq == plan.bwd_dkv == counts
        assert sorted(set(plan.block_offsets())) == offsets


def test_window_kernel_has_a_name_of_its_own():
    """The benchmark's ``kernel.flash_window_roofline`` finds the windowed
    forward's events by name; the causal kernels keep theirs."""
    import re

    q, k, v = _qkv(b=1, s=32, h=1, d=8)
    jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=8, block_q=16, block_k=16,
        sub_tile=8))(q, k, v)
    names = set(re.findall(r"\bname=(\w+)", str(jaxpr)))
    assert "flash_window_fwd" in names
    assert not names & {"flash_attention_fwd", "flash_attention_bwd_dq"}


@pytest.mark.parametrize("kw", [dict(causal=False, window=4),
                                dict(causal=True, window=0)])
def test_window_refuses_what_it_cannot_mask(kw):
    q, k, v = _qkv(b=1, s=16, h=1, d=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, **kw)
