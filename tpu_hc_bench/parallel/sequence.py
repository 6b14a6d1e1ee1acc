"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference's workload has no sequence dimension (CNNs only, SURVEY.md
§2c), but its *scale story* — one capability axis per fabric hop — maps on
TPU to sharding the sequence dimension of transformer attention over a mesh
axis, so contexts longer than one chip's HBM can be trained.  Two standard
TPU-native strategies, both composing with the data-parallel axis:

- **Ring attention** (blockwise, ``jax.lax.ppermute``): K/V shards rotate
  around the ring while each device accumulates its queries' attention with
  a numerically-stable online softmax.  Communication is neighbor-to-
  neighbor over ICI and overlaps with the per-block matmuls; memory is
  O(local_seq^2) per step instead of O(global_seq^2).
- **Ulysses** (all-to-all): one ``all_to_all`` re-shards activations from
  sequence-sharded to head-sharded, attention runs locally over the full
  sequence with ``heads/axis_size`` heads, and a second ``all_to_all``
  restores sequence sharding.  Cheaper at moderate context, requires
  ``heads % axis_size == 0``.

Both are called *inside* a ``jax.shard_map`` where ``axis_name`` is bound
and q/k/v carry the local sequence shard: ``[batch, local_seq, heads,
head_dim]``.  Outputs have the same layout.  Softmax statistics accumulate
in float32 regardless of input dtype (bf16-safe).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_hc_bench.topology import SEQ_AXIS

_NEG_INF = -1e30  # mask value: large-negative, not -inf (keeps exp() clean)


def dense_attention(q, k, v, causal: bool = False, scale: float | None = None,
                    q_offset: int | jax.Array = 0,
                    k_offset: int | jax.Array = 0,
                    window: int | None = None):
    """Plain softmax attention — the single-device reference implementation.

    ``q``/``k``/``v``: [batch, seq, heads, head_dim].  ``q_offset``/
    ``k_offset`` are the global positions of the first query/key row (used
    for causal masking of sequence shards).  ``window`` (causal only): a
    query at ``i`` sees keys ``i - window < j <= i``.
    """
    d = q.shape[-1]
    scale = (1.0 / d ** 0.5) if scale is None else scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = k_offset + jnp.arange(k.shape[1])
        seen = qpos[:, None] >= kpos[None, :]
        if window is not None:
            seen = seen & (qpos[:, None] - kpos[None, :] < window)
        s = jnp.where(seen, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def ring_attention(q, k, v, axis_name: str = SEQ_AXIS, causal: bool = False,
                   scale: float | None = None, kv_repeat: int = 1):
    """Blockwise ring attention over a sequence-sharded mesh axis.

    Must run inside ``shard_map`` with ``axis_name`` bound; q/k/v are the
    local sequence shards ``[batch, local_seq, heads, head_dim]``.  K/V
    travel the ring via ``ppermute`` (ICI neighbor hops); each of the
    ``axis_size`` steps folds one K/V block into the online-softmax
    accumulator (running max ``m``, normalizer ``l``, weighted sum ``o`` —
    all float32).  Equivalent to dense attention over the global sequence.

    ``kv_repeat > 1`` (GQA): k/v carry ``heads / kv_repeat`` KV heads and
    are broadcast up to the query-head count *inside each fold* — the
    ring only ever moves the un-repeated KV bytes.
    """
    from tpu_hc_bench.parallel.collectives import ppermute_ring

    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    scale = (1.0 / d ** 0.5) if scale is None else scale

    qpos = my * lq + jnp.arange(lq)                       # global query rows

    def fold(carry, k_blk, v_blk, src):
        if kv_repeat > 1:
            # block-local broadcast: no extra ring traffic
            k_blk = jnp.repeat(k_blk, kv_repeat, axis=2)
            v_blk = jnp.repeat(v_blk, kv_repeat, axis=2)
        m, l, o = carry
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = src * lk + jnp.arange(lk)
            visible = qpos[:, None] >= kpos[None, :]
            s = jnp.where(visible, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        if causal:
            # fully-masked rows still have m == _NEG_INF: force weights to 0
            p = jnp.where(visible, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        o = (o * corr.transpose(0, 2, 1)[..., None]
             + jnp.einsum("bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32)))
        return m_new, l, o

    m0 = jnp.full((b, h, lq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, lq), jnp.float32)
    o0 = jnp.zeros((b, lq, h, d), jnp.float32)

    # fold the local block first, then n-1 ring rotations (no wasted hop)
    carry0 = fold((m0, l0, o0), k, v, my)

    def body(t, carry):
        k_blk, v_blk, acc = carry
        k_blk = ppermute_ring(k_blk, axis_name)
        v_blk = ppermute_ring(v_blk, axis_name)
        acc = fold(acc, k_blk, v_blk, (my - t) % n)
        return k_blk, v_blk, acc

    _, _, (m, l, o) = jax.lax.fori_loop(1, n, body, (k, v, carry0))
    l = jnp.maximum(l, 1e-30)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str = SEQ_AXIS,
                      causal: bool = False, scale: float | None = None,
                      attn_fn=None, kv_repeat: int = 1):
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism.

    Re-shards [batch, local_seq, heads, head_dim] -> [batch, global_seq,
    local_heads, head_dim] with one ``all_to_all``, runs full-sequence
    attention on the local head group, then reverses the exchange.  Needs
    ``heads % axis_size == 0``.  ``attn_fn(q, k, v, causal=..., scale=...)``
    (always called with those keywords forwarded) overrides the local
    attention (e.g. a Pallas flash kernel); default is ``dense_attention``.

    ``kv_repeat > 1`` (GQA): k/v carry ``heads / kv_repeat`` KV heads and
    are exchanged un-repeated (needs ``kv_heads % axis_size == 0`` too),
    then broadcast to the local query-head count after the reshard — the
    all_to_all only ever moves the un-repeated KV bytes.
    """
    n = jax.lax.axis_size(axis_name)
    h = q.shape[2]
    h_kv = k.shape[2]
    if h % n:
        raise ValueError(f"heads={h} not divisible by axis size {n}")
    if kv_repeat > 1 and h_kv % n:
        raise ValueError(
            f"kv heads={h_kv} not divisible by axis size {n}")

    def seq_to_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    if kv_repeat > 1:
        qg = seq_to_heads(q)
        kg = jnp.repeat(seq_to_heads(k), kv_repeat, axis=2)
        vg = jnp.repeat(seq_to_heads(v), kv_repeat, axis=2)
    else:
        # one stacked exchange for q/k/v instead of three collective
        # launches (split/concat shifted by 1 for the leading stack dim)
        qg, kg, vg = jax.lax.all_to_all(
            jnp.stack((q, k, v)), axis_name, split_axis=3, concat_axis=2,
            tiled=True)
    if attn_fn is None:
        attn_fn = dense_attention
    out = attn_fn(qg, kg, vg, causal=causal, scale=scale)
    return heads_to_seq(out)


_IMPLS = {"dense", "flash", "ring", "ulysses", "ulysses_flash"}


def local_attention(q, k, v, impl: str = "dense",
                    axis_name: str | None = None, causal: bool = False,
                    scale: float | None = None, kv_repeat: int = 1):
    """Dispatch: the one attention entry point model code calls.

    ``impl='dense'``/``'flash'`` ignore ``axis_name`` (each shard attends
    locally — only correct unsharded); ``ring``/``ulysses``/
    ``ulysses_flash`` require ``axis_name``.  ``flash`` is the Pallas
    blocked-softmax kernel (``ops.flash_attention``); ``dense`` is the
    XLA-compiled reference; ``ulysses_flash`` composes the all-to-all
    sequence resharding with the flash kernel for the full-sequence local
    attention — the long-context production combination (O(S) memory from
    flash x S-scaling from the seq axis).

    ``kv_repeat > 1`` (GQA): k/v arrive with ``heads / kv_repeat`` KV
    heads.  The single-device impls broadcast them up front (pure compute
    reshape); the sequence-parallel impls move the un-repeated KV bytes
    over the fabric and broadcast after/inside the collective.
    """
    if impl not in _IMPLS:
        raise ValueError(
            f"unknown attention impl {impl!r}; have {sorted(_IMPLS)}"
        )
    if impl in ("dense", "flash") and kv_repeat > 1:
        k = jnp.repeat(k, kv_repeat, axis=2)
        v = jnp.repeat(v, kv_repeat, axis=2)
    if impl == "dense":
        return dense_attention(q, k, v, causal=causal, scale=scale)
    if impl == "flash":
        from tpu_hc_bench.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal, scale=scale)
    if axis_name is None:
        raise ValueError(f"impl={impl!r} requires axis_name (a bound mesh axis)")
    if impl == "ring":
        return ring_attention(q, k, v, axis_name, causal=causal, scale=scale,
                              kv_repeat=kv_repeat)
    if impl == "ulysses_flash":
        from tpu_hc_bench.ops.flash_attention import flash_attention

        return ulysses_attention(q, k, v, axis_name, causal=causal,
                                 scale=scale, attn_fn=flash_attention,
                                 kv_repeat=kv_repeat)
    assert impl == "ulysses", impl   # _IMPLS membership checked above
    return ulysses_attention(q, k, v, axis_name, causal=causal, scale=scale,
                             kv_repeat=kv_repeat)
