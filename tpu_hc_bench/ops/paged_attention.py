"""Paged single-query decode attention as a Pallas TPU kernel.

The serving lane's decode step attends one fresh query token per
request over that request's KV cache, which lives in a shared *paged*
pool (``serve.decode``: ``[layers, kv_heads, pages, page_size, lanes]``
plus an int32 page table per request; ``lanes`` is ``head_dim`` padded
to the 128-lane tile, so one (page, kv head) is a contiguous
tile-aligned ``(page_size, lanes)`` slab — the only slice shape Mosaic
accepts for an HBM->VMEM page copy).  The round-16
reference path gathers every request's pages into a dense
worst-case-length ``[b, S, heads, d]`` temporary and runs a plain
softmax — the single hottest per-token cost in the lane, and all of it
HBM traffic for buffers that never needed to exist.

This kernel is the PagedAttention/flash-decode analog:

- The page *tables* ride the grid as scalar-prefetch operands; the
  K/V pools stay in ``ANY`` memory (HBM) and each grid step DMAs
  exactly the pages its table slots name into VMEM scratch — no dense
  gather, no per-layer pool slice, nothing pool-sized is ever copied.
  The per-page copies are all started before the first wait, so the
  fetches overlap each other (a revolving next-block prefetch is the
  deferred follow-up).
- The softmax is the same online recurrence as ``ops.flash_attention``:

    m' = max(m, rowmax(S_blk));  l' = l*e^(m-m') + rowsum(e^(S_blk-m'))
    acc' = acc*e^(m-m') + e^(S_blk - m') @ V_blk

Grid is (batch, kv_heads, page_blocks): batch and heads are
embarrassingly parallel, the page-block dim carries the recurrence.
``pages_per_block`` is the kernel's block-size lever (how many pages —
``pages_per_block * page_size`` tokens — each grid step streams through
VMEM); together with ``--kv_page_size`` it is autotuned like any other
lever (``tune.space.SERVE_LEVERS``).  GQA folds ``heads/kv_heads``
query heads into each program's row block, and only the program's own
kv head's slice of each page is fetched.

**Int8 KV** (``--quant=int8_kv``): the pool may be int8 with one f32
scale per (layer, page), written at prefill/append time
(``serve.decode``).  The layer's row of scales rides the
scalar-prefetch channel (SMEM holds ``[pages]``, not ``[layers,
pages]``) and the dequantize happens *inside* the kernel, fused with the score/value
matmuls — never a dense ``astype`` of the cache in the layer loop (the
``dequantize-in-hot-loop`` lint exists to keep it that way).

Accumulation is always float32.  On the CPU backend the kernel runs in
Pallas interpreter mode (``ops._pallas.interpret``), which is how the
parity tests pin it against the gather reference on the CPU mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_hc_bench.ops._pallas import interpret as _interpret

_NEG_INF = -1e30

_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def _kernel(tables_ref, lengths_ref, k_scales_ref, v_scales_ref,
            q_ref, k_pool, v_pool, o_ref, lse_ref,
            k_buf, v_buf, m_ref, l_ref, acc_ref, sem, *,
            scale, page_size, pages_per_block, quantized, layer):
    """One (batch row, kv head, page block) program.

    The block's pages are consecutive *table slots* (the physical
    pages they map to are arbitrary — each slot is DMA'd from the
    ``ANY``-space pool into ``k_buf``/``v_buf`` scratch), so the
    block's token positions are contiguous and masking is the usual
    ``kpos < length`` test.
    """
    ppb = pages_per_block
    b, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)
    bk = ppb * page_size
    length = lengths_ref[b]

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def copies():
        out = []
        for i in range(ppb):
            page = tables_ref[b, j * ppb + i]
            rows = pl.ds(i * page_size, page_size)
            out.append(pltpu.make_async_copy(
                k_pool.at[layer, h, page],
                k_buf.at[rows, :], sem.at[0, i]))
            out.append(pltpu.make_async_copy(
                v_pool.at[layer, h, page],
                v_buf.at[rows, :], sem.at[1, i]))
        return out

    def block_body():
        # start every page fetch of the block before the first wait,
        # so the DMAs overlap each other
        for cp in copies():
            cp.start()
        for cp in copies():
            cp.wait()
        if quantized:
            ks, vs = [], []
            for i in range(ppb):
                page = tables_ref[b, j * ppb + i]
                rows = pl.ds(i * page_size, page_size)
                ks.append(k_buf[rows, :].astype(jnp.float32)
                          * k_scales_ref[page])
                vs.append(v_buf[rows, :].astype(jnp.float32)
                          * v_scales_ref[page])
            k = ks[0] if ppb == 1 else jnp.concatenate(ks, axis=0)
            v = vs[0] if ppb == 1 else jnp.concatenate(vs, axis=0)
        else:
            k = k_buf[...]
            v = v_buf[...]
        q = q_ref[0, 0]                                # [group, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                      # [group, bk] f32
        kpos = j * bk + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        visible = kpos < length
        s = jnp.where(visible, s, _NEG_INF)
        m_old = m_ref[:]                               # [group, 1]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        # fully-masked blocks keep m == _NEG_INF; exp(s-m)=1 there, so
        # re-mask (the flash_attention forward's exact discipline)
        p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_old - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        acc_ref[:] = acc_ref[:] * corr + jnp.dot(
            p if quantized else p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )

    # blocks entirely past the row's cache depth contribute nothing:
    # skip the fetches and both matmuls
    pl.when(j * bk < length)(block_body)

    @pl.when(j == nj - 1)
    def _():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:] + jnp.log(l)


def paged_decode_attention(q, k_pages, v_pages, tables, lengths,
                           scale: float | None = None,
                           pages_per_block: int = 1,
                           k_scales=None, v_scales=None,
                           layer: int = 0,
                           return_lse: bool = False):
    """Single-query attention over a paged KV pool, no dense gather.

    Args:
      q: ``[b, heads, head_dim]`` — one query token per request row.
      k_pages, v_pages: ``[layers, kv_heads, pages, page_size,
        lanes]`` pool, ``lanes >= head_dim`` (a 4-D single-layer pool
        is accepted too).  Lanes past ``head_dim`` must hold zeros
        (``serve.decode`` pads to the 128-lane tile; q is zero-padded
        to match and the output sliced back).  Passing the WHOLE pool
        with a static ``layer`` index matters: the pool stays an
        ``ANY``-space operand the kernel DMAs pages out of — a
        ``k_pages[l]`` slice at the call site would materialize a
        per-layer pool copy as a temp.  f32/bf16, or int8 with
        ``*_scales``.
      tables: ``[b, w]`` int32 page tables (slot t holds tokens
        ``t*page_size..``); every slot must hold a valid pool index
        (the serving engine's trash page 0 covers unused slots).
      lengths: ``[b]`` int32 — valid tokens per row, *including* any
        token already appended at position ``lengths-1``.
      scale: score scale; default ``1/sqrt(head_dim)``.
      pages_per_block: pages per grid step (the block-size lever);
        table width is padded to a multiple (pad slots -> page 0).
      k_scales, v_scales: ``[layers, pages]`` f32 per-page dequant
        scales (``[pages]`` for a 4-D pool), required iff int8.
      layer: static layer index into the pool's leading dim.
      return_lse: also return the per-row logsumexp of the scores —
        lets the caller merge tokens *not yet in the pool* (the decode
        step's freshly computed K/V) into the online softmax without a
        second pass.
    Returns:
      ``[b, heads, head_dim]`` in q's dtype; with ``return_lse``, a
      ``(out, lse [b, heads] f32)`` pair.
    """
    if k_pages.ndim == 4:
        k_pages, v_pages = k_pages[None], v_pages[None]
        if k_scales is not None:
            k_scales, v_scales = k_scales[None], v_scales[None]
        layer = 0
    b, heads, d = q.shape
    _, kv_heads, pages, page_size, lanes = k_pages.shape
    if lanes < d:
        raise ValueError(f"pool lanes={lanes} narrower than "
                         f"head_dim={d}")
    layer = int(layer)
    w = tables.shape[1]
    if heads % kv_heads:
        raise ValueError(f"heads={heads} not a multiple of "
                         f"kv_heads={kv_heads}")
    group = heads // kv_heads
    quantized = k_pages.dtype == jnp.int8
    if quantized and (k_scales is None or v_scales is None):
        raise ValueError("int8 KV pool needs k_scales/v_scales "
                         "([layers, pages] f32 per-page scales)")
    scale = (1.0 / d ** 0.5) if scale is None else float(scale)
    ppb = max(1, min(int(pages_per_block), w))
    if w % ppb:
        pad = ppb - w % ppb
        tables = jnp.pad(tables, ((0, 0), (0, pad)))    # pad slots -> 0
        w += pad
    nb = w // ppb

    # zero lanes add nothing to q.k, and the pool's zero lanes leave
    # the output's pad lanes zero: slice them off after the call
    qg = jnp.pad(q, ((0, 0), (0, 0), (0, lanes - d))).reshape(
        b, kv_heads, group, lanes)
    if quantized:
        # only this layer's row rides SMEM
        k_scales, v_scales = k_scales[layer], v_scales[layer]
    else:
        # dummy f32 scales keep ONE kernel signature; never read
        k_scales = v_scales = jnp.ones((1,), jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, kv_heads, nb),
        in_specs=[
            pl.BlockSpec((1, 1, group, lanes),
                         lambda b_, h, j, tbl, ln, ks, vs: (b_, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),          # k pool (HBM)
            pl.BlockSpec(memory_space=pl.ANY),          # v pool (HBM)
        ],
        out_specs=[
            pl.BlockSpec((1, 1, group, lanes),
                         lambda b_, h, j, tbl, ln, ks, vs: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, group, 1),
                         lambda b_, h, j, tbl, ln, ks, vs: (b_, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((ppb * page_size, lanes), k_pages.dtype),  # k block
            pltpu.VMEM((ppb * page_size, lanes), v_pages.dtype),  # v block
            pltpu.VMEM((group, 1), jnp.float32),       # running max
            pltpu.VMEM((group, 1), jnp.float32),       # running sum
            pltpu.VMEM((group, lanes), jnp.float32),   # output acc
            pltpu.SemaphoreType.DMA((2, ppb)),         # k/v page fetches
        ],
    )
    kernel = functools.partial(
        _kernel, scale=scale, page_size=page_size,
        pages_per_block=ppb, quantized=quantized, layer=layer)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kv_heads, group, lanes), q.dtype),
            jax.ShapeDtypeStruct((b, kv_heads, group, 1), jnp.float32),
        ],
        interpret=_interpret(),
        compiler_params=_PARAMS,
        name="paged_attention",
    )(tables, lengths, k_scales, v_scales, qg, k_pages, v_pages)
    out = out.reshape(b, heads, lanes)[..., :d]
    if return_lse:
        return out, lse.reshape(b, heads)
    return out
