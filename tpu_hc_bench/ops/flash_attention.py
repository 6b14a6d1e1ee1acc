"""Flash attention (blocked online-softmax) as a Pallas TPU kernel.

The reference's compute engine delegates its hot kernels to MKL-DNN
(SURVEY.md §2b #21); the TPU-native analog is XLA plus Pallas where manual
blocking beats the compiler.  Attention is the canonical case: the naive
``softmax(QK^T)V`` materializes an [S, S] score matrix in HBM per head,
while this kernel streams K/V blocks through VMEM with the online-softmax
recurrence, so scores never leave the chip:

    m' = max(m, rowmax(S_blk));   l' = l*e^(m-m') + rowsum(e^(S_blk - m'))
    acc' = acc*e^(m-m') + e^(S_blk - m') @ V_blk

The backward pass (custom VJP) recomputes probabilities blockwise from the
saved per-row logsumexp — the standard flash-attention backward:

    D_i  = rowsum(dO_i * O_i)
    P    = exp(S - lse)
    dV  += P^T dO;   dS = P * (dO V^T - D);   dQ += dS K;   dK += dS^T Q

Accumulation is always float32 regardless of input dtype (bf16-safe).  On
the CPU backend the kernels run in Pallas interpreter mode, which is how
the unit tests exercise them on the virtual CPU mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_hc_bench.ops._pallas import interpret as _interpret
from tpu_hc_bench.ops._pallas import pad_up as _pad_up

# Default blocks: 1024x1024, confirmed by a round-2 back-to-back A/B
# inside the FULL gpt2 train step (162.0 ms vs 175.8 ms for 512x512 at
# seq 1024 bs 16 — +8.5%).  NOTE the *isolated-kernel* microbench says
# the opposite (512x512 wins by 10-13% when the attention grad runs
# alone): in context the rest of the layer competes for VMEM and the
# scheduler hides the big tiles' latency, so only whole-model A/Bs are
# trusted for this knob.  Working set at d=64 is ~9 MB of VMEM (f32
# score/prob tiles dominate); callers with head_dim > 128 get block_k
# halved below.  Overridable per call for small test shapes.
_BLOCK_Q = 1024
_BLOCK_K = 1024
_NEG_INF = -1e30


# batch*heads and the outer block dim are embarrassingly parallel; only the
# innermost (accumulating) grid dim carries loop state
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def _mask(i, j, bq, bk, seq_k, causal):
    """[bq, bk] bool: key in-range (< seq_k) and causally visible."""
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = kpos < seq_k
    if causal:
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        m = jnp.logical_and(m, qpos >= kpos)
    return m


def _tile_live(i, j, bq, bk):
    """Scalar bool: causal tile (i, j) has at least one visible element.

    A tile is fully above the diagonal — every qpos < kpos — iff its max
    qpos ((i+1)*bq - 1) is below its min kpos (j*bk).  Skipping those
    tiles halves the work at long sequence lengths; the K/V block DMAs
    still run (rectangular grid), but both MXU matmuls are elided."""
    return (i + 1) * bq > j * bk


# ---------------------------------------------------------------------------
# forward: grid (batch*heads, q_blocks, k_blocks), k innermost
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale, causal, seq_k):
    i, j = pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def tile_body():
        # matmuls run in the input dtype (bf16 native on the MXU), f32 accum
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                      # [BQ, BK] f32
        visible = _mask(i, j, *s.shape, seq_k, causal)
        s = jnp.where(visible, s, _NEG_INF)

        m_old = m_ref[:]                               # [BQ, 1]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        # fully-masked rows keep m == _NEG_INF; exp(s-m)=1 there, so re-mask
        p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_old - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        acc_ref[:] = acc_ref[:] * corr + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(_tile_live(i, j, q_ref.shape[1], k_ref.shape[1]))(tile_body)
    else:
        tile_body()

    @pl.when(j == nj - 1)
    def _():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l)


def _fwd_call(q, k, v, scale, causal, seq_k, block_q, block_k):
    bh, sq, d = q.shape
    sk = k.shape[1]
    grid = (bh, sq // block_q, sk // block_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, seq_k=seq_k
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),       # o
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),   # lse residual
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),            # running max
            pltpu.VMEM((block_q, 1), jnp.float32),            # running sum
            pltpu.VMEM((block_q, d), jnp.float32),            # output acc
        ],
        interpret=_interpret(),
        compiler_params=_PARAMS,
        name="flash_attention_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: dq over (bh, i, j) with j innermost; dk/dv over (bh, j, i)
# ---------------------------------------------------------------------------


def _p_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, i, j,
              scale, causal, seq_k):
    """Shared recompute: probabilities P and score-grad dS for one tile."""
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    visible = _mask(i, j, *s.shape, seq_k, causal)
    # explicit mask (not just -inf) so rows whose lse ~ -inf stay zero
    p = jnp.where(visible, jnp.exp(s - lse_ref[0]), 0.0)     # [BQ, BK] f32
    dp = jax.lax.dot_general(
        do_ref[0], v_ref[0],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                         # [BQ, BK] f32
    # ds drops to the param dtype for its matmuls (bf16 MXU-native)
    ds = (p * (dp - delta_ref[0]) * scale).astype(q_ref.dtype)
    return p.astype(q_ref.dtype), ds, do_ref[0]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, causal, seq_k):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def tile_body():
        _, ds, _ = _p_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                             i, j, scale, causal, seq_k)
        acc_ref[:] += jnp.dot(ds, k_ref[0],
                              preferred_element_type=jnp.float32)

    if causal:
        pl.when(_tile_live(i, j, q_ref.shape[1], k_ref.shape[1]))(tile_body)
    else:
        tile_body()

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, seq_k):
    j, i = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def tile_body():
        p, ds, do = _p_and_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                              delta_ref, i, j, scale, causal, seq_k)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        dk_acc[:] += jax.lax.dot_general(
            ds, q_ref[0],
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        pl.when(_tile_live(i, j, q_ref.shape[1], k_ref.shape[1]))(tile_body)
    else:
        tile_body()

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_call(q, k, v, o, lse, do, scale, causal, seq_k, block_q, block_k):
    bh, sq, d = q.shape
    sk = k.shape[1]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # [bh, sq, 1]

    qi_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kj_spec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
    row_i = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          seq_k=seq_k),
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[qi_spec, kj_spec, kj_spec, qi_spec, row_i, row_i],
        out_specs=qi_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
        compiler_params=_PARAMS,
        name="flash_attention_bwd_dq",
    )(q, k, v, do, lse, delta)

    # same specs with the (j, i) grid order: i is now the innermost dim
    qi_spec2 = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    kj_spec2 = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    row_i2 = pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          seq_k=seq_k),
        grid=(bh, sk // block_k, sq // block_q),
        in_specs=[qi_spec2, kj_spec2, kj_spec2, qi_spec2, row_i2, row_i2],
        out_specs=[kj_spec2, kj_spec2],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=_interpret(),
        compiler_params=_PARAMS,
        name="flash_attention_bwd_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op: [batch, seq, heads, head_dim] with padding + custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, seq_k, block_q, block_k):
    o, _ = _fwd_call(q, k, v, scale, causal, seq_k, block_q, block_k)
    return o


def _flash_fwd(q, k, v, scale, causal, seq_k, block_q, block_k):
    o, lse = _fwd_call(q, k, v, scale, causal, seq_k, block_q, block_k)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, seq_k, block_q, block_k, res, g):
    q, k, v, o, lse = res
    return _bwd_call(q, k, v, o, lse, g, scale, causal, seq_k,
                     block_q, block_k)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _fold_heads(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold_heads(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def flash_attention(q, k, v, causal: bool = False,
                    scale: float | None = None,
                    block_q: int = _BLOCK_Q, block_k: int = _BLOCK_K):
    """Memory-efficient attention; drop-in for ``dense_attention``.

    Args:
      q: [batch, seq_q, heads, head_dim].
      k, v: [batch, seq_k, heads, head_dim].
      causal: mask key positions above the query's global position.
      scale: score scale; default 1/sqrt(head_dim).
      block_q, block_k: kernel tile sizes (tune per hardware; defaults
        1024x1024 — see the module-top sizing note).
    Returns:
      [batch, seq_q, heads, head_dim] in q's dtype.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = (1.0 / d ** 0.5) if scale is None else float(scale)
    if d > 128:                  # keep the VMEM working set bounded
        block_k = min(block_k, 512)
    block_q = min(block_q, _pad_up(sq, 8))
    block_k = min(block_k, _pad_up(sk, 8))

    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    sq_p, sk_p = _pad_up(sq, block_q), _pad_up(sk, block_k)
    # query padding: rows are sliced off below and receive zero cotangents
    # in the VJP; key padding is masked inside the kernel (kpos >= seq_k)
    qf = jnp.pad(qf, ((0, 0), (0, sq_p - sq), (0, 0)))
    kf = jnp.pad(kf, ((0, 0), (0, sk_p - sk), (0, 0)))
    vf = jnp.pad(vf, ((0, 0), (0, sk_p - sk), (0, 0)))

    o = _flash(qf, kf, vf, scale, causal, sk, block_q, block_k)
    return _unfold_heads(o[:, :sq], b, h)
