"""Flash attention (blocked online-softmax) as a Pallas TPU kernel.

The reference's compute engine delegates its hot kernels to MKL-DNN
(SURVEY.md §2b #21); the TPU-native analog is XLA plus Pallas where manual
blocking beats the compiler.  Attention is the canonical case: the naive
``softmax(QK^T)V`` materializes an [S, S] score matrix in HBM per head,
while this kernel streams K/V blocks through VMEM with the online-softmax
recurrence, so scores never leave the chip:

    m' = max(m, rowmax(S_blk));   l' = l*e^(m-m') + rowsum(e^(S_blk - m'))
    acc' = acc*e^(m-m') + e^(S_blk - m') @ V_blk

A causal call computes only the sub-tiles at or below the diagonal
(``tile_plan``; at seq >= 2048 the grid also skips whole blocks above it,
whose K/V block DMAs still run: the grid is a rectangle).

A causal call with a ``window`` (sliding-window layers: query ``i`` sees
keys ``i - window < j <= i``) is forward only, under a kernel of its own
(``flash_window_fwd``).  Its grid walks each query block's BAND: the key
blocks from the one the window's lower edge reaches to the diagonal
block, so a long sequence costs its band and not its square, and inside
a block only the sub-tiles that meet the band are computed; those the
band's lower edge or the diagonal crosses are masked.

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
import typing

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_hc_bench.ops._pallas import interpret as _interpret
from tpu_hc_bench.ops._pallas import pad_up as _pad_up

# Default blocks: 1024x1024, so at seq 1024 the grid is one step a head
# and cannot skip anything itself.  A causal call walks 256-wide sub-tiles
# INSIDE each block (``tile_plan``): those wholly above the diagonal are
# never computed, a row's sub-tiles wholly below it go through the
# matmuls and the softmax as ONE strip, and only the sub-tile the
# diagonal crosses builds a mask.
#
# Sizing note (PR 34, 2026-10-05, one TPU v5e, INSIDE the gpt2_medium
# train step of benchmark cell gpt2m-train-1k: seq 1024, batch 16, 16
# heads of 64, bf16; 12 s windows of benchmarks/run.py, one run each,
# examples/s/chip; 10 of 16 sub-tiles computed at 256, 36 of 64 at 128,
# 3 of 4 at 512):
#   the whole square, one 1024x1024 tile a head (before PR 34)     36.54
#   512x512 GRID blocks, K/V index maps clamped to the last live   34.75
#   256 sub-tiles one by one, an online-softmax step a sub-tile    36.32
#   strips, the row's state kept in scratch: 512 / 256 / 128       37.13 / 37.13 / 37.82
#   strips, one-block grid writes each row where it ends: 256      39.03  <- ships
# The three kernels alone at [256, 1024, 64] (host-timed, median of 20,
# forward + backward): 6.07 ms before, 4.83 as shipped (5.07 at 128,
# 5.14 at 512), 6.97 for the 512 grid blocks, 6.16 for the sub-tiles one
# by one — the same order as inside the step.  What it taught: skipping
# sub-tiles wins nothing by itself, because the [rows, 1] bookkeeping
# around a tile (row maxima, the rescale, the sums: vregs with one lane
# in use) costs as much as the sub-tiles skipped; a strip pays it once a
# row.  Smaller grid blocks lose to the per-step overhead and to K/V
# fetched again for every q block.  State carried in scratch for a grid
# that has one step costs 5% of the train step.  Only A/Bs inside the
# whole step decide this knob.
# Callers with head_dim > 128 get block_k halved below.  Overridable per
# call for small test shapes.
_BLOCK_Q = 1024
_BLOCK_K = 1024
_SUB_TILE = 256
_NEG_INF = -1e30


# batch*heads and the outer block dim are embarrassingly parallel; only the
# innermost (accumulating) grid dim carries loop state
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


# ---------------------------------------------------------------------------
# the tile plan: which sub-tiles of the score rectangle are computed.  The
# counts and the kernels' loops come from the same two span functions.
# ---------------------------------------------------------------------------


def _div_clip(x: int, d: int, n: int) -> int:
    """``x // d`` held to ``[0, n]``."""
    return min(max(x, 0) // d, n)


class TilePlan(typing.NamedTuple):
    """Blocks (one grid step each) and the sub-tiles a causal block is
    walked in.  Per kernel (``fwd``, ``bwd_dq``, ``bwd_dkv``) it counts
    ``(computed, masked)`` sub-tiles of the ``rect`` in the padded score
    rectangle of one head, from the spans the kernels walk; ``masked``
    are the computed ones the diagonal crosses, the only ones that build
    the causal mask."""
    block_q: int
    block_k: int
    sub_q: int
    sub_k: int
    sq_p: int
    sk_p: int
    causal: bool
    window: int | None = None

    @property
    def band(self) -> int:
        """Key blocks a query block visits under the window: its own and
        those behind it that the window reaches (the grid's last axis)."""
        nq = self.sq_p // self.block_q
        return min(nq, -(-(self.window - 1) // self.block_k) + 1)

    @property
    def one_block(self) -> bool:
        """The grid is one block a head: the kernels keep no state
        between grid steps and write each row's result where it ends."""
        return self.sq_p == self.block_q and self.sk_p == self.block_k

    def key_span(self, delta: int, a: int) -> tuple:
        """``(full, live)`` for q sub-tile ``a`` of a block whose first
        query sits ``delta`` positions past its first key: key sub-tiles
        ``[0, full)`` lie wholly at or below the diagonal, ``[full,
        live)`` cross it, the rest wholly above.  The forward's and
        ``bwd_dq``'s walk."""
        n = self.block_k // self.sub_k
        if not self.causal:
            return n, n
        first_q = delta + a * self.sub_q
        return (_div_clip(first_q + 1, self.sub_k, n),
                _div_clip(first_q + self.sub_q + self.sub_k - 1,
                          self.sub_k, n))

    def query_span(self, delta: int, b: int) -> tuple:
        """``(live, full)`` for key sub-tile ``b``: q sub-tiles ``[0,
        live)`` lie wholly above the diagonal, ``[live, full)`` cross it,
        ``[full, n)`` wholly at or below.  ``bwd_dkv``'s walk."""
        n = self.block_q // self.sub_q
        if not self.causal:
            return 0, 0
        first_k = b * self.sub_k - delta
        return (_div_clip(first_k, self.sub_q, n),
                _div_clip(first_k + self.sub_k - 1 + self.sub_q - 1,
                          self.sub_q, n))

    def window_span(self, delta: int, a: int) -> tuple:
        """``(lo, edge, full, live)`` for q sub-tile ``a`` under the
        window: key sub-tiles ``[lo, edge)`` cross the band's lower edge,
        ``[edge, full)`` lie wholly inside the band for every query of
        the sub-tile, ``[full, live)`` cross the diagonal (a sub-tile
        that crosses both is counted with the lower edge); the rest hold
        no visible key.  The windowed forward's walk."""
        n = self.block_k // self.sub_k
        first_q = delta + a * self.sub_q
        last_q = first_q + self.sub_q - 1
        full = _div_clip(first_q + 1, self.sub_k, n)
        live = _div_clip(last_q + self.sub_k, self.sub_k, n)
        lo = _div_clip(first_q - self.window + 1, self.sub_k, n)
        edge = min(max(-(-(last_q - self.window + 1) // self.sub_k), lo),
                   full)
        return lo, edge, full, live

    def block_offsets(self) -> list:
        """``delta = i * block_q - j * block_k`` of every block of the
        grid that is computed, i.e. holds a visible element.  Every block
        wholly at or below the diagonal (``delta >= block_k - 1``: its
        spans are all the same) reads ``block_k - 1``; a block the
        diagonal crosses keeps its own.  The kernels build one body a
        DISTINCT offset; the counts below sum over all of them.  Under a
        window the grid is the band (``band``: key block ``i - band + 1
        + t`` at step ``t``), whose offsets are few and kept as they are."""
        nq = self.sq_p // self.block_q
        if self.window is not None:
            return [(self.band - 1 - t) * self.block_k for i in range(nq)
                    for t in range(self.band) if i >= self.band - 1 - t]
        deltas = [i * self.block_q - j * self.block_k
                  for i in range(nq)
                  for j in range(self.sk_p // self.block_k)]
        if not self.causal:
            return [0] * len(deltas)
        return [min(d, self.block_k - 1) for d in deltas
                if d + self.block_q > 0]

    @property
    def rect(self) -> int:
        return (self.sq_p // self.sub_q) * (self.sk_p // self.sub_k)

    @property
    def fwd(self) -> tuple:
        rows = range(self.block_q // self.sub_q)
        if self.window is not None:
            spans = [self.window_span(d, a) for d in self.block_offsets()
                     for a in rows]
            return (sum(live - lo for lo, _, _, live in spans),
                    sum(edge - lo + live - full
                        for lo, edge, full, live in spans))
        spans = [self.key_span(d, a) for d in self.block_offsets()
                 for a in rows]
        return (sum(live for _, live in spans),
                sum(live - full for full, live in spans))

    bwd_dq = fwd         # the same walk: a q row over its key sub-tiles

    @property
    def bwd_dkv(self) -> tuple:
        if self.window is not None:
            raise ValueError("the windowed kernel is forward only")
        n_q = self.block_q // self.sub_q
        spans = [self.query_span(d, b) for d in self.block_offsets()
                 for b in range(self.block_k // self.sub_k)]
        return (sum(n_q - live for live, _ in spans),
                sum(full - live for live, full in spans))

    @property
    def computed_share(self) -> float:
        return (self.fwd[0] + self.bwd_dq[0] + self.bwd_dkv[0]) / (
            3 * self.rect)


def _fit(block, seq, sub):
    """One axis: the block clamped to the padded sequence, and the
    sub-tile it is walked in (the block itself where ``sub`` does not
    cut it)."""
    if sub and block % sub == 0 and seq > sub:
        return min(block, _pad_up(seq, sub)), sub
    block = min(block, _pad_up(seq, 8))
    return block, block


def tile_plan(sq: int, sk: int, block_q: int = _BLOCK_Q,
              block_k: int = _BLOCK_K, causal: bool = False,
              head_dim: int = 64, sub_tile: int | None = None,
              window: int | None = None) -> TilePlan:
    """What a call of these lengths runs, from static shapes alone.

    A non-causal call computes every block as one tile.  A causal call
    cuts each block into ``sub_tile`` x ``sub_tile`` sub-tiles (default
    ``_SUB_TILE``; the block itself where that does not divide it) and
    computes those that hold a visible element.  A ``window`` (causal
    self-attention only: ``sq == sk``, square blocks) also leaves out
    the blocks and sub-tiles wholly below the band."""
    if window is not None and not (causal and sq == sk and window >= 1):
        raise ValueError(f"a window needs causal self-attention and a width "
                         f">= 1 (sq {sq}, sk {sk}, window {window})")
    if head_dim > 128:           # keep the VMEM working set bounded
        block_k = min(block_k, 512)
    sub = (_SUB_TILE if sub_tile is None else sub_tile) if causal else None
    block_q, sub_q = _fit(block_q, sq, sub)
    block_k, sub_k = _fit(block_k, sk, sub)
    if window is not None and (block_q, sub_q) != (block_k, sub_k):
        raise ValueError(f"a window walks square blocks: {block_q} x "
                         f"{block_k}")
    return TilePlan(block_q, block_k, sub_q, sub_k, _pad_up(sq, block_q),
                    _pad_up(sk, block_k), causal, window)


def _when(cond, body):
    """``pl.when`` that also takes a Python bool (a one-block axis)."""
    if isinstance(cond, bool):
        if cond:
            body()
    else:
        pl.when(cond)(body)


def _block_ids(plan, q_axis, k_axis):
    """This grid step's ``(i, j)``; a Python 0 on an axis of one block,
    so that a single-block grid needs no ``pl.when`` at all."""
    return (0 if plan.sq_p == plan.block_q else pl.program_id(q_axis),
            0 if plan.sk_p == plan.block_k else pl.program_id(k_axis))


def _for_block(plan, i, j, body):
    """``body(delta)`` for block ``(i, j)`` with its offset a Python int,
    so that every span and slice inside is static: one body a distinct
    offset (``TilePlan.block_offsets``), none for a block wholly above the
    diagonal."""
    if not plan.causal:
        return body(0)
    delta = i * plan.block_q - j * plan.block_k
    for d in sorted(set(plan.block_offsets())):
        below = d == plan.block_k - 1
        _when(delta >= d if below else delta == d, functools.partial(body, d))


def _strips(lo, mid, hi, size, diag_first):
    """The sub-tiles ``[lo, hi)`` of one row (or column) of a block as
    ``(slice, first position, diag)`` pieces: those the diagonal crosses
    (``[lo, mid)`` if ``diag_first`` else ``[mid, hi)``) one by one, the
    others as ONE strip, so that the work around a piece (row maxima,
    sums, the state's rescale) is paid once for the strip."""
    diag = range(lo, mid) if diag_first else range(mid, hi)
    whole = (mid, hi) if diag_first else (lo, mid)
    pieces = [(pl.ds(x * size, size), x * size, True) for x in diag]
    if whole[1] > whole[0]:
        strip = (pl.ds(whole[0] * size, (whole[1] - whole[0]) * size),
                 whole[0] * size, False)
        pieces = pieces + [strip] if diag_first else [strip] + pieces
    return pieces


def _visible(q0, k0, shape, seq_k, diag, pad):
    """[rows, keys] bool, or None where the whole piece is visible: key
    in range (``pad``: the call has padded keys) and, on a sub-tile the
    diagonal crosses (``diag``), at or before the query."""
    if not (diag or pad):
        return None
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    m = kpos < seq_k if pad else None
    if diag:
        c = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0) >= kpos
        m = c if m is None else jnp.logical_and(m, c)
    return m


def _scores(q, k, scale):
    # matmuls run in the input dtype (bf16 native on the MXU), f32 accum
    return jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale


# ---------------------------------------------------------------------------
# forward: grid (batch*heads, q_blocks, k_blocks), k innermost
# ---------------------------------------------------------------------------


def _writer(o_ref, lse_ref):
    def write(rows, m, l, acc):
        l = jnp.maximum(l, 1e-30)
        o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, rows, :] = m + jnp.log(l)

    return write


def _fold_rows(rows, scores, v_ref, state, write):
    """One q sub-tile's pieces ``scores`` (``(s, visible, cols)``, masked
    scores in float32) folded into its rows' online softmax: into the
    state a row keeps between grid steps, or written where it ends."""
    m = functools.reduce(jnp.maximum, [
        jnp.max(s, axis=1, keepdims=True) for s, _, _ in scores])
    l, acc = 0.0, 0.0
    if state:
        m_ref, l_ref, acc_ref = state
        m_old = m_ref[rows, :]                     # [SQ, 1]
        m = jnp.maximum(m_old, m)
        corr = jnp.exp(m_old - m)
        l, acc = l_ref[rows, :] * corr, acc_ref[rows, :] * corr
    for s, visible, cols in scores:
        p = jnp.exp(s - m)
        if visible is not None:
            # fully-masked rows keep m == _NEG_INF; exp(s-m)=1
            # there, so re-mask
            p = jnp.where(visible, p, 0.0)
        l = l + jnp.sum(p, axis=1, keepdims=True)
        acc = acc + jnp.dot(p.astype(v_ref.dtype), v_ref[0, cols, :],
                            preferred_element_type=jnp.float32)
    if state:
        m_ref[rows, :], l_ref[rows, :], acc_ref[rows, :] = m, l, acc
    else:
        write(rows, m, l, acc)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *state,
                scale, seq_k, plan):
    i, j = _block_ids(plan, 1, 2)
    pad = plan.sk_p != seq_k
    if state:      # several blocks a head: a row's state rests here between
        m_ref, l_ref, acc_ref = state
    write = _writer(o_ref, lse_ref)

    def block(delta):
        for a in range(plan.block_q // plan.sub_q):
            full, live = plan.key_span(delta, a)
            if not live:
                continue
            rows = pl.ds(a * plan.sub_q, plan.sub_q)
            q = q_ref[0, rows, :]
            scores = []
            for cols, k0, diag in _strips(0, full, live, plan.sub_k, False):
                s = _scores(q, k_ref[0, cols, :], scale)   # [SQ, keys] f32
                visible = _visible(i * plan.block_q + a * plan.sub_q,
                                   j * plan.block_k + k0, s.shape, seq_k,
                                   diag, pad)
                if visible is not None:
                    s = jnp.where(visible, s, _NEG_INF)
                scores.append((s, visible, cols))
            _fold_rows(rows, scores, v_ref, state, write)

    if not state:
        # one block a head: every row ends here (key 0 is visible to all)
        return block(0)

    def init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    _when(j == 0, init)
    _for_block(plan, i, j, block)
    _when(j == plan.sk_p // plan.block_k - 1,
          lambda: write(slice(None), m_ref[:], l_ref[:], acc_ref[:]))


# The two calls are jitted so that a model's layers share ONE trace and one
# Mosaic lowering of each kernel (a causal kernel's body is its triangle
# unrolled): the 24-layer train step lowers 3 kernels where it lowered 72,
# and flax's eager ``model.init`` compiles the forward kernel once, not
# once a layer.  Warm ``setup_s`` of gpt2m-train-1k (PR 34, same v5e
# call): 64.8-65.2 s before PR 34, 92.3 with the unrolled kernels
# un-jitted, 54.2-57.0 as here.  XLA inlines the calls: the compiled step
# is the same program (38.994 / 38.993 examples/s/chip without / with).
_STATIC = ("scale", "seq_k", "plan")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(q, k, v, scale, seq_k, plan):
    bh, sq, d = q.shape
    block_q, block_k = plan.block_q, plan.block_k
    grid = (bh, sq // block_q, k.shape[1] // block_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, seq_k=seq_k, plan=plan
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
        scratch_shapes=[] if plan.one_block else [
            pltpu.VMEM((block_q, 1), jnp.float32),            # running max
            pltpu.VMEM((block_q, 1), jnp.float32),            # running sum
            pltpu.VMEM((block_q, d), jnp.float32),            # output acc
        ],
        interpret=_interpret(),
        compiler_params=_PARAMS,
        name="flash_attention_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# windowed forward: grid (batch*heads, q_blocks, band), the band innermost
# ---------------------------------------------------------------------------


def _band_visible(q0, k0, shape, window):
    """[rows, keys] bool: the key at or before the query and less than
    ``window`` positions behind it."""
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return jnp.logical_and(kpos <= qpos, kpos > qpos - window)


def _fwd_window_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *state,
                       scale, plan):
    """Step ``t`` of query block ``i`` visits key block ``i - band + 1 +
    t`` (none where that is below 0): its offset ``(band - 1 - t) x
    block`` is static a step, so every span inside is too.  Padded keys
    lie past every real query and are never visible to one; padded
    queries are sliced off by the caller."""
    write = _writer(o_ref, lse_ref)

    def block(delta):
        for a in range(plan.block_q // plan.sub_q):
            lo, edge, full, live = plan.window_span(delta, a)
            if live <= lo:
                continue
            rows = pl.ds(a * plan.sub_q, plan.sub_q)
            q = q_ref[0, rows, :]
            pieces = ([(x, 1, True) for x in range(lo, edge)]
                      + ([(edge, full - edge, False)] if full > edge else [])
                      + [(x, 1, True) for x in range(full, live)])
            scores = []
            for x, count, crossed in pieces:
                cols = pl.ds(x * plan.sub_k, count * plan.sub_k)
                s = _scores(q, k_ref[0, cols, :], scale)   # [SQ, keys] f32
                visible = None
                if crossed:     # positions from the block's first key
                    visible = _band_visible(delta + a * plan.sub_q,
                                            x * plan.sub_k, s.shape,
                                            plan.window)
                    s = jnp.where(visible, s, _NEG_INF)
                scores.append((s, visible, cols))
            _fold_rows(rows, scores, v_ref, state, write)

    if not state:
        # a band of one block: the diagonal's, where every row ends
        return block(0)
    m_ref, l_ref, acc_ref = state
    i, t = pl.program_id(1), pl.program_id(2)

    def init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    _when(t == 0, init)
    for t0 in range(plan.band):
        behind = plan.band - 1 - t0
        cond = t == t0
        if behind:
            cond = jnp.logical_and(cond, i >= behind)
        _when(cond, functools.partial(block, behind * plan.block_k))
    _when(t == plan.band - 1,
          lambda: write(slice(None), m_ref[:], l_ref[:], acc_ref[:]))


@functools.partial(jax.jit, static_argnames=("scale", "plan"))
def _fwd_window_call(q, k, v, scale, plan):
    bh, sq, d = q.shape
    block, band = plan.block_q, plan.band

    def kv_block(b, i, t):
        # the first steps of the first query blocks reach below key 0:
        # they load block 0 and compute nothing
        return b, jnp.maximum(i - (band - 1) + t, 0), 0

    return pl.pallas_call(
        functools.partial(_fwd_window_kernel, scale=scale, plan=plan),
        grid=(bh, sq // block, band),
        in_specs=[
            pl.BlockSpec((1, block, d), lambda b, i, t: (b, i, 0)),
            pl.BlockSpec((1, block, d), kv_block),
            pl.BlockSpec((1, block, d), kv_block),
        ],
        out_specs=[
            pl.BlockSpec((1, block, d), lambda b, i, t: (b, i, 0)),
            pl.BlockSpec((1, block, 1), lambda b, i, t: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[] if band == 1 else [
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, d), jnp.float32),
        ],
        interpret=_interpret(),
        compiler_params=_PARAMS,
        name="flash_window_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: dq over (bh, i, j) with j innermost; dk/dv over (bh, j, i)
# ---------------------------------------------------------------------------


def _p_and_ds(q, k, v, do, lse, delta, visible, scale):
    """Shared recompute: probabilities P and score-grad dS for one piece."""
    p = jnp.exp(_scores(q, k, scale) - lse)                  # [SQ, SK] f32
    if visible is not None:
        # explicit mask (not just -inf) so rows whose lse ~ -inf stay zero
        p = jnp.where(visible, p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                         # [SQ, SK] f32
    # ds drops to the param dtype for its matmuls (bf16 MXU-native)
    ds = (p * (dp - delta) * scale).astype(q.dtype)
    return p.astype(q.dtype), ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *state, scale, seq_k, plan):
    i, j = _block_ids(plan, 1, 2)
    pad = plan.sk_p != seq_k

    def block(delta):
        for a in range(plan.block_q // plan.sub_q):
            full, live = plan.key_span(delta, a)
            if not live:
                continue
            rows = pl.ds(a * plan.sub_q, plan.sub_q)
            q, do = q_ref[0, rows, :], do_ref[0, rows, :]
            lse, dl = lse_ref[0, rows, :], delta_ref[0, rows, :]
            acc = state[0][rows, :] if state else 0.0
            for cols, k0, diag in _strips(0, full, live, plan.sub_k, False):
                k = k_ref[0, cols, :]
                visible = _visible(i * plan.block_q + a * plan.sub_q,
                                   j * plan.block_k + k0,
                                   (plan.sub_q, k.shape[0]), seq_k, diag, pad)
                _, ds = _p_and_ds(q, k, v_ref[0, cols, :], do, lse, dl,
                                  visible, scale)
                acc = acc + jnp.dot(ds, k,
                                    preferred_element_type=jnp.float32)
            if state:
                state[0][rows, :] = acc
            else:
                dq_ref[0, rows, :] = acc.astype(dq_ref.dtype)

    if not state:
        return block(0)
    acc_ref, = state

    def init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def finish():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)

    _when(j == 0, init)
    _for_block(plan, i, j, block)
    _when(j == plan.sk_p // plan.block_k - 1, finish)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *state, scale, seq_k, plan):
    i, j = _block_ids(plan, 2, 1)
    pad = plan.sk_p != seq_k

    def block(delta):
        n_q = plan.block_q // plan.sub_q
        for b in range(plan.block_k // plan.sub_k):
            live, full = plan.query_span(delta, b)
            cols = pl.ds(b * plan.sub_k, plan.sub_k)
            k, v = k_ref[0, cols, :], v_ref[0, cols, :]
            if state:
                dk, dv = state[0][cols, :], state[1][cols, :]  # [SK, d] f32
            else:      # keys past every query of the block get zeros
                dk = dv = jnp.zeros(k.shape, jnp.float32)
            for rows, q0, diag in _strips(live, full, n_q, plan.sub_q, True):
                q, do = q_ref[0, rows, :], do_ref[0, rows, :]
                visible = _visible(i * plan.block_q + q0,
                                   j * plan.block_k + b * plan.sub_k,
                                   (q.shape[0], plan.sub_k), seq_k, diag, pad)
                p, ds = _p_and_ds(q, k, v, do, lse_ref[0, rows, :],
                                  delta_ref[0, rows, :], visible, scale)
                dv = dv + jax.lax.dot_general(
                    p, do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dk = dk + jax.lax.dot_general(
                    ds, q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            if state:
                state[0][cols, :], state[1][cols, :] = dk, dv
            else:
                dk_ref[0, cols, :] = dk.astype(dk_ref.dtype)
                dv_ref[0, cols, :] = dv.astype(dv_ref.dtype)

    if not state:
        return block(0)
    dk_acc, dv_acc = state

    def init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    _when(i == 0, init)
    _for_block(plan, i, j, block)
    _when(i == plan.sq_p // plan.block_q - 1, finish)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_call(q, k, v, o, lse, do, scale, seq_k, plan):
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q, block_k = plan.block_q, plan.block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # [bh, sq, 1]

    qi_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kj_spec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
    row_i = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, seq_k=seq_k, plan=plan),
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[qi_spec, kj_spec, kj_spec, qi_spec, row_i, row_i],
        out_specs=qi_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=([] if plan.one_block
                        else [pltpu.VMEM((block_q, d), jnp.float32)]),
        interpret=_interpret(),
        compiler_params=_PARAMS,
        name="flash_attention_bwd_dq",
    )(q, k, v, do, lse, delta)

    # same specs with the (j, i) grid order: i is now the innermost dim
    qi_spec2 = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    kj_spec2 = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    row_i2 = pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, seq_k=seq_k, plan=plan),
        grid=(bh, sk // block_k, sq // block_q),
        in_specs=[qi_spec2, kj_spec2, kj_spec2, qi_spec2, row_i2, row_i2],
        out_specs=[kj_spec2, kj_spec2],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[] if plan.one_block else [
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, scale, seq_k, plan):
    o, _ = _fwd_call(q, k, v, scale, seq_k, plan)
    return o


def _flash_fwd(q, k, v, scale, seq_k, plan):
    o, lse = _fwd_call(q, k, v, scale, seq_k, plan)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, seq_k, plan, res, g):
    q, k, v, o, lse = res
    return _bwd_call(q, k, v, o, lse, g, scale, seq_k, plan)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _fold_heads(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold_heads(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def flash_attention(q, k, v, causal: bool = False,
                    scale: float | None = None,
                    block_q: int = _BLOCK_Q, block_k: int = _BLOCK_K,
                    sub_tile: int | None = None, window: int | None = None):
    """Memory-efficient attention; drop-in for ``dense_attention``.

    Args:
      q: [batch, seq_q, heads, head_dim].
      k, v: [batch, seq_k, heads, head_dim].
      causal: mask key positions above the query's global position.
      scale: score scale; default 1/sqrt(head_dim).
      block_q, block_k: kernel tile sizes (tune per hardware; defaults
        1024x1024 — see the module-top sizing note).
      sub_tile: side of the sub-tiles a causal block is walked in
        (``tile_plan``; default ``_SUB_TILE``).
      window: sliding-window width (causal self-attention): query ``i``
        sees keys ``i - window < j <= i``.  Forward only.
    Returns:
      [batch, seq_q, heads, head_dim] in q's dtype.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = (1.0 / d ** 0.5) if scale is None else float(scale)
    plan = tile_plan(sq, sk, block_q, block_k, causal, head_dim=d,
                     sub_tile=sub_tile, window=window)

    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    # query padding: rows are sliced off below and receive zero cotangents
    # in the VJP; key padding is masked inside the kernel (kpos >= seq_k)
    qf = jnp.pad(qf, ((0, 0), (0, plan.sq_p - sq), (0, 0)))
    kf = jnp.pad(kf, ((0, 0), (0, plan.sk_p - sk), (0, 0)))
    vf = jnp.pad(vf, ((0, 0), (0, plan.sk_p - sk), (0, 0)))

    if window is not None:
        o, _ = _fwd_window_call(qf, kf, vf, scale, plan)
    else:
        o = _flash(qf, kf, vf, scale, sk, plan)
    return _unfold_heads(o[:, :sq], b, h)
