"""One Mamba-2 (SSD) decode step of one layer as a Pallas TPU kernel.

The serving lane keeps every Mamba-2 layer's state in one float32 leaf
``h [layers, slots, heads, P, N]`` (``serve.decode``: a request owns a
slot from admit to finish, slot 0 is the trash slot).  A decode step
moves every active row's state one token on::

    h' = h * exp(dt A) + (dt x) B^T          # [P, N] a head
    y  = h' C                                # [P] a head

In plain XLA (``models/granite4h.ssd_step`` over the slot-ordered
inputs) that is two passes over a layer's whole slice of the leaf — the
in-place update, then the read-out over the new state — and a scatter of
the rows' inputs into slot order around them, every slot visited
whether a row names it or not.

This kernel's grid follows the ROWS: the layer index and each row's slot
ride the scalar-prefetch channel, and grid step ``i`` updates row
``i``'s block ``h[layer, slots[i]]`` in VMEM, reads ``y`` out of the
updated block while it is there, and writes the block back where it
rests: each named slot's state is read once and written once.  The
blocks are moved by hand, ``_ROWS`` of them in VMEM: while row ``i`` is
computed the next ``_ROWS - 1`` rows are on their way in, and row
``i``'s write-back then runs beside those reads.  (Pallas' own pipeline
keeps one read and one write in flight; at this block size that, not the
arithmetic, bounds a kernel that uses it.)  The state is the kernel's
aliased operand (``input_output_aliases``), passed whole — a slice of the
leaf at the call site would stand as a copy of a layer of it, out and
back.

In VMEM the kernel works two heads at a time, as one ``[2P, N]`` tile
(``2P`` = 128 sublanes at the published widths): the heads' decays are
scalars from SMEM, their ``dt x`` one lane column of a ``[2P, heads /
2]`` block broadcast across the lanes, and the read-out is the tile
times ``C`` transposed and summed over sublanes, so that a pair's ``y``
lands as one row of ``2P`` lanes (``[heads / 2, 2P]`` is ``[heads, P]``
in row-major order) with no reduction across lanes.

Arithmetic: float32 on the vector unit, in ``ssd_step``'s association
(``h * decay + (dt x) * B``, then the sum of ``h' * C`` over ``N``); only
the order of that sum may differ.  No matmul rounds an operand.

Rows that name no request carry ``dt`` = 0 and name the trash slot:
decay 1 and no input, so the block they visit is written back as it was
read, however many of them there are and in whatever order (a read of
it beside another row's write of it reads the same values).  Any other
slot belongs to one request, so no two rows name it.

On the CPU backend the kernel runs in Pallas interpreter mode
(``ops._pallas.interpret``); the tests hold it to ``ssd_step``.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_hc_bench.ops._pallas import interpret as _interpret

# the kernel's name in a compiled program: its custom calls are
# ``ssd_decode``, ``ssd_decode.<n>``
NAME = "ssd_decode"

# rows are visited in order: the hand-made pipeline carries from one grid
# step to the next
_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
# state blocks in VMEM (2 MiB each at the published widths): the row
# computed and the rows fetched ahead; a block's write-back ends before
# the block is fetched into again
_ROWS = 3


def _kernel(layer_ref, slots_ref, decay_ref, dtx_ref, b_ref, c_ref, h_hbm,
            h_out, y_ref, buf, sem):
    """Row ``i``: ``h_hbm`` / ``h_out`` the whole leaf where it rests (one
    buffer); ``buf`` ``[_ROWS, heads, P, N]``, row ``j`` in ``buf[j %
    _ROWS]``; ``decay_ref`` ``[heads]`` in SMEM; ``dtx_ref`` ``[2P, heads
    / 2]`` (heads ``2r`` and ``2r + 1`` stacked in column ``r``);
    ``y_ref`` ``[heads / 2, 2P]`` (the same pair in row ``r``); ``b_ref``
    / ``c_ref`` ``[1, N]``."""
    i, n = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]

    def fetch(j):
        return pltpu.make_async_copy(h_hbm.at[layer, slots_ref[j]],
                                     buf.at[j % _ROWS], sem.at[0, j % _ROWS])

    def store(j):
        return pltpu.make_async_copy(buf.at[j % _ROWS],
                                     h_out.at[layer, slots_ref[j]],
                                     sem.at[1, j % _ROWS])

    def start_fetch(j):
        fetch(j).start()

    @pl.when(i == 0)
    def _():
        for j in range(_ROWS - 1):
            pl.when(j < n)(functools.partial(start_fetch, j))

    # row i + _ROWS - 1 goes where row i - 1 was: that write comes first
    @pl.when(i > 0)
    def _():
        store(i - 1).wait()

    @pl.when(i + _ROWS - 1 < n)
    def _():
        fetch(i + _ROWS - 1).start()

    fetch(i).wait()
    B, C = b_ref[0], c_ref[0]
    dtx = dtx_ref[0]
    block = buf.at[i % _ROWS]
    heads, P, N = block.shape
    for r in range(heads // 2):
        k = 2 * r
        h = (jnp.concatenate([block[k] * decay_ref[0, 0, k],
                              block[k + 1] * decay_ref[0, 0, k + 1]])
             + dtx[:, r:r + 1] * B)
        block[k:k + 2] = h.reshape(2, P, N)
        y_ref[0, r:r + 1] = jnp.sum(jnp.transpose(h * C), axis=0,
                                    keepdims=True)
    store(i).start()

    @pl.when(i == n - 1)
    def _():
        store(i).wait()


@jax.jit
def ssd_decode_step(h, layer, slots, decay, dtx, B, C):
    """One decode step of layer ``layer`` for ``b`` rows, in place.

    Args:
      h: ``[layers, slots, heads, P, N]`` float32, the whole state leaf
        (aliased: the result is the same buffer where the caller donates
        it); ``heads`` even.
      layer: int32 scalar, the layer's index into ``h`` (an operand, not
        a constant: the layers of a program share one lowering).
      slots: ``[b]`` int32, each row's slot (0, the trash slot, for a row
        that names no request).
      decay: ``[b, heads]`` float32, ``exp(dt A)``.
      dtx: ``[b, heads, P]`` float32, ``dt x``.
      B, C: ``[b, N]`` float32.
    Returns:
      ``(h, y [b, heads, P])``.
    """
    _, _, heads, P, N = h.shape
    b = slots.shape[0]
    row = lambda i, li, sl: (i, 0, 0)                       # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, heads), row, memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 2 * P, heads // 2), row),      # dt x, pairs
            pl.BlockSpec((1, 1, N), row),                   # B
            pl.BlockSpec((1, 1, N), row),                   # C
            pl.BlockSpec(memory_space=pl.ANY),              # h
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, heads // 2, 2 * P), row),      # y, pairs
        ],
        scratch_shapes=[pltpu.VMEM((_ROWS, heads, P, N), jnp.float32),
                        pltpu.SemaphoreType.DMA((2, _ROWS))],
    )
    h, y = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(h.shape, h.dtype),
                   jax.ShapeDtypeStruct((b, heads // 2, 2 * P), jnp.float32)],
        # operands count the two scalar-prefetch ones: h is the 7th
        input_output_aliases={6: 0},
        interpret=_interpret(),
        compiler_params=_PARAMS,
        name=NAME,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      decay[:, None],
      jnp.swapaxes(dtx.reshape(b, heads // 2, 2 * P), 1, 2),
      B[:, None], C[:, None], h)
    return h, y.reshape(b, heads, P)


def kernel_calls(hlo_text: str) -> int:
    """The kernel's custom calls in a compiled program's text (0 where it
    runs interpreted: the CPU lowers its body to plain operations)."""
    return len(re.findall(
        rf"%{NAME}(?:\.\d+)? = [^\n]*custom-call\(", hlo_text))
