"""One gated delta-rule ("KDA") decode step of one layer as a Pallas TPU
kernel.

The serving lane keeps every KDA layer's state in one float32 leaf
``S [layers, slots, heads, d_k, d_v]`` (``serve.decode``: a request owns
a slot from admit to finish, slot 0 is the trash slot).  A decode step
moves every active row's state one token on, per head::

    Sd = S * exp(g)[:, None]                 # [d_k, d_v]
    w  = beta * (v - sum(Sd * k[:, None], axis=d_k))
    S' = Sd + k[:, None] * w[None, :]
    o  = sum(S' * q[:, None], axis=d_k)      # [d_v]

In plain XLA (``models/solar_open2.kda_step`` over the slot-ordered
inputs) that is three passes over a layer's whole slice of the leaf —
``Sd k`` before the update, the in-place update, the read-out over the
new state — and a scatter of the rows' inputs into slot order around
them, every slot visited whether a row names it or not.

This kernel's grid follows the ROWS: the layer index and each row's slot
ride the scalar-prefetch channel, and grid step ``i`` updates row
``i``'s block ``S[layer, slots[i]]`` in VMEM, reads ``o`` out of the
updated block while it is there, and writes the block back where it
rests: each named slot's state is read once and written once.  The
blocks are moved by hand, ``_ROWS`` of them in VMEM: while row ``i`` is
computed the next rows are on their way in and row ``i - 1``'s
write-back is on its way out.  A block is 4 MiB at the published widths
(64 heads x 128 x 128 float32), so the three blocks and the rows'
double-buffered vectors take ~13 MiB of v5e's default scoped VMEM of
16 MiB, which Mosaic's own scratch shares: ``vmem_limit_bytes`` is
raised to ``_VMEM_LIMIT`` (of 128 MiB) rather than the blocks halved,
which would double the DMAs and the grid steps a row for the same
bytes.  The state is the kernel's aliased operand
(``input_output_aliases``), passed whole — a slice of the leaf at the
call site would stand as a copy of a layer of it, out and back.

In VMEM the kernel walks the heads in groups of ``_GROUP`` with a
``fori_loop`` (the group unrolled inside it), so the kernel's body — and
the Mosaic module each decode program lowers — is the same size at 8
heads as at 64.  A head's ``[d_k, d_v]`` tile puts ``d_k`` on sublanes:
the sums over ``d_k`` are sums over sublanes, and ``exp(g)``, ``k`` and
``q`` enter as lane columns of ``[d_k, group]`` blocks broadcast across
the lanes (the group's rows of each, ``[group, d_k]`` as the program
holds them, transposed in VMEM); ``v`` and ``o`` are a group's rows of
``[group, d_v]``, and ``beta`` a scalar from SMEM.  Every operand keeps
the layout the program holds it in: nothing is copied for the kernel.

Arithmetic: float32 on the vector unit, in ``kda_step``'s association;
only the order of the sums over ``d_k`` may differ.  No matmul rounds an
operand.

Rows that name no request carry ``g`` = 0 and ``beta`` = 0 and name the
trash slot: decay 1 and ``w`` = 0, so the block they visit is written
back as it was read, however many of them there are and in whatever
order (a read of it beside another row's write of it reads the same
values).  Any other slot belongs to one request, so no two rows name it.

On the CPU backend the kernel runs in Pallas interpreter mode
(``ops._pallas.interpret``); the tests hold it to ``kda_step``.
"""

from __future__ import annotations

import functools
import math
import re

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_hc_bench.ops._pallas import interpret as _interpret

# the kernel's name in a compiled program: its custom calls are
# ``kda_decode``, ``kda_decode.<n>``
NAME = "kda_decode"

# state blocks in VMEM (4 MiB each at the published widths): the row
# computed, the rows fetched ahead and the row being written back
_ROWS = 3
# heads a trip of the loop over heads (at most; a divisor of the heads)
_GROUP = 8
_VMEM_LIMIT = 48 * 2**20
# rows are visited in order: the hand-made pipeline carries from one grid
# step to the next
_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                               vmem_limit_bytes=_VMEM_LIMIT)


def _kernel(layer_ref, slots_ref, beta_ref, eg_ref, k_ref, q_ref, v_ref,
            s_hbm, s_out, o_ref, buf, sem):
    """Row ``i``: ``s_hbm`` / ``s_out`` the whole leaf where it rests (one
    buffer); ``buf`` ``[_ROWS, heads, d_k, d_v]``, row ``j`` in ``buf[j %
    _ROWS]``; ``beta_ref`` ``[heads]`` in SMEM; ``eg_ref``, ``k_ref``,
    ``q_ref`` ``[heads / G, G, d_k]`` and ``v_ref`` / ``o_ref`` ``[heads
    / G, G, d_v]``: group ``r``'s G heads in its rows."""
    i, n = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]

    def fetch(j):
        return pltpu.make_async_copy(s_hbm.at[layer, slots_ref[j]],
                                     buf.at[j % _ROWS], sem.at[0, j % _ROWS])

    def store(j):
        return pltpu.make_async_copy(buf.at[j % _ROWS],
                                     s_out.at[layer, slots_ref[j]],
                                     sem.at[1, j % _ROWS])

    def start_fetch(j):
        fetch(j).start()

    @pl.when(i == 0)
    def _():
        for j in range(_ROWS - 1):
            pl.when(j < n)(functools.partial(start_fetch, j))

    fetch(i).wait()
    block = buf.at[i % _ROWS]
    groups, G, _ = v_ref.shape[1:]

    def group(r, carry):
        # the group's d_k vectors as lane columns: [d_k, G] each
        eg, k, q = (jnp.transpose(ref[0, r]) for ref in (eg_ref, k_ref,
                                                          q_ref))
        vs = v_ref[0, r]                                # [G, d_v]
        out = []
        for j in range(G):
            h = r * G + j
            kj = k[:, j:j + 1]
            sd = block[h] * eg[:, j:j + 1]
            w = beta_ref[0, 0, h] * (
                vs[j:j + 1] - jnp.sum(sd * kj, axis=0, keepdims=True))
            s_new = sd + kj * w
            block[h] = s_new
            out.append(jnp.sum(s_new * q[:, j:j + 1], axis=0,
                               keepdims=True))
        o_ref[0, r] = jnp.concatenate(out, axis=0)
        return carry

    jax.lax.fori_loop(0, groups, group, 0)
    store(i).start()

    # row i + _ROWS - 1 goes where row i - 1 was: that write comes first
    @pl.when(i > 0)
    def _():
        store(i - 1).wait()

    @pl.when(i + _ROWS - 1 < n)
    def _():
        fetch(i + _ROWS - 1).start()

    @pl.when(i == n - 1)
    def _():
        store(i).wait()


@jax.jit
def kda_decode_step(S, layer, slots, eg, k, q, v, beta):
    """One decode step of layer ``layer`` for ``b`` rows, in place.

    Args:
      S: ``[layers, slots, heads, d_k, d_v]`` float32, the whole state
        leaf (aliased: the result is the same buffer where the caller
        donates it).
      layer: int32 scalar, the layer's index into ``S`` (an operand, not
        a constant: the layers of a program share one lowering).
      slots: ``[b]`` int32, each row's slot (0, the trash slot, for a row
        that names no request).
      eg: ``[b, heads, d_k]`` float32, ``exp(g)`` (1 on an inert row).
      k, q: ``[b, heads, d_k]`` float32.
      v: ``[b, heads, d_v]`` float32.
      beta: ``[b, heads]`` float32 (0 on an inert row).
    Returns:
      ``(S, o [b, heads, d_v])``.
    """
    _, _, heads, dk, dv = S.shape
    b = slots.shape[0]
    G = math.gcd(heads, _GROUP)
    groups = heads // G
    row = lambda i, li, sl: (i, 0, 0, 0)                    # noqa: E731
    vec = pl.BlockSpec((1, groups, G, dk), row)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, heads), lambda i, li, sl: (i, 0, 0),
                         memory_space=pltpu.SMEM),          # beta
            vec, vec, vec,                                  # exp(g), k, q
            pl.BlockSpec((1, groups, G, dv), row),          # v
            pl.BlockSpec(memory_space=pl.ANY),              # S
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, groups, G, dv), row),          # o
        ],
        scratch_shapes=[pltpu.VMEM((_ROWS, heads, dk, dv), jnp.float32),
                        pltpu.SemaphoreType.DMA((2, _ROWS))],
    )
    by_group = lambda x: x.reshape(b, groups, G, -1)        # noqa: E731
    S, o = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((b, groups, G, dv), jnp.float32)],
        # operands count the two scalar-prefetch ones: S is the 8th
        input_output_aliases={7: 0},
        interpret=_interpret(),
        compiler_params=_PARAMS,
        name=NAME,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      beta[:, None], by_group(eg), by_group(k), by_group(q), by_group(v), S)
    return S, o.reshape(b, heads, dv)


def kernel_calls(hlo_text: str) -> int:
    """The kernel's custom calls in a compiled program's text (0 where it
    runs interpreted: the CPU lowers its body to plain operations)."""
    return len(re.findall(
        rf"%{NAME}(?:\.\d+)? = [^\n]*custom-call\(", hlo_text))
