"""Hand-written TPU kernels (Pallas) for ops where stock XLA underperforms.

The reference delegates all kernels to MKL-DNN (SURVEY.md §2b #21); this
framework delegates to XLA:TPU and drops to Pallas only where measurement
shows a win.  The record (BASELINE.md):

- ``flash_attention`` — WINS from seq 512 up (50x at seq 8k): the
  production long-context path.
- ``xent`` — demoted: slower-or-parity at every measured vocab/seq
  (bert/gpt2/llama); kept as an experimental knob.
- ``fused_conv`` — whole-model parity (isolated-segment wins don't
  transfer); kept flag-gated as the recorded measurement apparatus.
- ``pool_bwd`` — recorded NULL (round 5): 1.6-4.4x slower than XLA's
  select-and-scatter on googlenet's pool shapes (the 9-tap VPU loop
  loses to the hardware window scan); kept as parity-tested apparatus,
  not wired into any model.
- ``paged_decode_attention`` — round 18: the serving lane's flash-decode
  kernel, K/V read directly through the int32 page tables (scalar
  prefetch + table-resolved block index maps, online softmax over
  pages, optional int8 pool with in-kernel per-page dequant).  Wired
  as ``--decode_attention=paged`` (serve lane).
- ``ssd_decode_step`` — the Mamba-2 decode step of one layer
  over a bucket's rows, each row's state block read from its slot,
  updated, read out and written back (the leaf aliased); the
  ``mamba2`` kind's decode step in ``serve.decode`` (no flag).
- ``kda_decode_step`` — the gated delta-rule ("KDA") decode step of
  one layer over a bucket's rows, the same frame as ``ssd_decode_step``
  (each row's block read from its slot, updated, read out and written
  back, the leaf aliased); the ``kda`` kind's decode step in
  ``serve.decode`` (no flag).
- ``fused_residual_norm`` — round 18: fused residual-add + Layer/RMS
  norm used by both paged decode families (one VMEM round-trip where
  the unfused form pays three HBM trips per layer).
"""

from tpu_hc_bench.ops.flash_attention import flash_attention  # noqa: F401
from tpu_hc_bench.ops.fused_conv import fused_bn_relu_conv  # noqa: F401
from tpu_hc_bench.ops.fused_residual_ln import fused_residual_norm  # noqa: F401
from tpu_hc_bench.ops.kda_decode import kda_decode_step  # noqa: F401
from tpu_hc_bench.ops.paged_attention import paged_decode_attention  # noqa: F401
from tpu_hc_bench.ops.pool_bwd import max_pool as pallas_max_pool  # noqa: F401
from tpu_hc_bench.ops.ssd_decode import ssd_decode_step  # noqa: F401
from tpu_hc_bench.ops.xent import softmax_xent, softmax_xent_reference  # noqa: F401
