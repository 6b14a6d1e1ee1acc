"""ResNet family in Flax — the reference's flagship workload.

The reference pins ``MODEL=resnet50`` (``run-tf-sing-ucx-openmpi.sh:34``)
and drives tf_cnn_benchmarks' ResNet-50 v1.5 implementation (the variant
with stride 2 on the 3x3 conv of the downsampling bottleneck) on 224x224
ImageNet in NCHW for MKL-DNN.  This is a fresh TPU-first implementation:

- NHWC only: channels on the 128-lane minor axis is what the MXU tiles
  (the launcher's ``--data_format=NCHW`` is translated by flags.resolve).
- Parameterized compute dtype: fp32 for reference parity, bf16 for the TPU
  fast path; parameters and BN statistics stay fp32 either way.
- BatchNorm uses *local* batch statistics per data-parallel worker, which is
  exactly Horovod DP semantics (each rank normalizes over its own
  per-worker batch; only gradients are allreduced).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


class BottleneckBlock(nn.Module):
    """ResNet-v1.5 bottleneck: 1x1 -> 3x3(stride) -> 1x1, projection shortcut."""

    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        # v1.5: stride lives on the 3x3, not the 1x1
        y = self.conv(self.filters, (3, 3), strides=(self.strides, self.strides))(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * 4, (1, 1), strides=(self.strides, self.strides),
                name="shortcut_conv",
            )(residual)
            residual = self.norm(name="shortcut_bn")(residual)
        return self.act(residual + y)


class BasicBlock(nn.Module):
    """Two-3x3 block for ResNet-18/34."""

    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), strides=(self.strides, self.strides))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters, (1, 1), strides=(self.strides, self.strides),
                name="shortcut_conv",
            )(residual)
            residual = self.norm(name="shortcut_bn")(residual)
        return self.act(residual + y)


def _bn_scale_shift(mdl, x, stats, momentum, epsilon, use_running_average):
    """BatchNorm folded to per-channel scale/shift ``(a, b)``.

    Creates the scale/bias params and running-stat variables ON ``mdl``
    (so callers keep nn.BatchNorm's variable layout), derives batch
    statistics from ``stats=(sum, sumsq)`` when given (the fused kernel's
    epilogue) or by reducing ``x``, and updates the running averages in
    train mode.  The ONE home of this logic for both fused-BN modules.
    """
    c = x.shape[-1]
    scale = mdl.param("scale", nn.initializers.ones, (c,), jnp.float32)
    bias = mdl.param("bias", nn.initializers.zeros, (c,), jnp.float32)
    ra_mean = mdl.variable("batch_stats", "mean",
                           lambda s: jnp.zeros(s, jnp.float32), (c,))
    ra_var = mdl.variable("batch_stats", "var",
                          lambda s: jnp.ones(s, jnp.float32), (c,))
    if use_running_average:
        mean, var = ra_mean.value, ra_var.value
    else:
        if stats is None:
            xf = x.astype(jnp.float32)
            mean = xf.mean((0, 1, 2))
            var = (xf * xf).mean((0, 1, 2)) - mean * mean
        else:
            s1, s2 = stats
            n = x.shape[0] * x.shape[1] * x.shape[2]
            mean = s1 / n
            var = s2 / n - mean * mean
        if not mdl.is_initializing():
            ra_mean.value = (momentum * ra_mean.value
                             + (1.0 - momentum) * mean)
            ra_var.value = (momentum * ra_var.value
                            + (1.0 - momentum) * var)
    a = scale * jax.lax.rsqrt(var + epsilon)
    b = bias - mean * a
    return a, b


class FusedBNReluConv3x3(nn.Module):
    """BatchNorm(input) -> relu -> 3x3 conv as ONE Pallas pass.

    Round-3 kernel (`ops/fused_conv.py`): at stage-2/3 shapes XLA does not
    fuse the BN-apply+relu into the conv's input read (measured 35% slower
    than the fused kernel, BASELINE.md round-3 table), so this module owns
    the input's BN params/running stats AND the conv kernel and emits the
    fused call where `fused_conv.eligible` says it wins; everywhere else
    (strided blocks, stage-1/4 shapes, tiny test images) it emits the
    identical XLA composition.  Returns ``(y, (sum, sumsq))`` — the
    epilogue's per-channel stats of y, consumed by the NEXT BatchNorm so
    no extra pass over y is ever made.
    """

    features: int
    strides: int = 1
    use_running_average: bool = False
    dtype: Any = jnp.float32
    momentum: float = 0.9
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x):
        from tpu_hc_bench.ops import fused_conv as fc

        cin = x.shape[-1]
        a, b = _bn_scale_shift(self, x, None, self.momentum, self.epsilon,
                               self.use_running_average)
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (3, 3, cin, self.features), jnp.float32)
        w = kernel.astype(self.dtype)
        if fc.eligible(x.shape, (3, 3), self.strides, cin):
            y, s1, s2 = fc.fused_bn_relu_conv(x, a, b, w)
        else:
            # same-dtype conv (like nn.Conv: MXU accumulates f32
            # internally, output in compute dtype) — a f32-preferred
            # output here would make autodiff transpose the conv with a
            # f32 cotangent against bf16 operands, which lax rejects
            xn = jnp.maximum(
                x.astype(jnp.float32) * a + b, 0.0).astype(self.dtype)
            y = jax.lax.conv_general_dilated(
                xn, w, (self.strides, self.strides), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            yf = y.astype(jnp.float32)
            s1 = yf.sum((0, 1, 2))
            s2 = (yf * yf).sum((0, 1, 2))
        return y, (s1, s2)


class StatsBatchNorm(nn.Module):
    """BatchNorm that consumes precomputed ``(sum, sumsq)`` stats (the
    fused kernel's epilogue) instead of re-reducing its input; same
    variable layout and running-stat semantics as ``nn.BatchNorm``."""

    use_running_average: bool = False
    dtype: Any = jnp.float32
    momentum: float = 0.9
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x, stats=None):
        a, b = _bn_scale_shift(self, x, stats, self.momentum, self.epsilon,
                               self.use_running_average)
        return (x.astype(jnp.float32) * a + b).astype(self.dtype)


class FusedBottleneckBlock(nn.Module):
    """BottleneckBlock with the BN1-relu-conv3x3 segment fused (Pallas)
    and BN2 fed from the kernel's stats epilogue.  Same math as
    ``BottleneckBlock`` (pinned by tests/test_fused_conv_model.py); the
    param tree differs (the fused module owns bn1+conv2 jointly), so
    checkpoints do not interchange with the unfused layout.
    """

    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    use_running_average: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y, st2 = FusedBNReluConv3x3(
            self.filters, strides=self.strides,
            use_running_average=self.use_running_average, dtype=self.dtype,
        )(y)
        y = self.act(StatsBatchNorm(
            use_running_average=self.use_running_average, dtype=self.dtype,
        )(y, stats=st2))
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * 4, (1, 1), strides=(self.strides, self.strides),
                name="shortcut_conv",
            )(residual)
            residual = self.norm(name="shortcut_bn")(residual)
        return self.act(residual + y)


class PreactBottleneckBlock(nn.Module):
    """ResNet-v2 bottleneck (He 2016 full preactivation): BN-relu precede
    every conv, identity carries no norm/act.  tf_cnn_benchmarks exposes
    these as ``resnet50_v2``/``101_v2``/``152_v2``."""

    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        preact = self.act(self.norm()(x))
        out_ch = self.filters * 4
        if x.shape[-1] != out_ch or self.strides != 1:
            residual = self.conv(
                out_ch, (1, 1), strides=(self.strides, self.strides),
                name="shortcut_conv",
            )(preact)
        else:
            residual = x
        y = self.conv(self.filters, (1, 1))(preact)
        y = self.act(self.norm()(y))
        y = self.conv(self.filters, (3, 3), strides=(self.strides, self.strides))(y)
        y = self.act(self.norm()(y))
        y = self.conv(out_ch, (1, 1))(y)
        return residual + y


class ResNet(nn.Module):
    """ImageNet ResNet, NHWC, parameterized depth and dtype."""

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.float32
    preact: bool = False                # v2: BN-relu inside blocks only
    fused_conv: bool = False            # round 3: Pallas fused
                                        # BN-relu-conv3x3 bottleneck segment
                                        # (ops/fused_conv.py win region)
    space_to_depth: bool = False        # pack 2x2 blocks into channels and
                                        # run the stem as a 4x4/s1 conv — the
                                        # standard TPU stem transform (3-ch
                                        # 7x7/s2 convs map poorly to the MXU)
    barrier: str = "none"               # fusion-split experiment knob:
                                        # pre  = barrier conv-out -> BN-in
                                        # post = barrier BN-out -> act/conv
                                        # both = both edges

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = functools.partial(
            nn.Conv, use_bias=False, dtype=self.dtype, padding="SAME"
        )
        norm = functools.partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.dtype,
        )
        def barriered(factory):
            # factory -> factory whose modules emit through an
            # optimization_barrier, splitting the fusion at that edge
            # (e.g. conv-backward from the BN-stat reductions XLA would
            # fuse into it — the round-1 ~43%-MXU-efficiency pattern)
            def make(*a, **k):
                m = factory(*a, **k)
                return lambda y: jax.lax.optimization_barrier(m(y))
            return make

        if self.barrier in ("pre", "both"):
            conv = barriered(conv)
        if self.barrier in ("post", "both"):
            norm = barriered(norm)
        act = nn.relu

        x = x.astype(self.dtype)
        if self.space_to_depth:
            # [N, 2h, 2w, c] -> [N, h, w, 4c]; the 7x7/s2 stem conv becomes a
            # 4x4/s1 conv over the packed image whose kernel rows/cols
            # interleave the (zero-padded-to-8x8) 7x7 weights.  Same math,
            # one quarter the spatial positions, 4x the contraction depth.
            n, h, w, c = x.shape
            x = x.reshape(n, h // 2, 2, w // 2, 2, c)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2, 4 * c)
            # padding ((1,2),(1,2)) in packed space reproduces SAME padding
            # (2 before, 3 after) of the 7x7/s2 conv at even input sizes
            x = conv(
                self.num_filters, (4, 4), padding=((1, 2), (1, 2)),
                name="conv_init_s2d",
            )(x)
        else:
            x = conv(self.num_filters, (7, 7), strides=(2, 2),
                     name="conv_init")(x)
        if not self.preact:
            x = act(norm(name="bn_init")(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        block_cls, extra = self.block_cls, {}
        if self.fused_conv:
            if self.block_cls is not BottleneckBlock:
                raise ValueError(
                    "fused_conv applies to the v1 bottleneck family "
                    "(resnet50/101/152) only")
            block_cls = FusedBottleneckBlock
            extra = dict(use_running_average=not train, dtype=self.dtype)
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = 2 if i > 0 and j == 0 else 1
                x = block_cls(
                    filters=self.num_filters * 2**i,
                    strides=strides,
                    conv=conv,
                    norm=norm,
                    act=act,
                    **extra,
                )(x)
        if self.preact:
            x = act(norm(name="bn_final")(x))
        x = jnp.mean(x, axis=(1, 2))  # global average pool over H,W
        x = nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)
        return x.astype(jnp.float32)


def _family(stages, block, preact=False):
    def create(num_classes=1000, dtype=jnp.float32, space_to_depth=False,
               fused_conv=False):
        return ResNet(stages, block, num_classes=num_classes, dtype=dtype,
                      preact=preact, space_to_depth=space_to_depth,
                      fused_conv=fused_conv)
    return create


resnet18 = _family([2, 2, 2, 2], BasicBlock)
resnet34 = _family([3, 4, 6, 3], BasicBlock)
resnet50 = _family([3, 4, 6, 3], BottleneckBlock)
resnet101 = _family([3, 4, 23, 3], BottleneckBlock)
resnet152 = _family([3, 8, 36, 3], BottleneckBlock)
resnet50_v2 = _family([3, 4, 6, 3], PreactBottleneckBlock, preact=True)
resnet101_v2 = _family([3, 4, 23, 3], PreactBottleneckBlock, preact=True)
resnet152_v2 = _family([3, 8, 36, 3], PreactBottleneckBlock, preact=True)
