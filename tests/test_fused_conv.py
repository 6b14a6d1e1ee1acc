"""Fused BN-apply+relu+conv3x3 kernel: numerics vs the XLA composition.

Runs in Pallas interpreter mode on the CPU backend (same pattern as
tests/test_flash.py); the performance claims live in BASELINE.md's
round-3 table.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_hc_bench.ops import fused_conv


def _ref(y1, a, b, w):
    xn = jnp.maximum(y1.astype(jnp.float32) * a + b, 0.0).astype(y1.dtype)
    y2 = jax.lax.conv_general_dilated(
        xn, w, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32,
    ).astype(y1.dtype)
    yf = y2.astype(jnp.float32)
    return y2, yf.sum((0, 1, 2)), (yf * yf).sum((0, 1, 2))


def _inputs(b=4, h=8, cin=16, cout=16, dtype=jnp.float32, seed=0):
    k = jax.random.PRNGKey(seed)
    y1 = jax.random.normal(k, (b, h, h, cin), dtype)
    w = jax.random.normal(jax.random.fold_in(k, 1), (3, 3, cin, cout),
                          dtype) * 0.1
    a = (jnp.abs(jax.random.normal(jax.random.fold_in(k, 2), (cin,),
                                   jnp.float32)) * 0.5 + 0.5)
    bb = jax.random.normal(jax.random.fold_in(k, 3), (cin,),
                           jnp.float32) * 0.1
    return y1, a, bb, w


def test_forward_matches_xla():
    y1, a, b, w = _inputs()
    y_f, s1_f, s2_f = fused_conv.fused_bn_relu_conv(y1, a, b, w)
    y_r, s1_r, s2_r = _ref(y1, a, b, w)
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1_f), np.asarray(s1_r),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s2_f), np.asarray(s2_r),
                               rtol=1e-4, atol=1e-3)


def test_forward_grouped_batch():
    # small maps pack multiple images per program (G > 1)
    y1, a, b, w = _inputs(b=8, h=4, cin=8, cout=8, seed=1)
    assert fused_conv._pick_group(8, 16) > 1
    y_f, s1_f, s2_f = fused_conv.fused_bn_relu_conv(y1, a, b, w)
    y_r, s1_r, s2_r = _ref(y1, a, b, w)
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1_f), np.asarray(s1_r),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("use_stats", [False, True])
def test_grads_match_xla(use_stats):
    """custom_vjp vs autodiff of the XLA composition, with and without
    the stats outputs participating in the loss (the next-BN path)."""
    y1, a, b, w = _inputs(b=2, h=6, cin=8, cout=8, seed=2)

    def loss_fused(y1, a, b, w):
        y2, s1, s2 = fused_conv.fused_bn_relu_conv(y1, a, b, w)
        out = jnp.sum(y2 * jnp.cos(jnp.arange(y2.size).reshape(y2.shape)))
        if use_stats:
            out = out + jnp.sum(s1 * 0.3) + jnp.sum(s2 * 0.1)
        return out

    def loss_ref(y1, a, b, w):
        y2, s1, s2 = _ref(y1, a, b, w)
        out = jnp.sum(y2 * jnp.cos(jnp.arange(y2.size).reshape(y2.shape)))
        if use_stats:
            out = out + jnp.sum(s1 * 0.3) + jnp.sum(s2 * 0.1)
        return out

    g_f = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(y1, a, b, w)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(y1, a, b, w)
    for gf, gr, name in zip(g_f, g_r, ["dy1", "da", "db", "dw"]):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=2e-4, atol=2e-4,
            err_msg=name)


def test_eligibility_is_the_measured_win_region():
    el = fused_conv.eligible
    assert not el((128, 56, 56, 64), (3, 3), (1, 1), 64)    # stage 1
    assert el((128, 28, 28, 128), (3, 3), (1, 1), 128)      # stage 2
    assert el((128, 14, 14, 256), (3, 3), (1, 1), 256)      # stage 3
    assert not el((128, 7, 7, 512), (3, 3), (1, 1), 512)    # stage 4
    assert not el((128, 28, 28, 128), (3, 3), (2, 2), 128)  # strided
    assert not el((128, 28, 28, 128), (1, 1), (1, 1), 128)  # 1x1
