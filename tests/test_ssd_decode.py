"""``ops.ssd_decode_step`` (the Mamba-2 decode kernel, interpreted on the
CPU) against what it replaces: ``models/granite4h.ssd_step`` over every
slot of a layer, the rows' inputs scattered to slot order before it and
the read-out gathered back to row order after it."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_hc_bench.models import granite4h as gh
from tpu_hc_bench.ops import ssd_decode


def _inputs(shape, slots, seed):
    """A leaf of ``shape`` = (layers, slots, heads, P, N) and one row's
    inputs a slot of ``slots``; a row on slot 0 names no request (its
    ``dt`` is 0, as the decode program makes it)."""
    rng = np.random.default_rng(seed)
    _, _, heads, P, N = shape
    b = len(slots)
    f32 = lambda a: jnp.asarray(a, jnp.float32)           # noqa: E731
    slots = jnp.asarray(slots, jnp.int32)
    dt = jnp.where((slots > 0)[:, None],
                   f32(rng.uniform(1e-3, 0.1, (b, heads))), 0.0)
    return dict(h=f32(rng.standard_normal(shape)), slots=slots, dt=dt,
                x=f32(rng.standard_normal((b, heads, P))),
                B=f32(rng.standard_normal((b, N))),
                C=f32(rng.standard_normal((b, N))),
                A=-f32(rng.uniform(1.0, 16.0, heads)))


def _xla_step(t, layer):
    """The XLA step it replaces: scatter, every slot stepped, gather."""
    n_slots = t["h"].shape[1]

    def at(rows):
        return jnp.zeros((n_slots,) + rows.shape[1:],
                         rows.dtype).at[t["slots"]].set(rows)

    h_l, y = gh.ssd_step(t["h"][layer], at(t["x"]), at(t["B"]), at(t["C"]),
                         at(t["dt"]), t["A"])
    return t["h"].at[layer].set(h_l), y[t["slots"]], h_l


def _kernel_step(t, layer):
    return ssd_decode.ssd_decode_step(
        t["h"], layer, t["slots"], jnp.exp(t["dt"] * t["A"]),
        t["dt"][..., None] * t["x"], t["B"], t["C"])


# (layers, slots, heads, P, N), the rows' slots (0: a row that names no
# request), the layer stepped
CASES = {
    "tiny_rows_in_slot_order": ((2, 5, 4, 32, 16), [1, 2, 3, 4], 0),
    "tiny_any_order_inactive_between": ((3, 7, 4, 32, 16),
                                        [5, 0, 2, 0, 0, 6, 1], 2),
    "tiny_one_row": ((2, 3, 4, 32, 16), [2], 1),
    "published_widths": ((2, 4, 64, 64, 128), [2, 0, 3], 1),
    "published_inactive_first_and_last": ((3, 5, 64, 64, 128),
                                          [0, 3, 1, 4, 0], 2),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_is_the_xla_step_it_replaces(case):
    """``y`` of every active row within 1e-6 of the terms it sums (only
    the order of the 128-lane sum may differ), each named slot's new
    state within 1e-6, every slot and layer no active row names bitwise
    as it was, and the leaf the kernel returns is the operand it was
    handed (aliased)."""
    shape, slots, layer = CASES[case]
    t = _inputs(shape, slots, seed=len(case))
    h, y = _kernel_step(t, layer)
    want_h, want_y, h_l = _xla_step(t, layer)
    active = np.asarray(t["slots"]) > 0
    named = np.asarray(t["slots"])[active]

    terms = jnp.sum(jnp.abs(h_l[t["slots"]] * t["C"][:, None, None, :]),
                    axis=-1)
    err = jnp.abs(y - want_y) / terms
    assert float(jnp.max(err[active])) < 1e-6
    np.testing.assert_allclose(h[layer, named], want_h[layer, named],
                               rtol=1e-6, atol=1e-6)
    untouched = np.ones(shape[:2], bool)
    untouched[layer, named] = False
    np.testing.assert_array_equal(np.asarray(h)[untouched],
                                  np.asarray(t["h"])[untouched])

    # the kernel's own program: its first operand is the leaf, and the
    # pallas call hands that very operand back as its first result
    outer = jax.make_jaxpr(lambda h: _kernel_step(dict(t, h=h), layer))(
        t["h"]).jaxpr
    inner, = [e.params["jaxpr"].jaxpr for e in outer.eqns
              if "jaxpr" in e.params]
    call, = [e for e in inner.eqns if e.primitive.name == "pallas_call"]
    (src, dst), = call.params["input_output_aliases"]
    assert dst == 0 and call.invars[src] is inner.invars[0]


def test_kernel_calls_counts_the_compiled_kernels_alone():
    text = "\n".join([
        "  %ssd_decode.3 = (f32[2,4,8]{2,1,0}, f32[4]{0}) custom-call(%a)",
        "  %ssd_decode = (f32[2,4,8]{2,1,0}, f32[4]{0}) custom-call(%b)",
        "  %custom-call.4 = f32[4]{0} custom-call(%ssd_decode.3)",
        "  %fusion.2 = f32[4]{0} fusion(%ssd_decode), kind=kLoop"])
    assert ssd_decode.kernel_calls(text) == 2
