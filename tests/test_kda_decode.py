"""``ops.kda_decode_step`` (the gated delta-rule decode kernel,
interpreted on the CPU) against what it replaces:
``models/solar_open2.kda_step`` over every slot of a layer, the rows'
inputs scattered to slot order before it and the read-out gathered back
to row order after it; and what holds its cost at set-up: one trace of
its body a decode program, whatever the program's KDA layers and the
heads."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_hc_bench.models import solar_open2 as so
from tpu_hc_bench.ops import kda_decode


def _inputs(shape, slots, seed):
    """A leaf of ``shape`` = (layers, slots, heads, d, d) and one row's
    inputs a slot of ``slots``, drawn as ``kda_inputs`` makes them; a row
    on slot 0 names no request (its ``g`` and ``beta`` are 0, as the
    decode program makes them)."""
    rng = np.random.default_rng(seed)
    _, _, heads, d, _ = shape
    b = len(slots)
    f32 = lambda a: jnp.asarray(a, jnp.float32)           # noqa: E731
    slots = jnp.asarray(slots, jnp.int32)
    active = (slots > 0)[:, None]
    return dict(
        S=f32(rng.standard_normal(shape)), slots=slots,
        q=so._l2norm(f32(rng.standard_normal((b, heads, d)))) / d ** 0.5,
        k=so._l2norm(f32(rng.standard_normal((b, heads, d)))),
        v=f32(rng.standard_normal((b, heads, d))),
        g=jnp.where(active[..., None],
                    -f32(rng.uniform(0.0, 1.0, (b, heads, d))), 0.0),
        beta=jnp.where(active, f32(rng.uniform(0.0, 2.0, (b, heads))), 0.0))


def _xla_step(t, layer):
    """The XLA step it replaces: scatter, every slot stepped, gather."""
    n_slots = t["S"].shape[1]

    def at(rows):
        return jnp.zeros((n_slots,) + rows.shape[1:],
                         rows.dtype).at[t["slots"]].set(rows)

    s_l, o = so.kda_step(t["S"][layer], at(t["q"]), at(t["k"]), at(t["v"]),
                         at(t["g"]), at(t["beta"]))
    return t["S"].at[layer].set(s_l), o[t["slots"]], s_l


def _kernel_step(t, layer):
    return kda_decode.kda_decode_step(
        t["S"], layer, t["slots"], jnp.exp(t["g"]), t["k"], t["q"], t["v"],
        t["beta"])


# (layers, slots, heads, d, d), the rows' slots (0: a row that names no
# request), the layer stepped
CASES = {
    "tiny_rows_in_slot_order": ((2, 5, 4, 16, 16), [1, 2, 3, 4], 0),
    "tiny_any_order_inactive_between": ((3, 7, 4, 16, 16),
                                        [5, 0, 2, 0, 0, 6, 1], 2),
    "tiny_one_row": ((2, 3, 4, 16, 16), [2], 1),
    "twelve_heads_odd_groups": ((2, 6, 12, 16, 16), [4, 0, 1, 3], 1),
    "narrow_heads_published_d": ((3, 6, 8, 128, 128), [3, 0, 5, 1, 0], 2),
    "published_widths": ((2, 4, 64, 128, 128), [2, 0, 3], 1),
    "published_inactive_first_and_last": ((3, 5, 64, 128, 128),
                                          [0, 3, 1, 4, 0], 0),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_is_the_xla_step_it_replaces(case):
    """``o`` of every active row within 1e-6 of the terms it sums (only
    the order of the sums over ``d_k`` may differ), each named slot's new
    state within 1e-6, every slot and layer no active row names bitwise
    as it was, and the leaf the kernel returns is the operand it was
    handed (aliased)."""
    shape, slots, layer = CASES[case]
    t = _inputs(shape, slots, seed=len(case))
    S, o = _kernel_step(t, layer)
    want_S, want_o, s_l = _xla_step(t, layer)
    active = np.asarray(t["slots"]) > 0
    named = np.asarray(t["slots"])[active]

    terms = jnp.sum(jnp.abs(s_l[t["slots"]] * t["q"][..., None]), axis=-2)
    err = jnp.abs(o - want_o) / terms
    assert float(jnp.max(err[active])) < 1e-6
    np.testing.assert_allclose(S[layer, named], want_S[layer, named],
                               rtol=1e-6, atol=1e-6)
    untouched = np.ones(shape[:2], bool)
    untouched[layer, named] = False
    np.testing.assert_array_equal(np.asarray(S)[untouched],
                                  np.asarray(t["S"])[untouched])

    # the kernel's own program: its state operand is the leaf, and the
    # pallas call hands that very operand back as its first result
    outer = jax.make_jaxpr(lambda S: _kernel_step(dict(t, S=S), layer))(
        t["S"]).jaxpr
    inner, = [e.params["jaxpr"].jaxpr for e in outer.eqns
              if "jaxpr" in e.params]
    call, = [e for e in inner.eqns if e.primitive.name == "pallas_call"]
    (src, dst), = call.params["input_output_aliases"]
    assert dst == 0 and call.invars[src] is inner.invars[0]


def test_kernel_calls_counts_the_compiled_kernels_alone():
    text = "\n".join([
        "  %kda_decode.3 = (f32[2,4,8]{2,1,0}, f32[4]{0}) custom-call(%a)",
        "  %kda_decode = (f32[2,4,8]{2,1,0}, f32[4]{0}) custom-call(%b)",
        "  %ssd_decode = (f32[2,4,8]{2,1,0}, f32[4]{0}) custom-call(%c)",
        "  %custom-call.4 = f32[4]{0} custom-call(%kda_decode.3)",
        "  %fusion.2 = f32[4]{0} fusion(%kda_decode), kind=kLoop"])
    assert kda_decode.kernel_calls(text) == 2


@pytest.fixture
def counted_body(monkeypatch):
    """The kernel's body wrapped in a counter of its traces, with the
    jitted call's own cache emptied before and after."""
    seen = []
    body = kda_decode._kernel

    def counting(*refs):
        seen.append(refs[-2].shape)             # the VMEM blocks
        return body(*refs)

    monkeypatch.setattr(kda_decode, "_kernel", counting)
    kda_decode.kda_decode_step.clear_cache()
    yield seen
    kda_decode.kda_decode_step.clear_cache()


def test_a_decode_program_traces_the_body_once_for_all_its_layers(
        counted_body):
    """Lowering the tiny Solar preset's decode program at each of its
    buckets traces the kernel's body once a bucket, not once a bucket
    and KDA layer (3): the layers share one lowering, as the chip's
    warm set-up pays it for each decode program."""
    from tpu_hc_bench.models import solar_open2
    from tpu_hc_bench.serve import decode

    model = solar_open2.solar_open2_tiny()
    family = decode.build_family(model)
    assert len(family.state_layers) == 3
    page, width, buckets = 4, 8, (1, 2, 4, 8)
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"]
    kv = jax.eval_shape(lambda: decode.init_kv_state(
        family, 1 + 8 * width, page, jnp.float32, slots=9))
    fn = jax.jit(decode.build_decode_fn(family, page, width),
                 donate_argnums=(1,))
    sd = jax.ShapeDtypeStruct
    for n, b in enumerate(buckets, 1):
        fn.lower(params, kv, sd((b,), jnp.int32),
                 sd((b, width + 1), jnp.int32), sd((b,), jnp.int32),
                 sd((b,), jnp.bool_))
        assert len(counted_body) == n
    # the leaf's own buffer size is the blocks', whatever the bucket
    assert set(counted_body) == {(kda_decode._ROWS,) + kv["state"]["S"]
                                 .shape[2:]}


def _equations(jaxpr) -> int:
    """Equations of a jaxpr and of every jaxpr inside its equations."""
    n = 0
    for e in jaxpr.eqns:
        n += 1
        for p in e.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    n += _equations(sub)
    return n


def test_the_body_is_the_same_size_at_any_head_count():
    """The heads are a loop in the body, not unrolled in Python: its
    jaxpr holds as many equations at 64 heads as at 8 (and so does the
    Mosaic module each decode program lowers)."""
    def body_size(heads):
        sd = jax.ShapeDtypeStruct
        b, d = 4, 128
        vec = sd((b, heads, d), jnp.float32)
        outer = jax.make_jaxpr(kda_decode.kda_decode_step)(
            sd((3, 9, heads, d, d), jnp.float32), sd((), jnp.int32),
            sd((b,), jnp.int32), vec, vec, vec, vec,
            sd((b, heads), jnp.float32)).jaxpr
        inner, = [e.params["jaxpr"].jaxpr for e in outer.eqns
                  if "jaxpr" in e.params]
        call, = [e for e in inner.eqns if e.primitive.name == "pallas_call"]
        return _equations(call.params["jaxpr"])

    assert body_size(8) == body_size(64) == body_size(32)
