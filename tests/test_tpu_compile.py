"""Compile-only checks against a DESCRIBED TPU v5e (no chip attached).

The Mosaic compiler is installed here and compiles for a topology that
is described, not attached: what the chip's compiler would refuse, and
what it names, shows in seconds at no chip time.  Nothing runs, so
nothing here says a word about results or times.

All of these live in this ONE file: only one process at a time may load
the TPU's library and it keeps it until it exits, so the topology is
described inside a module-scoped fixture (never at import, never in a
``skipif`` or a ``parametrize`` argument) and every compile happens in
the test's own process.
"""

from __future__ import annotations

import importlib
import os
import re

import pytest

# the benchmark's reader finds the flash kernel's Mosaic calls by this
# pattern (benchmarks/harness/readers.py::FLASH_KERNEL)
FLASH_KERNEL = r"MultiHeadAttention|flash_attention"
FLASH_NAMES = ("flash_attention_fwd", "flash_attention_bwd_dq",
               "flash_attention_bwd_dkv")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """The kernels as the chip compiles them: the process's backend is
    the CPU, so the kernel modules would take their interpreter branch;
    and a compile for a described device cannot be read back from JAX's
    persistent cache, so the cache stays out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    for mod in ("flash_attention", "paged_attention"):
        monkeypatch.setattr(
            importlib.import_module(f"tpu_hc_bench.ops.{mod}"),
            "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _custom_calls(hlo_text: str) -> list[str]:
    """Names of the Mosaic kernels in a compiled program's text."""
    return re.findall(
        r"%(\S+) = [^\n]*custom-call\([^\n]*"
        r"custom_call_target=\"tpu_custom_call\"", hlo_text)


def test_flash_kernels_carry_their_names_in_a_gpt2_medium_layer(
        one_chip, mosaic):
    """Two layers of GPT-2 medium's widths at the train cell's batch,
    forward and backward: each layer issues exactly three Mosaic calls,
    each under a name of its own, and the benchmark's pattern matches
    those three and nothing else."""
    import jax
    import jax.numpy as jnp

    from tpu_hc_bench.models import gpt

    layers = 2
    model = gpt.GPTLM(hidden=1024, num_layers=layers, heads=16, ffn=4096,
                      dtype=jnp.bfloat16, attention_impl="flash")
    tokens = jax.ShapeDtypeStruct((16, 1024), jnp.int32, sharding=one_chip)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((16, 1024), jnp.int32), train=False))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        params)

    def loss(p, t):
        return model.apply(p, t, train=False).astype(jnp.float32).mean()

    text = jax.jit(jax.grad(loss)).lower(params, tokens).compile().as_text()
    calls = _custom_calls(text)
    matched = [c for c in calls if re.search(FLASH_KERNEL, c)]
    assert len(calls) == len(matched) == 3 * layers, calls
    for name in FLASH_NAMES:
        # "flash_attention_bwd_dq" must not count "..._bwd_dkv"
        assert sum(1 for c in calls
                   if re.search(name + r"(?![a-z])", c)) == layers, (
            name, calls)


def test_paged_kernel_carries_its_name(one_chip, mosaic):
    """The decode kernel over the chat cell's pool (24 layers, 16 KV
    heads, 641 pages of 16 tokens, 128 lanes), 16 rows."""
    import jax
    import jax.numpy as jnp

    pa = importlib.import_module("tpu_hc_bench.ops.paged_attention")

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sd((24, 16, 641, 16, 128), jnp.float32)
    text = jax.jit(
        lambda q, k, v, t, n: pa.paged_decode_attention(
            q, k, v, t, n, layer=3)
    ).lower(sd((16, 16, 64), jnp.float32), pool, pool,
            sd((16, 64), jnp.int32), sd((16,), jnp.int32)
            ).compile().as_text()
    calls = _custom_calls(text)
    assert len(calls) == 1 and "paged_attention" in calls[0], calls


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_gather_arm_holds_no_second_pool(one_chip, mosaic, program):
    """Two layers of GPT-2 medium's widths over the chat cell's pool
    geometry (16 KV heads, 641 pages of 16 tokens, 128 lanes; 16 rows,
    a 512-token prompt): the program the chip's compiler builds reads
    and writes the donated pool where it rests — no ``copy``, ``slice``,
    ``scatter`` or fusion makes another array of a pool leaf's or a
    layer's shape, and its temporaries stay under one leaf's bytes
    (the engine's ``kv_pool_temp_ratio``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_hc_bench.analysis import hlo
    from tpu_hc_bench.models import gpt
    from tpu_hc_bench.serve import decode

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    model = gpt.GPTLM(hidden=1024, num_layers=2, heads=16, ffn=4096,
                      dtype=jnp.float32)
    family = decode.build_family(model)
    params = jax.tree.map(
        lambda x: sd(x.shape, x.dtype),
        jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
            train=False))["params"])
    page, width, rows = 16, 40, 16
    leaf = (2, 16, 1 + rows * width, page, 128)
    kv = (sd(leaf, jnp.float32), sd(leaf, jnp.float32))
    if program == "decode":
        fn = decode.build_decode_fn(family, page, width)
        args = (sd((rows,), jnp.int32), sd((rows, width), jnp.int32),
                sd((rows,), jnp.int32), sd((rows,), jnp.bool_))
    else:
        fn = decode.build_prefill_fn(family, page, width)
        args = (sd((1, 512), jnp.int32), sd((), jnp.int32),
                sd((width,), jnp.int32))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, kv, *args).compile()
    found = hlo.new_buffers_of_shape(
        compiled.as_text(), [hlo.shape_text(leaf), hlo.shape_text(leaf[1:])])
    assert not found, [(i.name, i.opcode) for i in found]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 4 * np.prod(leaf), temp


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_hybrid_cache_tree_holds_no_second_copy_of_a_leaf(one_chip, mosaic,
                                                          program):
    """One period of the hybrid family (a softmax layer over pages, three
    delta-rule layers over state slots) at the published head sizes, a
    narrow hidden size and 16 rows: the programs the chip's compiler
    builds update the page pool AND the recurrent state ``S`` where they
    rest (no new array of either's shape; the small convolution tails
    are a row scatter, held to the bound on temporaries), and their
    temporaries stay under the largest leaf's bytes (the engine's
    ``kv_pool_temp_ratio``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_hc_bench.analysis import hlo
    from tpu_hc_bench.models import solar_open2
    from tpu_hc_bench.serve import decode

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    model = solar_open2.SolarOpen2LM(
        vocab_size=2048, hidden=512, heads=8, kv_heads=2, kda_heads=8,
        n_routed=16, experts_held=(0, 2), top_k=4, expert_ffn=256,
        shared_ffn=256, dtype=jnp.bfloat16)
    family = decode.build_family(model)
    params = jax.tree.map(
        lambda x: sd(x.shape, x.dtype),
        jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
            train=False))["params"])
    page, width, rows = 16, 40, 16
    kv = jax.tree.map(
        lambda x: sd(x.shape, x.dtype),
        jax.eval_shape(lambda: decode.init_kv_state(
            family, 1 + rows * width, page, jnp.bfloat16, slots=rows + 1)))
    if program == "decode":
        fn = decode.build_decode_fn(family, page, width)
        args = (sd((rows,), jnp.int32), sd((rows, width + 1), jnp.int32),
                sd((rows,), jnp.int32), sd((rows,), jnp.bool_))
    else:
        fn = decode.build_prefill_fn(family, page, width)
        args = (sd((1, 512), jnp.int32), sd((), jnp.int32),
                sd((width + 1,), jnp.int32))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, kv, *args).compile()
    leaves = jax.tree.leaves(kv)
    found = hlo.new_buffers_of_shape(
        compiled.as_text(),
        [hlo.shape_text(kv["pages"][0].shape, "bf16"),
         hlo.shape_text(kv["state"]["S"].shape, "f32")])
    # a leaf this small is moved whole into the chip's fast memory and
    # back (a result in memory space ``S(1)``, asynchronous ``copy-start``
    # / ``copy-done`` between the spaces): no re-layout, and at the
    # cell's size (GBs) it cannot happen; the bound on temporaries below
    # still holds it
    found = [i for i in found
             if i.opcode not in ("copy-start", "copy-done")
             and "S(1)}" not in i.text.split(" = ")[1].split(" ")[0]]
    assert not found, [(i.name, i.opcode) for i in found]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < max(np.prod(x.shape) * x.dtype.itemsize for x in leaves)
    parts = set(decode.part_of_ops(compiled.as_text()).values())
    assert parts == set(decode.PARTS)
