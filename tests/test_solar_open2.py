"""The hybrid linear/softmax-attention MoE family (``models/solar_open2``)
on the serve lane's normal path, at the tiny preset on the CPU with
seeded random weights: the chunked prefill against the token-by-token
recurrence, prefill then decode through both pools against the
benchmark's plain reference, the share test of the expert layer, slot
hygiene, and what the engine refuses for this family."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from tpu_hc_bench.models import moe as moe_mod  # noqa: E402
from tpu_hc_bench.models import solar_open2 as so  # noqa: E402
from tpu_hc_bench.serve import decode as decode_mod  # noqa: E402

PAGE, WIDTH = 4, 8


def _recurrence_inputs(s, heads=3, d=16, seed=1):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape),   # noqa: E731
                                   jnp.float32)
    q = so._l2norm(f(s, heads, d)) / d ** 0.5
    k = so._l2norm(f(s, heads, d))
    g = -jnp.asarray(rng.uniform(0, 1.5, (s, heads, d)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, (s, heads)), jnp.float32)
    return q, k, f(s, heads, d), g, beta, f(heads, d, d)


def _token_by_token(q, k, v, g, beta, S0):
    def body(S, x):
        return so.kda_step(S, *x)

    S, o = jax.lax.scan(body, S0, (q, k, v, g, beta))
    return o, S


@pytest.mark.parametrize("s,chunk", [(48, 48), (48, 16), (48, 8), (40, 16),
                                     (7, 64)])
def test_chunked_prefill_equals_the_recurrence(s, chunk):
    """State and outputs, one to six chunks, a length that is no
    multiple of the chunk (``kda_sequence`` pads with inert positions),
    from a state that is not zero."""
    x = _recurrence_inputs(s)
    o_ref, S_ref = _token_by_token(*x)
    o, S = so.kda_sequence(*x, chunk=chunk)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(S, S_ref, atol=2e-5)


def test_padded_positions_are_inert():
    """beta = 0 and g = 0 past the prompt: the state after a padded
    bucket is the state after the prompt alone."""
    q, k, v, g, beta, S0 = _recurrence_inputs(32)
    n = 19
    valid = jnp.arange(32) < n
    _, S_pad = so.kda_chunked(
        q, k, v, jnp.where(valid[:, None, None], g, 0.0),
        jnp.where(valid[:, None], beta, 0.0), S0, chunk=16)
    _, S_ref = _token_by_token(q[:n], k[:n], v[:n], g[:n], beta[:n], S0)
    np.testing.assert_allclose(S_pad, S_ref, atol=2e-5)


# ---------------------------------------------------------------------
# the programs against the benchmark's plain reference


@pytest.fixture(scope="module")
def tiny():
    """The tiny configuration's weights on both sides: the reference's
    tree and the program's, from one seed; float32 (the CPU multiplies
    float32 as float32, so both sides compute the same arithmetic)."""
    from harness import spec
    from families import solar_open2 as fam

    cfg = fam.tiny_config(spec.config_of(spec.load_benchmark(),
                                         "solar_open2_250b_ep8"))
    ref = fam.reference
    z = ref.sizes(cfg)
    to32 = lambda t: jax.tree.map(                      # noqa: E731
        lambda x: x.astype(jnp.float32), t)
    model = so.SolarOpen2LM(
        vocab_size=z["V"], hidden=z["H"], num_layers=z["L"],
        heads=z["heads"], kv_heads=z["kvh"], head_dim=z["d"],
        gqa_layers=z["gqa"], kda_heads=z["kh"], kda_head_dim=z["kd"],
        gate_rank=z["r"], n_routed=z["E"],
        experts_held=(0, z["Eh"]), top_k=z["k"], expert_ffn=z["F"],
        shared_ffn=z["Fs"], dtype=jnp.float32)
    params = to32(fam.program_tree(ref.leaf_values(cfg, 7), cfg))
    return {"cfg": cfg, "ref": ref, "model": model, "params": params,
            "ref_params": to32(ref.make_params(cfg, 7)),
            "family": decode_mod.build_family(model)}


def _programs(t, slots=4, pages=1 + 3 * WIDTH):
    fam = t["family"]
    kv = decode_mod.init_kv_state(fam, pages, PAGE, jnp.float32,
                                  slots=slots)
    return (kv, jax.jit(decode_mod.build_prefill_fn(fam, PAGE, WIDTH)),
            jax.jit(decode_mod.build_decode_fn(fam, PAGE, WIDTH)))


def _table(first_page, slot):
    return np.array(list(range(first_page, first_page + WIDTH)) + [slot],
                    np.int32)


def _serve_one(t, kv, prefill, decode, toks, plen, table, bucket=16,
               row=1, rows=2):
    """Prefill ``toks[:plen]`` then decode the rest one token a step in
    row ``row`` of a ``rows``-row bucket; returns the logits of every
    position from ``plen - 1`` on, and the cache."""
    pad = np.zeros((1, bucket), np.int32)
    pad[0, :plen] = toks[:plen]
    _, lg, kv = prefill(t["params"], kv, pad, np.int32(plen), table)
    out = [np.asarray(lg[0])]
    for n in range(plen, len(toks)):
        tables = np.zeros((rows, WIDTH + 1), np.int32)
        tables[row] = table
        feed = np.zeros((rows,), np.int32)
        feed[row] = toks[n]
        lengths = np.zeros((rows,), np.int32)
        lengths[row] = n
        on = np.zeros((rows,), bool)
        on[row] = True
        _, lg, kv = decode(t["params"], kv, feed, tables, lengths, on)
        out.append(np.asarray(lg[row]))
    return np.stack(out), kv


@pytest.mark.parametrize("plen", [5, 11, 16])
def test_prefill_then_decode_equals_the_references_full_forward(tiny, plen):
    """Through both pools (pages for the softmax layer, a slot for the
    delta-rule layers) against the plain reference's one pass over the
    whole sequence, on logits; a prompt shorter than its bucket and one
    that fills it."""
    t = tiny
    toks = np.random.default_rng(plen).integers(
        1, 256, 22).astype(np.int32)
    kv, prefill, decode = _programs(t)
    got, _ = _serve_one(t, kv, prefill, decode, toks, plen, _table(1, 2))
    h = t["ref"].hidden_states(t["ref_params"], toks[None], t["cfg"], "f32")
    want = np.asarray(t["ref"].logits_of(t["ref_params"], h[0], "f32"))
    np.testing.assert_allclose(got, want[plen - 1:], atol=2e-4)


def test_models_own_forward_equals_the_reference(tiny):
    t = tiny
    toks = np.random.default_rng(3).integers(1, 256, (2, 21)).astype(
        np.int32)
    got = t["model"].apply({"params": t["params"]}, jnp.asarray(toks),
                           train=False)
    h = t["ref"].hidden_states(t["ref_params"], toks, t["cfg"], "f32")
    want = t["ref"].logits_of(t["ref_params"], h, "f32")
    np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("dirty", ["slot_reused_after_finish",
                                   "slot_reused_after_preempt",
                                   "trash_slot_written"])
def test_a_slot_leaves_no_trace_in_another_requests_logits(tiny, dirty):
    """A request's logits are bitwise what a fresh cache gives, whatever
    its slot held before (another request's finished state, a preempted
    residency of its own) and whatever inactive rows wrote to the trash
    slot meanwhile."""
    t = tiny
    rng = np.random.default_rng(11)
    toks = rng.integers(1, 256, 20).astype(np.int32)
    other = rng.integers(1, 256, 20).astype(np.int32)
    kv, prefill, decode = _programs(t)
    clean, _ = _serve_one(t, kv, prefill, decode, toks, 9, _table(1, 1))
    kv, _, _ = _programs(t)
    if dirty == "slot_reused_after_finish":
        _, kv = _serve_one(t, kv, prefill, decode, other, 12, _table(1, 1))
    elif dirty == "slot_reused_after_preempt":
        # a residency of the same request cut short, then a re-prefill
        # of prompt + prefix into the same slot
        _, kv = _serve_one(t, kv, prefill, decode, toks[:14], 9,
                           _table(1, 1))
    else:
        # rows 0 and 2 inactive beside it: they name slot 0 and page 0
        _, kv = _serve_one(t, kv, prefill, decode, other, 12,
                           _table(9, 2), rows=4)
        assert float(jnp.abs(kv["state"]["S"][:, 0]).max()) == 0.0
    again, kv = _serve_one(t, kv, prefill, decode, toks, 9, _table(1, 1))
    np.testing.assert_array_equal(again, clean)
    if dirty == "slot_reused_after_preempt":
        resumed, _ = _serve_one(t, kv, prefill, decode, toks, 14,
                                _table(1, 1))
        np.testing.assert_allclose(resumed, clean[5:], atol=2e-4)


@pytest.mark.parametrize("dense_rows", [0, 256])
def test_the_eight_shares_add_up_to_the_uncut_layer(tiny, dense_rows,
                                                    monkeypatch):
    """Guide section 4's share test: the routed parts of the 8 shares
    (2 of 16 experts each) plus the shared expert counted once = the
    layer that holds every expert; through the grouped matmuls and
    through every held expert over every row."""
    monkeypatch.setattr(moe_mod, "DENSE_ROWS", dense_rows)
    t = tiny
    z = t["ref"].sizes(t["cfg"])
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 9, z["H"])), jnp.float32)

    def layer(held, shared):
        return moe_mod.MoEFFN(
            z["H"], z["F"], z["E"], top_k=z["k"], impl="ragged",
            score="sigmoid", gated=True, shared_ffn=shared,
            experts_held=held)

    whole = layer((0, z["E"]), z["Fs"])
    p = whole.init(jax.random.PRNGKey(0), x)["params"]
    p = dict(p, router_bias=jnp.asarray(
        rng.normal(size=(z["E"],)) * 0.1, jnp.float32))
    want = whole.apply({"params": p}, x)
    share = z["E"] // 8
    routed = sum(
        layer((lo, lo + share), 0).apply(
            {"params": {k: (v[lo:lo + share] if k in ("wg", "wi", "wo")
                            else v)
                        for k, v in p.items() if "shared" not in k}}, x)
        for lo in range(0, z["E"], share))
    shared_only = want - layer((0, z["E"]), 0).apply(
        {"params": {k: v for k, v in p.items() if "shared" not in k}}, x)
    np.testing.assert_allclose(routed + shared_only, want, atol=1e-5)
    assert float(jnp.abs(routed).max()) > 1e-3


def _share_layer(gated=True, held=(4, 10)):
    return moe_mod.MoEFFN(32, 24, 16, top_k=4, impl="ragged",
                          score="sigmoid", gated=gated, shared_ffn=24,
                          experts_held=held)


@pytest.mark.parametrize("gated,held,rows", [
    (True, (4, 10), (2, 9)), (True, (0, 16), (16, 1)),
    (False, (4, 10), (1, 4)), (True, (14, 16), (3, 5))])
def test_every_expert_over_every_row_is_the_grouped_share(
        gated, held, rows, monkeypatch):
    """The dense arm of the held share against the grouped matmuls: the
    same output and the same sown picks, gated and two-matrix experts,
    a share in the middle, at the end and the whole layer."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=rows + (32,)), jnp.float32)
    layer = _share_layer(gated, held)
    monkeypatch.setattr(moe_mod, "DENSE_ROWS", 0)
    p = layer.init(jax.random.PRNGKey(3), x)["params"]
    p = dict(p, router_bias=jnp.asarray(rng.normal(size=(16,)) * 0.1,
                                        jnp.float32))
    want, sown = layer.apply({"params": p}, x, mutable=["stats"])
    monkeypatch.setattr(moe_mod, "DENSE_ROWS", 256)
    got, sown_d = layer.apply({"params": p}, x, mutable=["stats"])
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(jnp.abs(want).max()) > 1e-3
    np.testing.assert_array_equal(sown_d["stats"]["picks_held"][0],
                                  sown["stats"]["picks_held"][0])


@pytest.mark.parametrize("rows,dense_rows,grouped", [
    (3, 256, True),       # 3 x 4 picks < 16 experts: some stay empty
    (4, 256, False),      # every expert expects a row
    (256, 256, False), (257, 256, True), (64, 0, True)])
def test_the_share_arm_follows_the_shapes_alone(rows, dense_rows,
                                                grouped, monkeypatch):
    monkeypatch.setattr(moe_mod, "DENSE_ROWS", dense_rows)
    layer = _share_layer()
    x = jnp.zeros((rows, 1, 32), jnp.float32)
    p = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x))
    text = str(jax.make_jaxpr(
        lambda p, x: layer.apply(p, x, mutable=["stats"]))(p, x))
    assert ("ragged_dot" in text) == grouped


def test_sigmoid_routing_bias_moves_the_choice_and_not_the_weight():
    scores = jnp.asarray([[0.9, 0.8, 0.1, 0.2]], jnp.float32)
    idx, gates = moe_mod.sigmoid_topk(scores, jnp.zeros(4), 2)
    assert sorted(idx[0].tolist()) == [0, 1]
    np.testing.assert_allclose(float(gates.sum()), 1.0, atol=1e-6)
    idx, gates = moe_mod.sigmoid_topk(
        scores, jnp.asarray([0.0, -1.0, 0.0, 0.0]), 2, normalize=False)
    assert sorted(idx[0].tolist()) == [0, 3]
    np.testing.assert_allclose(sorted(gates[0].tolist()), [0.2, 0.9],
                               atol=1e-6)


def test_softmax_moe_refuses_the_sigmoid_paths_fields():
    """The share path's fields take either score since softmax-routed
    gated experts joined it; what it still refuses is a dispatch that
    drops tokens."""
    with pytest.raises(ValueError, match="ragged"):
        moe_mod.MoEFFN(8, 16, 4, experts_held=(0, 2)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2, 8)))


# ---------------------------------------------------------------------
# the engine


def _cfg(**kw):
    from tpu_hc_bench import flags

    base = dict(model="solar_open2_tiny", workload="serve",
                arrival_rate=1000.0, num_requests=10, max_prompt_len=16,
                max_output_len=6, max_in_flight=4, kv_page_size=4, seed=0)
    base.update(kw)
    return flags.BenchmarkConfig(**base).resolve()


@pytest.fixture(scope="module")
def engine():
    from tpu_hc_bench.serve import engine as engine_mod

    return engine_mod.ServeEngine(_cfg(), print_fn=lambda m: None)


@pytest.fixture(scope="module")
def requests(engine):
    from tpu_hc_bench.serve import arrivals

    return arrivals.build_requests(engine.cfg, engine.spec.vocab_size)


def _run(engine, requests, **kw):
    from tpu_hc_bench.serve import engine as engine_mod

    class Keep:
        enabled = False
        out_dir = None
        last_record = None

        def __init__(self):
            self.records = []

        def event(self, kind, **f):
            self.records.append({"kind": kind, **f})

        def close(self):
            pass

    w = Keep()
    summary = engine.run(requests, writer=w,
                         clock=engine_mod.VirtualClock(
                             {"prefill": 0.004, "decode": 0.003}), **kw)
    return summary, {r["id"]: r["generated"] for r in w.records
                     if r["kind"] == "request"}


def test_engine_serves_the_family_and_counts_its_pools(engine, requests):
    summary, answers = _run(engine, requests)
    assert summary["completed"] == len(requests) == len(answers)
    assert summary["post_warmup_compiles"] == 0
    kv = engine._kv
    assert set(kv) == {"pages", "state"}
    # pages for the ONE softmax layer, cap + 1 slots for the three others
    assert kv["pages"][0].shape[0] == 1
    assert kv["state"]["S"].shape[:2] == (3, engine.cap + 1)
    assert summary["state_pool_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(kv["state"]))
    steps = summary["decode_steps"]
    assert summary["state_slot_steps"] == steps * engine.cap
    assert 0 < summary["state_slots"] <= summary["state_slot_steps"]
    rows = summary["state_slots"]
    assert summary["moe_picks"] == rows * 4 * 4       # top-4 x 4 layers
    # 2 of 16 experts held: an eighth of the picks, give or take
    assert 0.03 < summary["moe_picks_held"] / summary["moe_picks"] < 0.3
    wall = sum(p["wall_s"] for p in summary["loop_phases"].values())
    assert abs(wall - summary["loop_wall_s"]) < 0.02 * summary["loop_wall_s"]
    assert set(summary["op_parts"]) == {
        f"{k}@{n}" for k, n in engine.compiled if k != "page_copy"}
    assert {"kda", "gqa", "moe", "head"} == set(
        summary["op_parts"][f"decode@{engine.cap}"].values())
    # every decode program is counted; interpreted here, the kernel is no
    # custom call (what the chip's compiler makes of it:
    # tests/test_tpu_compile.py)
    assert summary["kda_kernel_calls"] == {
        f"decode@{b}": 0 for b in engine.batch_buckets}


def test_preemption_reprefills_into_a_clean_slot(engine, requests):
    """A pool too small for four residents: victims give back pages AND
    slot, are re-prefilled from a zero state, and every answer is the
    unpressured run's."""
    _, calm = _run(engine, requests)
    saved = engine.num_pages
    try:
        engine.num_pages = 1 + 2 * engine.table_width + 2
        summary, pressed = _run(engine, requests, kv_preempt="on")
    finally:
        engine.num_pages = saved
    assert summary["degrade"]["preempts"] > 0
    assert pressed == calm


@pytest.mark.parametrize("flag,value,match", [
    ("prefix_cache", "on", "prefix_cache"),
    ("decode_attention", "paged", "decode_attention=paged"),
    ("quant", "int8_w", "quant"),
])
def test_engine_refuses_loudly_what_the_family_does_not_support(
        flag, value, match):
    from tpu_hc_bench.serve import engine as engine_mod

    kw = {flag: value}
    if flag == "prefix_cache":
        kw["kv_reserve"] = "lazy"
    with pytest.raises(ValueError, match=match):
        engine_mod.ServeEngine(_cfg(**kw), print_fn=lambda m: None)


def test_run_refuses_a_prefix_cache_override(engine, requests):
    with pytest.raises(ValueError, match="prefix_cache"):
        engine.run(requests, prefix_cache="on", kv_reserve="lazy")


def test_use_fp16_reaches_the_serve_lane_and_float32_stays_default():
    from tpu_hc_bench import flags

    on = flags.parse_flags(["--model=solar_open2_tiny", "--use_fp16=True"],
                           workload="serve")
    off = flags.parse_flags(["--model=solar_open2_tiny"], workload="serve")
    assert on.compute_dtype == "bfloat16" and off.compute_dtype == "float32"


def test_bfloat16_arm_holds_matrices_and_pages_so_and_the_state_float32():
    from tpu_hc_bench.serve import arrivals
    from tpu_hc_bench.serve import engine as engine_mod

    eng = engine_mod.ServeEngine(
        _cfg(use_fp16=True, max_in_flight=2, max_prompt_len=8,
             max_output_len=3, num_requests=3), print_fn=lambda m: None)
    kv = eng._kv
    assert kv["pages"][0].dtype == jnp.bfloat16
    assert kv["state"]["conv"].dtype == jnp.bfloat16
    assert kv["state"]["S"].dtype == jnp.float32
    p = eng.params
    assert p["layer_1_mixer"]["wq"].dtype == jnp.bfloat16
    assert p["layer_0_moe"]["wi"].dtype == jnp.bfloat16
    assert p["layer_0_moe"]["router"]["kernel"].dtype == jnp.float32
    assert p["layer_1_mixer"]["A_log"].dtype == jnp.float32
    summary, answers = _run(
        eng, arrivals.build_requests(eng.cfg, eng.spec.vocab_size))
    assert summary["completed"] == 3 and len(answers) == 3


def test_part_of_ops_reads_the_named_scopes():
    text = '''HloModule m

ENTRY %main (p: f32[4]) -> f32[4] {
  %fusion.3 = bf16[4,8]{1,0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(decode)/kda/mul"}
  %dot.1 = f32[4,9]{1,0} dot(%a, %b), metadata={op_name="jit(decode)/head/dot_general"}
  %copy.2 = f32[4]{0} copy(%c), metadata={op_name="jit(decode)/transpose"}
  ROOT %x = (f32[2]{0}, s32[]) fusion(%y), kind=kLoop, calls=%fd, metadata={op_name="jit(prefill)/moe/MoEFFN/top_k"}
}
'''
    assert decode_mod.part_of_ops(text) == {
        "fusion.3:bf16[4,8]": "kda", "dot.1:f32[4,9]": "head",
        "x:f32[2]": "moe"}
