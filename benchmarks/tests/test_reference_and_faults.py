"""The plain reference against ``models/`` at a tiny size on the CPU; the
lower-precision control coming out as not correct; and the rest of a run
driven with the timed path broken underneath, which has to end with
``correct`` false for each fault a cell can have."""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from families import gpt2
from harness import (adapters, checks, rehearse, serve_lane, spec, traffic,
                     train_lane)

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "cpu", reason="CPU rehearsal sizes")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


@pytest.fixture(scope="module")
def tiny(bench):
    return rehearse.tiny_config(spec.config_of(bench, "gpt2_medium"))


def test_reference_forward_equals_models_gpt(tiny):
    from tpu_hc_bench.models import gpt

    model = gpt.GPTLM(hidden=tiny["n_embd"], num_layers=tiny["n_layer"],
                      heads=tiny["n_head"], ffn=4 * tiny["n_embd"],
                      max_len=tiny["n_positions"])
    ref = gpt2.reference
    seed = 2**31 + 5
    tokens = traffic.seed_rng(seed, 9).integers(1, 50257, (2, 24)).astype(
        np.int32)
    got = model.apply({"params": adapters.program_weights(tiny, seed)},
                      tokens, train=False)
    params = ref.make_params(tiny, seed)
    want = ref.logits_of(params, ref.hidden_states(params, tokens, tiny))
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


def test_reference_loss_and_gradients_equal_models_gpt(tiny):
    from tpu_hc_bench.models import gpt

    model = gpt.GPTLM(hidden=tiny["n_embd"], num_layers=tiny["n_layer"],
                      heads=tiny["n_head"], ffn=4 * tiny["n_embd"],
                      max_len=tiny["n_positions"])
    ref = gpt2.reference
    batch = gpt2.train_batch(tiny, {"batch_per_chip": 2, "seq_len": 16}, 4,
                             1)

    def program_loss(tree):
        logits = model.apply({"params": tree}, batch[0], train=False)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, batch[1][..., None], -1)[..., 0]
        return (nll * batch[2]).sum() / batch[2].sum()

    tree = adapters.program_weights(tiny, 4)
    lp, gp = jax.value_and_grad(program_loss)(tree)
    params = ref.make_params(tiny, 4)
    lr, gr = jax.value_and_grad(ref.loss_fn)(params, batch, tiny)
    assert abs(float(lp) - float(lr)) < 1e-5
    norm = lambda x: float(jnp.sqrt(jnp.sum(jnp.square(x))))     # noqa
    got = {k: norm(v) for k, v in
           gpt2.program_parts(gp, tiny).items()}
    want = {k: norm(v) for k, v in
            gpt2.reference_parts(gr, tiny).items()}
    assert got.keys() == want.keys() and len(got) == 4 + 2 * 16
    med = float(np.median(list(want.values())))
    for k, w in want.items():
        assert abs(got[k] - w) <= 1e-4 * max(w, med), k
    # a key's bias has no gradient under the softmax; its neighbours have
    assert want[("c_attn_b.k", 0)] < 1e-3 * med < want[("c_attn_b.v", 0)]


def reference_greedy(tiny, seed, precision, lens=(30, 17, 9, 24), out=48):
    """A sample of requests decoded greedily by the reference itself in
    ``precision``, with the logits it chose from: the reference put in
    the program's place."""
    ref = gpt2.reference
    params = ref.make_params(tiny, seed)
    rng = traffic.seed_rng(seed, 9)

    @jax.jit
    def step(p, t, n):
        logits = ref.logits_of(
            p, ref.hidden_states(p, t, tiny, precision)[0, n - 1], precision)
        return jnp.argmax(logits), logits

    sample = []
    for n in lens:
        prompt = rng.integers(1, 50257, n).astype(np.int32)
        toks = np.zeros((1, 96), np.int32)
        toks[0, :n] = prompt
        served, rows = [], []
        for j in range(out):
            t, logits = step(params, toks, n + j)
            served.append(int(t))
            rows.append((j, np.asarray(logits)))
            toks[0, n + j] = int(t)
        sample.append({"rid": n, "prompt": prompt,
                       "served": np.asarray(served, np.int32),
                       "rows": rows[:checks.LOGIT_ROWS]})
    return sample


@pytest.mark.parametrize("stated", ["f32", "bf16"])
def test_serve_control_fails_through_the_verdict_and_the_stated_passes(
        tiny, stated):
    """The serve arm states float32 with matmul operands rounded to
    bfloat16 (on the CPU, where float32 multiplies in float32: plain
    float32); its control also stores every tensor in bfloat16.  Through
    the same ``verdict`` and the configuration's own limits as a run:
    the reference decoding in the stated precision passes, the control
    does not, and it is ``logit_error_excess`` that stops it.  (At the
    cell's size on the chip: ``tools/readings.py``, PERF.md section 2.)"""
    arm = tiny["serve_arm"]
    seed = 21

    def held(precision):
        sample = reference_greedy(tiny, seed, precision)
        st = checks.serve_stats(tiny, seed, sample, 96, 48, stated)
        assert st["program"]["n"] == 192 and st["program"]["rows"] == 128
        return checks.serve_numbers_from(tiny, st["program"])

    sound, control = held(stated), held(arm["control"])
    assert checks.verdict(sound), sound
    assert not checks.verdict(control), control
    value, limit = control["logit_error_excess"]
    # the chip reads +0.49 to +0.57 at the cell's size (PERF.md section 2)
    assert value > 1.5 * limit > 0 > sound["logit_error_excess"][0] - 0.1


def test_tapped_rows_go_to_their_requests_by_page_and_position():
    """A decode row belongs to the request whose prefill last wrote the
    row's first page; one whose length or fed token does not fit that
    request's answer is left out; the longest request leads the sample."""
    prompts = {7: np.arange(1, 6, dtype=np.int32),
               8: np.arange(11, 14, dtype=np.int32)}
    by_rid = {rid: {"rid": rid, "prompt": p} for rid, p in prompts.items()}
    records = [{"id": 7, "prompt_len": 5, "generated": [50, 51, 52]},
               {"id": 8, "prompt_len": 3, "generated": [60, 61]}]
    row = lambda x: np.full((4,), float(x), np.float32)     # noqa: E731
    tapped = {
        "prefill": [(prompts[7], 3, row(70)), (prompts[8], 9, row(80))],
        "decode": [
            # before request 8's prefill: page 9 is nobody's yet
            (np.array([50, 60]), np.array([3, 9]), np.array([5, 3]),
             np.array([True, True]), np.stack([row(71), row(99)]), 1),
            # request 7 fed its 2nd token; request 8 a token it never made
            (np.array([51, 66]), np.array([3, 9]), np.array([6, 3]),
             np.array([True, True]), np.stack([row(72), row(99)]), 2),
            # an inactive (padding) row is never read
            (np.array([60, 0]), np.array([9, 3]), np.array([3, 0]),
             np.array([True, False]), np.stack([row(81), row(99)]), 2)]}
    got = checks.sample_with_rows(records, by_rid, tapped)
    assert [s["rid"] for s in got] == [7, 8]
    assert [(o, float(x[0])) for o, x in got[0]["rows"]] == [
        (0, 70.0), (1, 71.0), (2, 72.0)]
    assert [(o, float(x[0])) for o, x in got[1]["rows"]] == [
        (0, 80.0), (1, 81.0)]


def plant(monkeypatch, fault):
    """The timed path broken underneath, from outside the lanes."""
    if fault == "token_altered":
        # every decode step's first row yields its token + 1, where the
        # token is produced
        warm = serve_lane.warm_up

        def warm_then_break(engine, *a, **k):
            warm(engine, *a, **k)

            def broken(exe):
                def call(*a, **k):
                    tok, logits, kv = exe(*a, **k)
                    tok = np.array(tok)
                    tok[0] = (tok[0] + 1) % engine.spec.vocab_size
                    return tok, logits, kv
                return call

            engine.compiled = {k: broken(v) if k[0] == "decode" else v
                               for k, v in engine.compiled.items()}

        monkeypatch.setattr(serve_lane, "warm_up", warm_then_break)
    elif fault == "state_unchanged":
        class Unchanged(train_lane.StepObserver):
            def step(self, state, batch, rng):  # the step donates its input
                kept = jax.tree.map(lambda x: x.copy(), state)
                _, metrics = self.fn(state, batch, rng)
                return kept, metrics

        monkeypatch.setattr(train_lane, "StepObserver", Unchanged)
    elif fault == "half_batch":
        class HalfBatch(train_lane.StepObserver):
            def step(self, state, batch, rng):
                batch = jax.tree.map(
                    lambda x: x.at[x.shape[0] // 2:].set(
                        x[:x.shape[0] // 2]), batch)
                return self.fn(state, batch, rng)

        monkeypatch.setattr(train_lane, "StepObserver", HalfBatch)
    elif fault is not None:
        raise ValueError(fault)


def rehearse_cell(bench, name, seed=31):
    cell = spec.cell_of(bench, name)
    cfg = spec.config_of(bench, cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    args = argparse.Namespace(seed=seed, seconds=2.0, trace=0)
    work = os.path.join(spec.ROOT, ".bench_work")
    result: dict = {}
    rc = rehearse.run(cell, cfg, mix, args, work, result=result)
    assert rc == 0
    return result


@pytest.mark.parametrize("name,fault,correct", [
    ("gpt2m-serve-backlog-r2", None, True),
    ("gpt2m-serve-backlog-r2", "token_altered", False),
    ("gpt2m-train-1k", None, True),
    ("gpt2m-train-1k", "state_unchanged", False),
    ("gpt2m-train-1k", "half_batch", False),
])
def test_a_broken_timed_path_ends_with_correct_false(bench, name, fault,
                                                     correct, capsys,
                                                     monkeypatch):
    plant(monkeypatch, fault)
    result = rehearse_cell(bench, name)
    out = capsys.readouterr().out
    assert result["correct"] is correct, out[-2000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["platform"] == "cpu" and "metrics" not in line


@pytest.mark.parametrize("name", ["gpt2m-serve-backlog-r2"])
def test_run_py_rehearses_the_cell_from_the_command_line(name):
    """``run.py --rehearse`` as a process of its own: the cell, its mix
    and its configuration are found by name, every answer comes, and the
    line carries counts and nothing under a device metric's name."""
    import subprocess
    import sys

    got = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", name, "--seed", str(2**31 + 27), "--seconds", "2",
         "--trace", "0", "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=spec.ROOT,
        capture_output=True, text=True, timeout=600)
    assert got.returncode == 0, got.stderr[-2000:]
    line = json.loads(got.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] == name and line["correct"] is True
    counts = line["counts"]
    assert counts["failed"] == 0 and counts["requests_finished"] > 0
    assert "metrics" not in line and "device" not in line
