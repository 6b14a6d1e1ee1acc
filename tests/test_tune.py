"""Budgeted autotuner (tpu_hc_bench/tune/, round 14).

Default lane is pure host-side work — space enumeration, the static
pruner, successive halving over a STUBBED runner with a deterministic
synthetic throughput surface, journal resume, registry round-trip, and
``--config=auto`` resolution.  No subprocess training runs (tier-1 sits
~805s of the 870s budget); the one real end-to-end micro-search on
``trivial`` plus its follow-up ``--config=auto`` bench run is
slow-marked.

The load-bearing pins:
- a stub-surface search recovers the known-best (seeded) config for two
  members whose surfaces peak there — the closed-loop claim;
- the pruner's three skip classes (flag-invalid / lint / hbm-oom) each
  reject without a run and land in the journal;
- a killed search resumed with the same --out never re-measures a
  journaled (candidate, rung) pair;
- ``--config=auto`` applies a tuned row to default fields only, falls
  back LOUDLY when no row exists, and survives a stale row;
- the tuned-config-staleness lint flags rows spelling dead flag names.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tpu_hc_bench import flags
from tpu_hc_bench.analysis import lints
from tpu_hc_bench.tune import prune, registry, runner, search, space

HW = "cpu-test-w1"


def make_stub(peak_overrides: dict, calls: list | None = None,
              wall_s: float = 1.0):
    """A deterministic synthetic throughput surface: score decays with
    distance from ``peak_overrides`` in (log2 batch, log2 accum, dtype,
    toggles) space, so the seeded config is the argmax iff the peak sits
    there.  Longer rungs keep the ordering (rung-invariant surface)."""

    def stub(c: space.Candidate, rung: int, batches: int) -> dict:
        if calls is not None:
            calls.append((c.key, rung))
        d = dict(c.overrides)
        peak = dict(peak_overrides)
        dist = 0.0
        b = d.get("batch_size", 64)
        pb = peak.get("batch_size", 64)
        dist += abs(np.log2(b) - np.log2(pb))
        a = d.get("gradient_accumulation_steps", 1)
        pa = peak.get("gradient_accumulation_steps", 1)
        dist += abs(np.log2(a) - np.log2(pa))
        for k in ("accum_dtype", "gradient_checkpointing", "scan_layers",
                  "fusion_threshold_bytes", "variable_update"):
            if d.get(k) != peak.get(k):
                dist += 1.0
        return {"per_chip": round(1000.0 * 0.8 ** dist, 3),
                "goodput": 0.9, "wall_s": wall_s}

    return stub


# --------------------------------------------------------------- space


def test_member_space_seed_first_and_valid():
    sp = space.member_space("trivial")
    assert sp[0] == space.seed_candidate("trivial")
    keys = [c.key for c in sp]
    assert len(keys) == len(set(keys)), "duplicate candidates"
    for c in sp:
        d = dict(c.overrides)
        b = d.get("batch_size", 64)
        a = d.get("gradient_accumulation_steps", 1)
        assert b % a == 0 and b // a >= 1, c.key
        if d.get("accum_dtype", "f32") != "f32":
            assert a > 1, f"dtype lever without accum: {c.key}"


def test_member_space_covers_the_manual_levers():
    sp = space.member_space("trivial")
    keys = [c.key for c in sp]
    # batch ladder around the seeded 512
    for b in (128, 256, 1024, 2048):
        assert any(f"batch_size={b}" in k for k in keys)
    # accum ladder and the zero1 arm toggle
    assert any("gradient_accumulation_steps=8" in k for k in keys)
    assert any("variable_update=zero1" in k for k in keys)
    # the fusion-threshold axis
    assert any("fusion_threshold_bytes" in k for k in keys)


def test_member_space_decoder_levers():
    sp = space.member_space("gpt2_moe")
    seed = sp[0]
    assert dict(seed.base).get("attention_impl") == "flash"
    assert dict(seed.overrides)["accum_dtype"] == "bf16"
    keys = [c.key for c in sp]
    # decoders get the remat/scan toggles and the dtype flip back to f32
    # (the flip's key drops the default accum_dtype)
    assert any("scan_layers=True" in k for k in keys)
    assert any("gradient_checkpointing=True" in k for k in keys)
    assert "batch_size=512,gradient_accumulation_steps=64" in keys


def test_grid_mode_crosses_batch_accum_dtype():
    axes = space.member_space("gpt2_moe", mode="axes")
    grid = space.member_space("gpt2_moe", mode="grid")
    assert len(grid) > len(axes)
    # the grid contains a cross point no axis pass generates: off-seed
    # batch AND off-seed accum together
    assert any(
        dict(c.overrides).get("batch_size") == 256
        and dict(c.overrides).get("gradient_accumulation_steps") == 32
        for c in grid)


def test_seed_matrix_matches_the_zoo_table():
    m = dict(space.seed_matrix())
    assert len(m) == 36
    assert m["trivial"] == 512 and m["ncf"] == 1048576
    # the old EXTRA_FLAGS knowledge, now derived from SEED_CONFIGS
    assert space.seed_extra_flags("trivial") == []
    assert space.seed_extra_flags("bert_large") == [
        "--gradient_accumulation_steps=32"]
    assert set(space.seed_extra_flags("gpt2_moe")) == {
        "--accum_dtype=bf16", "--attention_impl=flash",
        "--gradient_accumulation_steps=64"}


def test_candidate_rejects_non_lever_overrides():
    with pytest.raises(ValueError, match="not a tunable lever"):
        space.Candidate.make("trivial", {"learning_rate": 0.1})


# --------------------------------------------------------------- prune


def test_prune_hbm_model_rejects_known_oom():
    # trivial seed: batch 512, accum 1 -> microbatch anchor 512,
    # headroom 2 -> the batch-2048 one-shot candidate is a known OOM
    res = prune.static_prune(space.member_space("trivial"))
    oom = [s for s in res.skipped if s.cls == prune.HBM_OOM]
    assert any("batch_size=2048" == s.candidate.key for s in oom)
    assert all("batch_size=2048" != c.key for c in res.survivors)


def test_prune_bf16_seed_rejects_f32_accumulator():
    # gpt2_moe's seed NEEDED accum_dtype=bf16 at batch 512 (the f32
    # grad tree is what OOMed, BASELINE.md round 5) -> an f32-accum
    # candidate at that batch is a free skip
    hbm = prune.HbmModel.seeded("gpt2_moe")
    assert hbm.needs_bf16_accum_at == 512
    c = space.Candidate.make(
        "gpt2_moe",
        {"batch_size": 512, "gradient_accumulation_steps": 64},
        {"attention_impl": "flash"})
    assert hbm.check(c) is not None
    # the seeded bf16 point itself survives
    assert hbm.check(space.seed_candidate("gpt2_moe")) is None


def test_prune_flag_invalid_via_resolve():
    # accum_dtype without accumulation is a flag-time ValueError; the
    # space never generates it, but a hand-built candidate hits the
    # resolve() wall and classifies as flag-invalid
    bad = space.Candidate(
        "trivial", overrides=(("accum_dtype", "bf16"),))
    res = prune.static_prune([bad])
    assert not res.survivors
    assert res.skipped[0].cls == prune.FLAG_INVALID
    assert "accum_dtype" in res.skipped[0].reason


def test_prune_lint_class_skips_the_member():
    cands = space.member_space("trivial")
    res = prune.static_prune(
        cands, lint_fn=lambda m: ("host-sync-in-jit at foo.py:1",))
    assert not res.survivors
    assert {s.cls for s in res.skipped} == {prune.LINT}
    assert len(res.skipped) == len(cands)


# -------------------------------------------------------------- search


def test_search_recovers_seed_for_two_members(tmp_path):
    """The closed-loop claim: with a surface peaked at the seeded
    best-known config, the budgeted search returns exactly that config
    for two different members (acceptance criterion)."""
    for model in ("trivial", "gpt2_moe"):
        seed = space.seed_candidate(model)
        j = search.run_search(
            model, str(tmp_path / model), HW,
            settings=search.SearchSettings(budget_s=1e9),
            runner=make_stub(dict(seed.overrides)),
            print_fn=lambda m: None)
        assert j["status"] == "complete"
        assert j["best"]["key"] == seed.key, model


def test_search_halving_bookkeeping(tmp_path):
    calls: list = []
    j = search.run_search(
        "trivial", str(tmp_path), HW,
        settings=search.SearchSettings(budget_s=1e9, rung0_batches=4,
                                       growth=2, max_rungs=3),
        runner=make_stub({"batch_size": 512}, calls),
        print_fn=lambda m: None)
    rungs = j["rungs"]
    assert [r["batches"] for r in rungs] == [4, 8, 16][:len(rungs)]
    # each rung keeps ~half, never fewer than one
    for r in rungs:
        assert len(r["kept"]) == max(1, int(len(r["measured"]) * 0.5))
    # no (candidate, rung) pair measured twice
    assert len(calls) == len(set(calls))
    # journal measurements mirror the calls exactly
    journaled = {(k, int(rg)) for k, m in j["measurements"].items()
                 for rg in m}
    assert journaled == set(calls)
    # pruning is journaled alongside (hbm-oom from the seeded model)
    assert any(s["class"] == prune.HBM_OOM for s in j["skipped"])


def test_search_budget_exhaustion_and_resume(tmp_path):
    out = str(tmp_path)
    # each measurement bills 100s against a 250s budget -> exhausts
    # after 3 runs, mid-rung
    j = search.run_search(
        "trivial", out, HW,
        settings=search.SearchSettings(budget_s=250.0),
        runner=make_stub({"batch_size": 512}, wall_s=100.0),
        print_fn=lambda m: None)
    assert j["status"] == "budget-exhausted"
    assert j["spent_s"] == pytest.approx(300.0)
    done = {(k, int(r)) for k, m in j["measurements"].items() for r in m}
    assert len(done) == 3
    # resumed with a bigger budget: the journaled measurements are
    # never re-run
    calls: list = []
    j2 = search.run_search(
        "trivial", out, HW,
        settings=search.SearchSettings(budget_s=1e9),
        runner=make_stub({"batch_size": 512}, calls),
        print_fn=lambda m: None)
    assert j2["status"] == "complete"
    assert not (done & set(calls)), "re-measured a journaled pair"
    assert j2["best"]["key"] == "batch_size=512"


def test_search_resume_after_kill(tmp_path):
    """A search killed mid-run (journal committed after every
    measurement) resumes without repeating completed work."""
    out = str(tmp_path)
    base = make_stub({"batch_size": 512})
    n = 0

    def dying(c, rung, batches):
        nonlocal n
        n += 1
        if n > 4:
            raise KeyboardInterrupt("killed")
        return base(c, rung, batches)

    with pytest.raises(KeyboardInterrupt):
        search.run_search("trivial", out, HW,
                          settings=search.SearchSettings(budget_s=1e9),
                          runner=dying, print_fn=lambda m: None)
    j = search.load_journal(out)
    assert j is not None and j["status"] == "running"
    done = {(k, int(r)) for k, m in j["measurements"].items() for r in m}
    assert len(done) == 4
    calls: list = []
    j2 = search.run_search(
        "trivial", out, HW,
        settings=search.SearchSettings(budget_s=1e9),
        runner=make_stub({"batch_size": 512}, calls),
        print_fn=lambda m: None)
    assert j2["status"] == "complete"
    assert not (done & set(calls))


def test_search_rerun_of_finished_journal_is_a_noop(tmp_path):
    # a FINISHED search re-run with the same --out must not burn budget
    # on a fresh measurement past the halving's stopping point
    out = str(tmp_path)
    j = search.run_search("trivial", out, HW,
                          settings=search.SearchSettings(budget_s=1e9),
                          runner=make_stub({"batch_size": 512}),
                          print_fn=lambda m: None)
    assert j["status"] == "complete"
    calls: list = []
    j2 = search.run_search("trivial", out, HW,
                           settings=search.SearchSettings(budget_s=1e9),
                           runner=make_stub({"batch_size": 512}, calls),
                           print_fn=lambda m: None)
    assert not calls
    assert j2["status"] == "complete"
    assert j2["best"]["key"] == j["best"]["key"]


def test_search_best_prefers_the_deepest_rung(tmp_path):
    # a candidate eliminated at rung 0 with a noisy high score must not
    # beat the halving's steady-state winner; the promoted record's
    # measured_batches is the winner's OWN rung length
    cands = [space.Candidate.make("trivial", {"batch_size": b})
             for b in (128, 256, 512, 1024)]
    r0 = {"batch_size=128": 100.0, "batch_size=256": 99.0,
          "batch_size=512": 70.0, "batch_size=1024": 40.0}
    r1 = {"batch_size=128": 60.0, "batch_size=256": 59.0}

    def stub(c, rung, batches):
        return {"per_chip": (r0 if rung == 0 else r1)[c.key],
                "wall_s": 1.0}

    j = search.run_search(
        "trivial", str(tmp_path), HW,
        settings=search.SearchSettings(budget_s=1e9, rung0_batches=8,
                                       max_rungs=2),
        runner=stub, space=cands, print_fn=lambda m: None)
    # rung 0 cut batch 512 at score 70; the rung-1 winner scores 60 —
    # deepest-rung-first selection picks it anyway
    assert j["best"]["key"] == "batch_size=128"
    assert j["best"]["score"] == pytest.approx(60.0)
    assert j["best"]["record"]["measured_batches"] == 16


def test_search_journal_guards_model_and_hardware(tmp_path):
    out = str(tmp_path)
    search.run_search("trivial", out, HW,
                      settings=search.SearchSettings(budget_s=1e9),
                      runner=make_stub({"batch_size": 512}),
                      print_fn=lambda m: None)
    with pytest.raises(ValueError, match="is for model"):
        search.run_search("lenet", out, HW,
                          runner=make_stub({}), print_fn=lambda m: None)
    with pytest.raises(ValueError, match="per-hardware"):
        search.run_search("trivial", out, "v5e-16gb-w4",
                          runner=make_stub({}), print_fn=lambda m: None)


def test_search_max_candidates_truncation_is_journaled(tmp_path):
    j = search.run_search(
        "trivial", str(tmp_path), HW,
        settings=search.SearchSettings(budget_s=1e9, max_candidates=3),
        runner=make_stub({"batch_size": 512}),
        print_fn=lambda m: None)
    assert j["truncated"] > 0
    assert len(j["rungs"][0]["measured"]) == 3
    # the seed (enumerated first) survives truncation
    assert space.seed_candidate("trivial").key in j["rungs"][0]["measured"]


def test_search_all_failed(tmp_path):
    j = search.run_search(
        "trivial", str(tmp_path), HW,
        settings=search.SearchSettings(budget_s=1e9, max_candidates=2),
        runner=lambda c, r, b: {"error": "exit-1", "wall_s": 1.0},
        print_fn=lambda m: None)
    assert j["status"] == "all-failed"
    assert j["best"] is None


def test_commit_json_never_leaves_a_truncated_journal(tmp_path):
    path = str(tmp_path / "tune_state.json")
    search.commit_json(path, {"ok": 1})
    assert json.load(open(path)) == {"ok": 1}
    assert not os.path.exists(path + ".tmp")


# -------------------------------------------------------------- runner


def test_runner_stdout_parse_and_score():
    rec = runner.parse_stdout_metrics(
        "images/sec/chip: 2687.1  step: 47.6ms (p50 47.1ms)  MFU: 33.3%")
    assert rec["per_chip"] == pytest.approx(2687.1)
    assert rec["step_ms"] == pytest.approx(47.6)
    assert rec["mfu_pct"] == pytest.approx(33.3)
    # goodput-adjusted objective; NaN/absent goodput falls back to raw
    assert runner.score({"per_chip": 100.0, "goodput": 0.5}) == 50.0
    assert runner.score({"per_chip": 100.0}) == 100.0
    assert runner.score({"per_chip": 100.0, "error": "timeout"}) == 0.0
    # the launcher exit-code contract classes
    assert runner.EXIT_CLASSES[70] == "watchdog-timeout"
    assert runner.EXIT_CLASSES[75] == "preempted"


# ------------------------------------------------------------ registry


def _searched_journal(tmp_path, model="trivial"):
    seed = space.seed_candidate(model)
    return search.run_search(
        model, str(tmp_path / f"search-{model}"), HW,
        settings=search.SearchSettings(budget_s=1e9),
        runner=make_stub(dict(seed.overrides)), print_fn=lambda m: None)


def test_registry_round_trip(tmp_path, monkeypatch):
    j = _searched_journal(tmp_path)
    regdir = tmp_path / "reg"
    path, row = registry.promote(j, registry_dir=regdir)
    assert path == regdir / f"{HW}.json"
    assert registry.lookup("trivial", HW, regdir) == row
    assert row["overrides"] == {"batch_size": 512}
    assert row["search_status"] == "complete"
    # provenance: the winner's own deepest-rung length (default
    # settings: rung0 8 steps, growth 2 -> rung 2 measures 32)
    assert row["measured_batches"] == 32
    # promote merges: a second member lands in the same hardware file
    j2 = _searched_journal(tmp_path, "gpt2_moe")
    registry.promote(j2, registry_dir=regdir)
    rows = registry.load_rows(HW, regdir)
    assert set(rows) == {"trivial", "gpt2_moe"}


def test_promote_refuses_a_bestless_journal(tmp_path):
    with pytest.raises(ValueError, match="no successful measurement"):
        registry.promote({"model": "trivial", "hardware": HW,
                          "status": "all-failed", "best": None})


def test_config_auto_applies_tuned_row(tmp_path, monkeypatch):
    j = _searched_journal(tmp_path)
    regdir = tmp_path / "reg"
    registry.promote(j, registry_dir=regdir)
    monkeypatch.setenv(registry.REGISTRY_ENV, str(regdir))
    monkeypatch.setenv(registry.HW_ENV, HW)
    cfg = flags.BenchmarkConfig(model="trivial", config="auto").resolve()
    assert cfg.config_source == "auto"
    assert cfg.batch_size == 512
    assert cfg.tuned_config["hardware"] == HW
    assert "config" in cfg.translations


def test_config_auto_explicit_flag_wins(tmp_path, monkeypatch):
    j = _searched_journal(tmp_path)
    regdir = tmp_path / "reg"
    registry.promote(j, registry_dir=regdir)
    monkeypatch.setenv(registry.REGISTRY_ENV, str(regdir))
    monkeypatch.setenv(registry.HW_ENV, HW)
    cfg = flags.BenchmarkConfig(model="trivial", config="auto",
                                batch_size=64 * 3).resolve()
    assert cfg.config_source == "auto"
    assert cfg.batch_size == 64 * 3          # the operator's choice
    assert "explicit flag wins" in cfg.translations["config"]


def test_config_auto_explicit_default_value_pins(tmp_path, monkeypatch):
    # through parse_flags, a typed --batch_size=64 (the dataclass
    # default value) still pins against the tuned row — explicitness
    # is what the operator wrote, not a default-value compare
    j = _searched_journal(tmp_path)
    regdir = tmp_path / "reg"
    registry.promote(j, registry_dir=regdir)
    monkeypatch.setenv(registry.REGISTRY_ENV, str(regdir))
    monkeypatch.setenv(registry.HW_ENV, HW)
    cfg = flags.parse_flags(["--model=trivial", "--config=auto",
                             "--batch_size=64"])
    assert cfg.explicit_flags == ("batch_size", "config", "model")
    assert cfg.batch_size == 64
    assert "explicit flag wins" in cfg.translations["config"]
    # untyped fields still receive the row
    cfg = flags.parse_flags(["--model=trivial", "--config=auto"])
    assert cfg.batch_size == 512


def test_config_auto_falls_back_loudly_without_a_row(tmp_path,
                                                     monkeypatch):
    monkeypatch.setenv(registry.REGISTRY_ENV, str(tmp_path / "empty"))
    monkeypatch.setenv(registry.HW_ENV, HW)
    cfg = flags.BenchmarkConfig(model="trivial", config="auto").resolve()
    assert cfg.config_source == "baseline"
    assert cfg.tuned_config is None
    assert cfg.batch_size == 64              # untouched defaults
    note = cfg.translations["config"]
    assert "no tuned row" in note and "tune search" in note


def test_config_auto_survives_a_stale_row(tmp_path, monkeypatch):
    regdir = tmp_path / "reg"
    regdir.mkdir()
    (regdir / f"{HW}.json").write_text(json.dumps({
        "hardware": HW,
        "members": {"trivial": {"overrides": {"batch_size": 512,
                                              "dead_flag": 1},
                                "base": {}, "score": 1.0}}}))
    monkeypatch.setenv(registry.REGISTRY_ENV, str(regdir))
    monkeypatch.setenv(registry.HW_ENV, HW)
    cfg = flags.BenchmarkConfig(model="trivial", config="auto").resolve()
    assert cfg.config_source == "auto"
    assert cfg.batch_size == 512             # the live flag applied
    assert "dead_flag (unknown flag)" in cfg.translations["config"]


def test_config_manual_is_the_default_and_validated():
    cfg = flags.BenchmarkConfig(model="trivial").resolve()
    assert cfg.config_source == "manual" and cfg.tuned_config is None
    with pytest.raises(ValueError, match="manual|auto"):
        flags.BenchmarkConfig(model="trivial", config="bogus").resolve()


def test_hardware_key_env_pin(monkeypatch):
    monkeypatch.setenv(registry.HW_ENV, "v5e-16gb-w4")
    assert registry.hardware_key() == "v5e-16gb-w4"


# ----------------------------------------------------- staleness lint


def test_tuned_config_staleness_lint(tmp_path):
    regdir = tmp_path / "tuned"
    regdir.mkdir()
    (regdir / "cpu-w1.json").write_text(json.dumps({
        "hardware": "cpu-w1",
        "members": {
            "trivial": {"overrides": {"batch_size": 512}},
            "lenet": {"overrides": {"microbatch_ladder": 4},
                      "base": {"dead_base_flag": True}},
        }}))
    fs = lints.check_tuned_registry(regdir)
    assert {f.lint for f in fs} == {lints.TUNED_STALENESS}
    assert {f.model for f in fs} == {"lenet"}
    assert {f.location.split("/")[-1] for f in fs} == {
        "microbatch_ladder", "dead_base_flag"}
    assert all(f.severity == "warning" for f in fs)


def test_tuned_config_staleness_flags_unreadable_file(tmp_path):
    regdir = tmp_path / "tuned"
    regdir.mkdir()
    (regdir / "broken.json").write_text("{ not json")
    fs = lints.check_tuned_registry(regdir)
    assert len(fs) == 1 and "unreadable" in fs[0].message


def test_repo_registry_is_lint_clean():
    # the acceptance bar: whatever artifacts/tuned/ the repo ships lints
    # clean (missing dir included)
    assert lints.check_tuned_registry() == []


def test_sweep_from_registry_skips_stale_rows(tmp_path, monkeypatch,
                                              capsys):
    # one stale row must not block re-validating the other members
    # (and with only stale rows the sweep makes no subprocess runs)
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "sweep_zoo_under_test", os.path.join(root, "scripts",
                                             "sweep_zoo.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    regdir = tmp_path / "reg"
    regdir.mkdir()
    (regdir / "hw-x.json").write_text(json.dumps({
        "hardware": "hw-x",
        "members": {"trivial": {"overrides": {"dead_lever": 1}}}}))
    monkeypatch.setenv(registry.REGISTRY_ENV, str(regdir))
    out = tmp_path / "sweep.jsonl"
    monkeypatch.setattr(sys, "argv",
                        ["sweep_zoo.py", "--from_registry",
                         "--hardware", "hw-x", "--out", str(out)])
    mod.main()
    err = capsys.readouterr().err
    assert "skipping trivial" in err and "not a tunable lever" in err
    assert out.read_text() == ""


# ----------------------------------------------- sliced-batch satellite


def test_full_batch_identity_flag_parses():
    p = flags.build_parser()
    ns = p.parse_args(["--full_batch_identity=True", "--config=auto"])
    assert ns.full_batch_identity is True
    assert ns.config == "auto"
    ns = p.parse_args([])
    assert ns.full_batch_identity is False
    assert ns.config == "manual"


def test_shard_batch_local_identity_at_world_one(mesh8):
    # world=1: the local rows ARE the global batch, so the sliced path
    # must place bitwise-identical arrays to the device_put path
    from tpu_hc_bench.train import step as step_mod

    mesh = mesh8
    rng = np.random.default_rng(0)
    batch = (rng.standard_normal((16, 4, 4, 3)).astype(np.float32),
             rng.integers(0, 10, size=(16,)).astype(np.int32))
    a = step_mod.shard_batch(batch, mesh)
    b = step_mod.shard_batch_local(batch, mesh)
    for x, y in zip(a, b):
        assert x.sharding.is_equivalent_to(y.sharding, x.ndim)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------------ CLI + e2e


def test_cli_show_and_promote(tmp_path, monkeypatch, capsys):
    from tpu_hc_bench.tune.__main__ import main as tune_main

    j = _searched_journal(tmp_path)
    journal_path = tmp_path / "search-trivial" / "tune_state.json"
    regdir = tmp_path / "reg"
    rc = tune_main(["promote", "--journal", str(journal_path),
                    "--registry", str(regdir)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "promoted: trivial" in out
    rc = tune_main(["show", "--hardware", HW,
                    "--registry", str(regdir)])
    assert rc == 0
    assert "batch_size=512" in capsys.readouterr().out
    # show on an empty registry: loud, nonzero
    rc = tune_main(["show", "--hardware", "no-such-hw",
                    "--registry", str(regdir)])
    assert rc == 1


@pytest.mark.slow
def test_real_micro_search_promote_and_config_auto(tmp_path):
    """The end-to-end acceptance loop, real subprocess runs: a budgeted
    micro-search on ``trivial`` completes within budget, journals >= 1
    pruner skip, emits a registry row, and a follow-up BENCH_CONFIG=auto
    bench run resolves it (config_source=auto in the BENCH json)."""
    from tpu_hc_bench.tune import prune as prune_mod

    out = str(tmp_path / "search")
    regdir = tmp_path / "reg"
    env_hw = "cpu-micro-w1"
    os.environ[registry.HW_ENV] = env_hw          # subprocesses inherit
    os.environ[registry.REGISTRY_ENV] = str(regdir)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        j = search.run_search(
            "trivial", out, env_hw,
            settings=search.SearchSettings(
                budget_s=600.0, rung0_batches=2, warmup=1, max_rungs=2,
                timeout_s=240.0, max_candidates=2),
            lint_fn=prune_mod.baseline_lint_classes)
        assert j["status"] in ("complete", "budget-exhausted")
        assert j["best"] is not None
        assert j["spent_s"] <= j["budget_s"]
        # static pruning was load-bearing: the hbm-oom class skipped
        # without a run (trivial's batch-2048 one-shot candidate)
        assert any(s["class"] == prune_mod.HBM_OOM for s in j["skipped"])
        path, row = registry.promote(j, registry_dir=regdir)
        assert path.exists()

        bench_env = dict(os.environ)
        bench_env.update(BENCH_FORCE_CPU="1", BENCH_MODEL="trivial",
                         BENCH_WARMUP="1", BENCH_BATCHES="2",
                         BENCH_CONFIG="auto")
        bench_env.pop("BENCH_BATCH_SIZE", None)
        proc = subprocess.run(
            [sys.executable, "bench.py"], capture_output=True, text=True,
            timeout=600,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env=bench_env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        rec = json.loads(proc.stdout.strip().splitlines()[-1])
        assert rec["extra"]["config_source"] == "auto"
        assert rec["extra"]["tuned_config"]["overrides"] == \
            row["overrides"]
    finally:
        os.environ.pop(registry.HW_ENV, None)
        os.environ.pop(registry.REGISTRY_ENV, None)


@pytest.mark.slow
def test_sweep_zoo_from_registry_smoke(tmp_path):
    """--from_registry sweeps the tuned rows (subprocess, one member)."""
    regdir = tmp_path / "reg"
    regdir.mkdir()
    (regdir / "cpu-sweep-w1.json").write_text(json.dumps({
        "hardware": "cpu-sweep-w1",
        "members": {"trivial": {"overrides": {"batch_size": 64},
                                "base": {}, "score": 1.0}}}))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env[registry.REGISTRY_ENV] = str(regdir)
    out = tmp_path / "sweep.jsonl"
    proc = subprocess.run(
        [sys.executable, "scripts/sweep_zoo.py", "--from_registry",
         "--hardware", "cpu-sweep-w1", "--out", str(out),
         "--warmup", "1", "--batches", "2"],
        capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    recs = [json.loads(line) for line in out.read_text().splitlines()]
    assert len(recs) == 1
    assert recs[0]["model"] == "trivial"
    assert recs[0]["config_source"] == "registry"
    assert recs[0].get("per_chip", 0) > 0, recs[0]
