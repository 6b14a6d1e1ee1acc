"""The set-up account (``obs.timeline.setup`` / ``setup_account``): JAX's
own trace, lowering and backend-compile events charged to the innermost
set-up span, each program's outcome in the persistent cache, and the
spans that the serve engine and the goodput transitions open."""

import time
import uuid

import pytest

from tpu_hc_bench.analysis import lints
from tpu_hc_bench.obs import timeline
from tpu_hc_bench.utils import compile_cache


@pytest.fixture
def account():
    """A clean account with the listener running; clean again after."""
    compile_cache.listen()
    timeline.reset_setup_account()
    yield timeline.setup_account
    timeline.reset_setup_account()


def _fresh_fn():
    """A function no other test has traced: a constant of its own, so the
    program is new to this process and to the persistent cache."""
    import jax.numpy as jnp

    c = float(uuid.uuid4().int % 10_000) + 0.5
    return lambda x: jnp.sin(x) * c + jnp.cos(x)


def _aot(fn, *args):
    import jax

    return jax.jit(fn).lower(*args).compile()


def test_an_aot_compile_in_a_setup_span_is_traced_lowered_and_compiled(
        account):
    import jax.numpy as jnp

    x = jnp.zeros((5,), jnp.float32)
    with timeline.setup("aot", program="f@5"):
        _aot(_fresh_fn(), x)
    acc = account()
    prog = acc["programs"]["f@5"]
    assert prog["trace_s"] > 0 and prog["lower_s"] > 0
    assert prog["compile_s"] > 0
    assert prog["outcome"] in timeline.OUTCOMES
    assert "compiles" not in prog           # one backend compile
    assert sum(acc[o] for o in timeline.OUTCOMES) == 1
    assert acc["phases"]["aot"][0] == 1


def test_a_jitted_inner_function_is_traced_once_in_trace_s(
        account, monkeypatch):
    """The outer trace holds the inner one: the events nest, and the
    program's ``trace_s`` is their union (the outer's length), not their
    sum."""
    import jax
    import jax.numpy as jnp

    seen = []
    forward = timeline.setup_event

    def spy(field, t0, t1):
        seen.append((field, t0, t1))
        forward(field, t0, t1)

    monkeypatch.setattr(timeline, "setup_event", spy)
    inner = jax.jit(_fresh_fn())

    def outer(x):
        time.sleep(0.01)        # the outer trace is the longer interval
        return inner(x) + 1.0

    x = jnp.zeros((3,), jnp.float32)
    seen.clear()
    with timeline.setup("aot", program="outer@3"):
        _aot(outer, x)
    traces = sorted((t0, t1) for field, t0, t1 in seen
                    if field == "trace_s")
    assert len(traces) >= 2
    widest = max(traces, key=lambda s: s[1] - s[0])
    assert all(widest[0] <= t0 and t1 <= widest[1] for t0, t1 in traces)
    trace_s = account()["programs"]["outer@3"]["trace_s"]
    assert trace_s == pytest.approx(widest[1] - widest[0])
    assert trace_s < sum(t1 - t0 for t0, t1 in traces)


def test_a_program_compiled_into_the_cache_is_loaded_after_clear_caches(
        account, tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc.reset_cache()
    try:
        fn, x = _fresh_fn(), jnp.zeros((6,), jnp.float32)
        with timeline.setup("aot", program="cold"):
            _aot(fn, x)
        jax.clear_caches()
        with timeline.setup("aot", program="warm"):
            _aot(fn, x)
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[1])
        cc.reset_cache()
    acc = account()
    assert acc["programs"]["cold"]["outcome"] == "compiled"
    assert acc["programs"]["warm"]["outcome"] == "loaded"
    assert (acc["compiled"], acc["loaded"], acc["uncached"]) == (1, 1, 0)


def test_a_compile_with_no_setup_span_open_changes_nothing(account):
    import jax.numpy as jnp

    x = jnp.zeros((4,), jnp.float32)
    before = compile_cache.process_compiles()
    _aot(_fresh_fn(), x)
    assert compile_cache.process_compiles() == before + 1
    assert account() == {"phases": {}, "programs": {}, "compiled": 0,
                         "uncached": 0, "loaded": 0, "covered_s": 0.0}


def test_with_the_recorder_off_the_fold_is_kept_and_no_annotation_opened(
        account, monkeypatch):
    opened = []
    monkeypatch.setattr(timeline, "_annotate",
                        lambda name: opened.append(name))
    rec = timeline.get_recorder()
    was = rec.enabled
    rec.enabled = False
    try:
        n = rec._n
        with timeline.setup("init"):
            pass
        assert rec._n == n
    finally:
        rec.enabled = was
    assert opened == []
    assert account()["phases"]["init"][0] == 1


def test_with_the_recorder_on_a_setup_span_is_on_both_sinks(
        account, monkeypatch):
    opened = []
    monkeypatch.setattr(timeline, "_annotate",
                        lambda name: opened.append(name))
    rec = timeline.get_recorder()
    was = rec.enabled
    rec.enabled = True
    try:
        with timeline.setup("aot", program="decode@16"):
            pass
    finally:
        rec.enabled = was
    assert opened == ["setup.aot"]
    assert rec.tail(1)[0]["name"] == "setup.aot"
    assert rec.tail(1)[0]["program"] == "decode@16"
    assert {"setup.aot", "setup.init", "setup.pool", "setup.inspect",
            "setup.warmup", "setup.probe"} <= timeline.KNOWN_SPANS


def test_covered_s_is_the_union_of_the_top_level_spans(account):
    with timeline.setup("init") as outer:
        with timeline.setup("pool"):
            time.sleep(0.01)
    with timeline.setup("inspect") as last:
        pass
    acc = account()
    assert acc["covered_s"] == pytest.approx(
        (outer.t1 - outer.t0) + (last.t1 - last.t0))
    assert acc["phases"]["pool"][1] <= acc["phases"]["init"][1]
    assert set(acc["phases"]) == {"init", "pool", "inspect"}


def test_goodput_transitions_open_setup_init_and_setup_warmup(account):
    import jax.numpy as jnp

    from tpu_hc_bench.obs import goodput, metrics

    x = jnp.zeros((7,), jnp.float32)
    phases = goodput.PhaseTracker(metrics.MetricsWriter(None))
    phases.enter("compile")
    _aot(_fresh_fn(), x)
    phases.enter("step")
    phases.end()
    acc = account()
    assert {k: v[0] for k, v in acc["phases"].items()} == {
        "init": 1, "warmup": 1}
    # a compile in the warm-up is charged to the span's own name
    assert acc["programs"]["warmup"]["compile_s"] > 0
    assert acc["covered_s"] == pytest.approx(
        acc["phases"]["init"][1] + acc["phases"]["warmup"][1])


def test_the_engine_lists_each_aot_program_once_and_run_adds_none(
        account, serve_cfg):
    from tpu_hc_bench.serve import arrivals
    from tpu_hc_bench.serve import engine as engine_mod

    engine = engine_mod.ServeEngine(serve_cfg, print_fn=lambda m: None)
    acc = account()
    aot = {k for k in acc["programs"] if "@" in k}
    assert aot == {f"{kind}@{n}" for kind, n in engine.compiled}
    assert len(aot) == engine.lower_count
    assert acc["phases"]["aot"][0] == engine.lower_count
    assert {"init", "pool", "aot"} <= set(acc["phases"])
    assert acc["covered_s"] > 0
    record = engine.compile_record
    assert record["new_entries"] == acc["compiled"]
    assert record["warm"] == (acc["compiled"] == 0 and acc["loaded"] > 0)
    engine.run(arrivals.build_requests(serve_cfg,
                                       engine.spec.vocab_size)[:2])
    assert account()["programs"] == acc["programs"]


def test_resolve_starts_the_one_compile_listener():
    from jax._src import monitoring

    compile_cache.resolve("off")
    compile_cache.resolve("off")
    assert monitoring.get_event_time_span_listeners().count(
        compile_cache._on_span) == 1
    assert monitoring.get_event_listeners().count(
        compile_cache._on_event) == 1


@pytest.mark.parametrize("src,flagged", [
    ('timeline_mod.setup("aot", program="decode@8")', False),
    ('timeline_mod.setup("warmup")', False),
    ('timeline_mod.setup("aott")', True),
])
def test_span_registry_lint_reads_setup_names(src, flagged):
    text = ("from tpu_hc_bench.obs import timeline as timeline_mod\n"
            f"def f():\n    with {src}:\n        pass\n")
    found = [f for f in lints.lint_source_text(
        text, filename="tpu_hc_bench/serve/engine.py")
        if f.lint == lints.SPAN_REGISTRY]
    assert bool(found) is flagged


def test_setup_union_counts_nested_intervals_once():
    assert timeline._union_s([(0.0, 2.0), (0.5, 1.0), (3.0, 4.0),
                              (3.5, 5.0)]) == pytest.approx(4.0)
    assert timeline._union_s([]) == 0.0
    assert timeline._union_s([(1.0, 1.0)]) == 0.0
