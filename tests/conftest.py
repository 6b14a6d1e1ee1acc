"""Test harness: 8 virtual CPU devices standing in for a TPU slice.

The reference has no test suite at all (SURVEY.md §4); its verification is
operational.  We close that gap with unit tests running on a simulated
8-device mesh — the multi-process simulation story SURVEY.md §4 calls for.

NOTE: ``jax_num_cpu_devices`` must be set before the backend initializes,
hence the config calls at conftest import time (before any test module
imports build arrays).  No try/except on purpose: if a call fails, the
backend is already initialized with the wrong device count, and aborting
collection loudly beats every mesh test failing with confusing shape
errors.
"""

import os

# Persistent XLA executable cache: the suite's cost is dominated by
# compiles of 8-device CPU programs, which are identical run to run —
# a warm cache turns the ~20-min cold lane into a few minutes.  Placed
# the way a user would place it (utils.compile_cache honors the
# variable), before jax is imported, and outside the checkout: a tree
# that fills with cache entries is too large to copy to the chip.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      "/tmp/tpu_hc_bench_jax_cache")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="also run tests marked slow (whole-model param counts and "
             "other heavyweight compiles) — the full lane; the true "
             "multi-process tests are NOT slow-marked and always run")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavyweight whole-model test (runs only with --runslow); "
        "the multi-process suite is deliberately unmarked")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow test: pass --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh8():
    from tpu_hc_bench.topology import build_mesh, discover_layout

    return build_mesh(discover_layout())


def ceiling_file(tmp_path) -> str:
    """The ONE copy of the test fabric-ceiling sweep (schema 1), shared
    by the session ``rewind_run`` fixture and test_goodput's ceiling
    unit tests — two drifting copies of the sweep schema is how table
    rot starts."""
    import json

    data = {
        "schema": 1, "world_size": 8, "device_kind": "cpu",
        "sweeps": {"allreduce": [
            {"op": "allreduce", "world_size": 8, "message_bytes": 1024,
             "mean_us": 10.0, "algbw_gbps": 0.1, "busbw_gbps": 0.18},
            {"op": "allreduce", "world_size": 8,
             "message_bytes": 1 << 20, "mean_us": 100.0,
             "algbw_gbps": 10.0, "busbw_gbps": 17.5},
        ]},
    }
    p = tmp_path / "sweep.json"
    p.write_text(json.dumps(data))
    return str(p)


# --- serving-lane session fixtures (rounds 16-20) ---------------------
# ONE warmed engine per family, shared by test_serve AND
# test_requests_obs — engine warmup is the serving lane's whole test
# cost, so every closed loop below rides these in VIRTUAL time.

SERVE_VCOSTS = {"prefill": 0.004, "decode": 0.003, "classify": 0.002}


def _serve_quiet(_msg):
    pass


@pytest.fixture(scope="session")
def serve_cfg():
    from tpu_hc_bench import flags

    return flags.BenchmarkConfig(
        model="moe_tiny", workload="serve",
        arrival_rate=50.0, num_requests=8,
        max_prompt_len=8, max_output_len=4,
        max_in_flight=2, kv_page_size=4, seed=0,
    ).resolve()


@pytest.fixture(scope="session")
def moe_engine(serve_cfg):
    from tpu_hc_bench.serve import engine as engine_mod

    return engine_mod.ServeEngine(serve_cfg, print_fn=_serve_quiet)


@pytest.fixture(scope="session")
def moe_requests(serve_cfg, moe_engine):
    from tpu_hc_bench.serve import arrivals

    return arrivals.build_requests(serve_cfg, moe_engine.spec.vocab_size)


@pytest.fixture(scope="session")
def moe_ab(tmp_path_factory, moe_engine, moe_requests):
    """BOTH scheduler arms over the same trace and warmed engine, each
    leaving a real metrics dir — the serving lane's only closed-loop
    runs in the default lane."""
    from tpu_hc_bench.obs import metrics as obs_metrics
    from tpu_hc_bench.serve import engine as engine_mod

    root = tmp_path_factory.mktemp("serve_ab")
    out = {}
    for arm in ("static", "continuous"):
        mdir = str(root / arm)
        writer = obs_metrics.MetricsWriter(
            mdir, obs_metrics.run_manifest(
                cfg=moe_engine.cfg, extra={"workload": "serve"}))
        try:
            summary = moe_engine.run(
                moe_requests, batching=arm, writer=writer,
                clock=engine_mod.VirtualClock(SERVE_VCOSTS))
        finally:
            writer.close()
        out[arm] = {"summary": summary, "mdir": mdir}
    return out


@pytest.fixture(scope="session")
def trivial_engine():
    from tpu_hc_bench import flags
    from tpu_hc_bench.serve import engine as engine_mod

    cfg = flags.BenchmarkConfig(
        model="trivial", workload="serve",
        arrival_rate=100.0, num_requests=6, max_in_flight=2,
        # regression pin: classify members allocate no KV pool, so an
        # explicit --kv_pages below one request's worst case must not
        # crash their construction (it used to trip the decode-lane
        # pool validation)
        kv_pages=2,
    ).resolve()
    return engine_mod.ServeEngine(cfg, print_fn=_serve_quiet)


@pytest.fixture(scope="session")
def rewind_run(tmp_path_factory):
    """ONE tiny driver run with an injected rewind fault, shared by
    every default-lane e2e assertion (test_goodput's acceptance checks
    AND test_memory_obs's ledger/report checks) — session scope so the
    lane pays for a single run no matter how many modules consume it.

    nan at step 1: the double-buffered guard fetch processes window 2's
    counters at window 4, so the rewind lands mid-run with clean replay
    steps after it (goodput strictly between 0 and 1).
    """
    from tpu_hc_bench import flags
    from tpu_hc_bench.train import driver

    tmp = tmp_path_factory.mktemp("shared_e2e")
    ceiling = ceiling_file(tmp)
    mdir = str(tmp / "m")
    cfg = flags.BenchmarkConfig(
        batch_size=2, num_warmup_batches=1, num_batches=6,
        display_every=2, model="trivial", num_classes=10,
        init_learning_rate=0.05, on_nonfinite="rewind",
        inject_fault="nan_loss@1", train_dir=str(tmp / "ck"),
        metrics_dir=mdir, fabric_ceiling=ceiling,
    ).resolve()
    out: list[str] = []
    res = driver.run_benchmark(cfg, print_fn=out.append)
    return {"dir": mdir, "ceiling": ceiling, "result": res,
            "out": out, "tmp": tmp}
