"""One train cell, once: the window drives ``train.driver.run_benchmark``
— the driver's own loop, flow control and completion markers — with the
benchmark's weights, inputs and clock.

Seams into the program, each checked loudly so that a renamed one stops
the run instead of measuring something else.  Which model it is the
lane does not know: the batch, the weights' layout, the synthetic source
to replace and the model's constants come from the configuration's
family file, the optimizer's state from the train arm's optimizer file.

- the driver's synthetic source (the family's ``SYNTHETIC_SOURCE``)
  hands over the benchmark's batch (rows that all differ, from the
  seed);
- ``train.step.make_train_state``: the state starts from the benchmark's
  weights;
- ``train.step.build_train_step``: the compiled step is observed — one
  object, driven from the seed through its first steps in set-up (the
  reference follows the first three) and handed on to the window; the
  benchmark's clock starts at the first timed step and stops when the
  last one is ready;
- the family's ``program_constants`` (for GPT-2 the dropout rates the
  configuration assumes, for which the program has no flag).
"""

from __future__ import annotations

import gc
import importlib
import os
import time

import numpy as np

from harness import adapters, checks, device, tracing, traffic

WARMUP_STEPS = 4        # steps 1-3 are compared; step 4 keeps them


class StepObserver:
    """Wraps the compiled train step.  Calls are counted from 1; the
    first ``WARMUP_STEPS`` are set-up, the next ``timed`` the window."""

    def __init__(self, step_fn, cfg: dict, seed: int, timed: int,
                 counter, tracer=None):
        self.fn, self.cfg, self.seed = step_fn, cfg, seed
        self.timed, self.tracer = timed, tracer
        self.counter = counter
        self.compiles = None
        self.calls = 0
        self.losses: list = []
        self.grad_norms = None
        self.update_norms = None
        self.t0 = self.t1 = None

    def __call__(self, state, batch, rng):
        import jax

        self.calls += 1
        k = self.calls
        if k == WARMUP_STEPS + 1:
            self.t0 = time.monotonic()
            self.counter.arm()
            if self.tracer is not None:
                self.tracer.arm()
        out_state, metrics = self.step(state, batch, rng)
        if k <= 3:
            self.losses.append(metrics["loss"])
        if k == 1:
            self.grad_norms = _first_gradient_norms(out_state, self.cfg)
        if k == 3:
            self.update_norms = _update_norms(out_state.params, self.cfg,
                                              self.seed)
        if k == WARMUP_STEPS + self.timed:
            jax.block_until_ready(metrics["loss"])
            self.t1 = time.monotonic()
            self.compiles = self.counter.disarm()
            if self.tracer is not None:
                self.tracer.stop()
        return out_state, metrics

    def step(self, state, batch, rng):
        """The compiled step itself (the tests' planted faults override
        this)."""
        return self.fn(state, batch, rng)

    def __getattr__(self, item):
        return getattr(self.fn, item)


def _part_norms(tree, cfg: dict):
    """``{(part, layer): norm}`` of a program-layout tree, one jitted
    call (the result is a few hundred scalars, fetched after the
    window)."""
    import jax
    import jax.numpy as jnp

    fam = adapters.family_of(cfg)
    return jax.jit(lambda t: {
        k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for k, x in fam.program_parts(t, cfg).items()})(tree)


def _first_gradient_norms(state, cfg: dict):
    """Per part, the norm of the first gradient as the optimizer got it,
    worked out from its state after one step (the optimizer's file says
    how)."""
    tree, scale = adapters.optimizer_of(cfg["train_arm"]).first_gradient(
        state.opt_state)
    return {k: v * scale for k, v in _part_norms(tree, cfg).items()}


def _update_norms(params, cfg: dict, seed: int):
    """Per part, the norm of (parameters now - parameters at the start);
    the start is drawn again from the seed inside the same program, so no
    second copy of the weights is kept through the warm-up."""
    import jax
    import jax.numpy as jnp

    fam = adapters.family_of(cfg)
    ref = fam.reference

    def fn(key, p):
        p0 = fam.program_tree(ref.leaf_values(cfg, key), cfg)
        delta = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b, p, p0)
        return {k: jnp.sqrt(jnp.sum(jnp.square(x)))
                for k, x in fam.program_parts(delta, cfg).items()}

    return jax.jit(fn)(ref.seed_key(seed), params)


class _Batch:
    """Stands in for the driver's synthetic source."""

    def __init__(self, batch):
        self._batch = batch

    def batch(self):
        return self._batch

    def __iter__(self):
        while True:
            yield self._batch


def patch_program(cfg: dict, seed: int, batch, observer_box: dict,
                  timed: int, counter, tracer):
    """Installs the seams; returns an undo callable."""
    from tpu_hc_bench.train import driver, step as step_mod

    fam = adapters.family_of(cfg)
    constants = [(importlib.import_module(mod), name, value)
                 for mod, name, value in fam.program_constants(cfg)]
    seams = [(driver, fam.SYNTHETIC_SOURCE),
             (step_mod, "make_train_state"),
             (step_mod, "build_train_step")] + [
                 (mod, name) for mod, name, _ in constants]
    for mod, name in seams:
        if not hasattr(mod, name):
            raise RuntimeError(f"the seam {mod.__name__}.{name} is gone: "
                               f"the train lane cannot be driven")
    saved = [(mod, name, getattr(mod, name)) for mod, name in seams]
    orig_state, orig_step = step_mod.make_train_state, \
        step_mod.build_train_step

    def make_state(model, pcfg, example_batch, rng=None):
        import jax

        state = orig_state(model, pcfg, example_batch, rng)
        shapes = jax.tree.map(lambda x: (x.shape, str(x.dtype)),
                              state.params)
        state = state.replace(params=None)
        tree = adapters.program_weights(cfg, seed)
        got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), tree)
        if got != shapes:
            raise RuntimeError("the program's parameter tree no longer "
                               "matches benchmarks/harness/adapters.py")
        return state.replace(params=tree)

    def build_step(*a, **k):
        obs = StepObserver(orig_step(*a, **k), cfg, seed, timed, counter,
                           tracer)
        observer_box["observer"] = obs
        return obs

    setattr(driver, fam.SYNTHETIC_SOURCE, lambda *a, **k: _Batch(batch))
    step_mod.make_train_state = make_state
    step_mod.build_train_step = build_step
    for mod, name, value in constants:
        setattr(mod, name, value)

    def undo():
        for mod, name, value in saved:
            setattr(mod, name, value)

    return undo


def run_cell(cell: dict, cfg: dict, mix: dict, args, t_proc: float,
             dev: dict, workdir: str, rehearsal: bool = False) -> dict:
    import jax

    from tpu_hc_bench import flags as flags_mod
    from tpu_hc_bench.topology import discover_layout
    from tpu_hc_bench.train import driver

    counter = device.CompileCounter()
    fam = adapters.family_of(cfg)
    arm = cfg["train_arm"]
    chips = cell["chips"]
    timed = traffic.generator_of(mix).steps(mix, args.seconds)
    batch = fam.train_batch(cfg, mix, args.seed, chips)
    flags = [f"--model={arm['model']}",
             f"--batch_size={mix['batch_per_chip']}",
             f"--num_warmup_batches={WARMUP_STEPS}",
             f"--num_batches={timed}", f"--display_every={timed}",
             f"--seed={args.seed % (2**31 - 1)}"
             ] + fam.train_flags(cfg, mix, list(arm["flags"]), rehearsal)
    pcfg = flags_mod.parse_flags(flags)
    layout = discover_layout(num_hosts=1, workers_per_host=chips)
    tracer = None
    if args.trace:
        tracer = tracing.WindowTracer(os.path.join(workdir, "trace"),
                                      args.seconds)
    box: dict = {}
    undo = patch_program(cfg, args.seed, batch, box, timed, counter, tracer)
    log = lambda m: print(f"[train] {m}", flush=True)     # noqa: E731
    try:
        result = driver.run_benchmark(pcfg, layout=layout,
                                      fabric_name=mix.get("fabric", "ici"),
                                      print_fn=log)
    finally:
        undo()
    obs = box["observer"]
    if obs.t0 is None or obs.t1 is None:
        raise RuntimeError("the observed step never reached the window")
    setup_s = obs.t0 - t_proc
    wall = obs.t1 - obs.t0
    mem_peak = device.memory_peak_bytes(chips)
    log(f"memory_stats: {jax.local_devices()[0].memory_stats()}")
    global_batch = mix["batch_per_chip"] * chips
    program = {
        "losses": [float(np.asarray(x)) for x in obs.losses],
        "grad_norms": {k: float(np.asarray(v))
                       for k, v in obs.grad_norms.items()},
        "update_norms": {k: float(np.asarray(v))
                         for k, v in obs.update_norms.items()},
    }
    ctx = {
        "cell": cell, "config": cfg, "family": fam, "mix": mix,
        "seconds": args.seconds,
        "window_s": wall, "setup_s": setup_s, "chips": chips,
        "steps": timed, "examples": timed * global_batch,
        "compiles_in_window": obs.compiles,
        "peaks": None if rehearsal else device.peaks(dev["kind"]),
        "program_result": {
            "p50_step_ms": result.p50_step_ms,
            "p50_step_granularity": result.p50_step_granularity},
        "trace": None,
    }
    log(f"set-up {setup_s:.1f}s; window {wall:.2f}s: {timed} steps x "
        f"{global_batch} examples; program's own reading "
        f"{result.images_per_sec_per_chip:.3f} examples/s/chip")
    del obs, box, result
    gc.collect()
    if tracer is not None:
        ctx["trace"] = tracer.reduce(chips)
    reference = checks.reference_train(cfg, args.seed, batch,
                                       pcfg.init_learning_rate)
    numbers = checks.train_numbers_from(cfg, program, reference)
    return {"ctx": ctx, "numbers": numbers, "attempted": timed,
            "failed": 0, "memory_peak_bytes": mem_peak, "setup_s": setup_s,
            "program": program, "reference": reference, "batch": batch,
            "lr": pcfg.init_learning_rate}
