"""Train-step builders: data-parallel SGD with XLA-collective allreduce.

The reference's per-step hot loop (SURVEY.md §3.1) is: forward/backward on
MKL-DNN kernels -> Horovod DistributedOptimizer allreduce (C++ fusion
buffer, 128 MiB) -> OpenMPI/HCOLL -> UCX -> IB verbs.  The TPU-native step
compiles the whole thing into one XLA program: forward/backward on the MXU,
gradient ``psum`` over the mesh's data axis (optionally through the
Horovod-style fusion buckets of ``parallel.collectives``), optimizer update
fused in.  Four variable-update modes extend the reference's
``--variable_update`` choices (flags.py):

- ``psum`` (default; reference ``horovod``): ``jax.shard_map`` over the
  mesh — replicated params, sharded batch, explicit fused gradient psum.
  ``--overlap_grad_comm=on`` (default) packs the fusion buckets in
  backward-completion order so XLA's async collectives overlap the
  remaining backward compute; ``off`` barriers the full gradient tree
  first (the serialized control arm).
- ``replicated``: GSPMD — params/batch get shardings, XLA inserts the
  collectives itself (the idiomatic-JAX arm of the A/B).
- ``zero1``: ZeRO-1 optimizer-state sharding — gradients reduce-SCATTER
  over the data axis (same fusion buckets, half the allreduce's ring
  traffic), each device owns and updates 1/N of the optimizer state
  (stacked ``[N, k]`` leaves sharded over the data axis), then the
  updated parameter shards all-gather back to replicated params.  Same
  Horovod per-worker-BN semantics as ``psum``; per-device optimizer
  bytes drop ~1/N — the HBM lever for the big-param members.
- fabric ``host`` (reference ``sock``): per-device grads are stacked to
  host, averaged in numpy, update applied on host — the slow-fallback
  smoke path.

BatchNorm: per-worker batch statistics during the step (Horovod semantics),
then cross-worker ``pmean`` of the updated running stats so the replicated
state stays bitwise-identical on every device.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_hc_bench.flags import BenchmarkConfig
from tpu_hc_bench.models import ModelSpec
from tpu_hc_bench.parallel.collectives import (
    all_gather_tree, allreduce_gradients, fused_psum_tree,
    reduce_scatter_tree, zero1_shard_len,
)
from tpu_hc_bench.parallel import fabric as fabric_mod
from tpu_hc_bench.resilience import guards
from tpu_hc_bench.topology import DATA_AXIS


class TrainState(flax.struct.PyTreeNode):
    step: jax.Array
    params: Any
    batch_stats: Any            # {} for models without BN
    opt_state: Any
    apply_fn: Callable = flax.struct.field(pytree_node=False)
    tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)


def make_optimizer(cfg: BenchmarkConfig) -> optax.GradientTransformation:
    """--optimizer dispatch (reference pins momentum, :74)."""
    lr = cfg.init_learning_rate
    if cfg.optimizer == "momentum":
        return optax.sgd(lr, momentum=cfg.momentum)
    if cfg.optimizer == "sgd":
        return optax.sgd(lr)
    if cfg.optimizer == "adam":
        return optax.adam(lr)
    if cfg.optimizer == "adamw":
        return optax.adamw(lr)
    if cfg.optimizer == "rmsprop":
        return optax.rmsprop(lr, decay=0.9, eps=1.0)  # tf_cnn rmsprop params
    raise ValueError(f"unknown optimizer {cfg.optimizer!r}")


def make_train_state(
    model, cfg: BenchmarkConfig, example_batch: tuple, rng: jax.Array | None = None
) -> TrainState:
    """Initialize params on host-side abstract init, then TrainState."""
    rng = rng if rng is not None else jax.random.PRNGKey(cfg.seed)
    inputs = example_batch[0]
    # jit the whole init: one compiled program instead of hundreds of
    # eager dispatches
    init_fn = jax.jit(functools.partial(model.init, train=False))
    variables = init_fn(
        {"params": rng, "dropout": jax.random.fold_in(rng, 1)},
        jnp.asarray(inputs[:1]),
    )
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    tx = make_optimizer(cfg)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=tx.init(params),
        apply_fn=model.apply,
        tx=tx,
    )


def abstract_train_state(
    model, cfg: BenchmarkConfig, example_batch: tuple
) -> TrainState:
    """Host-side zero-filled TrainState — a checkpoint template.

    Same tree structure/dtypes as ``make_train_state`` but built from
    ``jax.eval_shape``, so it allocates NO device memory (host zeros are
    copy-on-write pages).  Used where a template must coexist with a
    sharded model that may not fit one device (the PP checkpoint
    interchange).
    """
    rng = jax.random.PRNGKey(cfg.seed)
    inputs = np.asarray(example_batch[0])
    shapes = jax.eval_shape(
        functools.partial(model.init, train=False),
        {"params": rng, "dropout": jax.random.fold_in(rng, 1)},
        jax.ShapeDtypeStruct(inputs[:1].shape, inputs.dtype),
    )
    tx = make_optimizer(cfg)
    params_s = shapes["params"]
    opt_s = jax.eval_shape(tx.init, params_s)
    zeros = lambda tree: jax.tree.map(
        lambda s: np.zeros(s.shape, s.dtype), tree)
    return TrainState(
        step=np.zeros((), np.int32),
        params=zeros(params_s),
        batch_stats=zeros(shapes.get("batch_stats", {})),
        opt_state=zeros(opt_s),
        apply_fn=model.apply,
        tx=tx,
    )


# ---------------------------------------------------------------------
# ZeRO-1 state layout (--variable_update=zero1)
#
# Params stay replicated (the all-gather restores them every step); the
# OPTIMIZER state is built over per-device parameter shards and sharded
# over the data axis.  Layout: every param leaf of ``size`` elements owns
# a shard of ``k = ceil(size / N)`` elements per device; the optimizer
# state's array leaves are stacked ``[N, k]`` (row i = device i's shard)
# and placed with ``P(DATA_AXIS)`` on the leading dim, scalar leaves
# (e.g. adam's count) replicate.  The layout depends only on the param
# shapes and N — NOT on the fusion threshold — so checkpoints survive
# threshold changes; a zero1 checkpoint is NOT interchangeable with a
# psum/replicated one (different opt-state shapes; Orbax fails loudly on
# the structure mismatch).


def _stack_param_shards(p: jax.Array, num_shards: int) -> jax.Array:
    """``[N, k]`` stacked shards of a leaf (zero-padded to ``N * k``)."""
    k = zero1_shard_len(p.size, num_shards)
    flat = p.reshape(-1)
    pad = num_shards * k - p.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(num_shards, k)


def _local_param_shard(p: jax.Array, idx, num_shards: int) -> jax.Array:
    """Device ``idx``'s 1-D shard of a (replicated) param leaf — the
    slice the sharded optimizer updates."""
    k = zero1_shard_len(p.size, num_shards)
    flat = p.reshape(-1)
    pad = num_shards * k - p.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return jax.lax.dynamic_slice(flat, (idx * k,), (k,))


def make_zero1_state(model, cfg: BenchmarkConfig, example_batch: tuple,
                     num_shards: int,
                     rng: jax.Array | None = None) -> TrainState:
    """TrainState for the zero1 arm: replicated params, optimizer state
    built over stacked ``[N, k]`` param shards.

    ``tx.init`` runs ON the stacked tree, which equals per-shard init
    stacked for every registry optimizer (their inits are elementwise —
    zeros_like traces/moments plus scalar counts).
    """
    base = make_train_state(model, cfg, example_batch, rng)
    stacked = jax.tree.map(
        lambda p: _stack_param_shards(p, num_shards), base.params)
    return base.replace(opt_state=jax.jit(base.tx.init)(stacked))


def zero1_opt_template(params, tx, num_shards: int):
    """Host zero-filled optimizer-state template in the zero1 stacked
    layout for ``num_shards`` devices — the restore target when a
    checkpoint was saved under a DIFFERENT world size
    (``utils.checkpoint.restore_elastic``): the on-disk ``[N_saved, k]``
    leaves restore into this, then ``resplit_zero1_opt`` re-lays them
    out for the live world.  Pure ``eval_shape`` + ``np.zeros`` — no
    device memory."""
    stacked = jax.eval_shape(
        lambda p: jax.tree.map(
            lambda x: _stack_param_shards(x, num_shards), p), params)
    shapes = jax.eval_shape(tx.init, stacked)
    return jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), shapes)


def resplit_zero1_opt(opt_state, params, tx, n_old: int, n_new: int):
    """Re-layout a gathered zero1 optimizer state from ``[n_old, k]``
    stacked shards to ``[n_new, k']`` — the elastic-resume reshard.

    Stacked leaves are identified by comparing abstract ``tx.init``
    templates over the n_old-stacked vs n_new-stacked params: a leaf
    whose shapes AGREE between the two is stacking-invariant (scalar
    counts, schedule state — their shapes never depend on N; and when
    ``n_old == n_new`` every leaf trivially agrees and the identity is
    correct), because for a genuinely stacked leaf
    ``(n_old, ceil(s/n_old)) == (n_new, ceil(s/n_new))`` forces
    ``n_old == n_new``.  Comparing against the RAW-params template
    instead would misclassify any param whose own shape coincides with
    its stacked layout (e.g. a ``(n_old, k)`` kernel) and silently skip
    its resplit.  Stacked leaves are resplit on host via
    ``collectives.zero1_resplit_rows`` (strip old padding, re-pad for
    the new axis) — bitwise on the real elements in both directions.
    """
    from tpu_hc_bench.parallel.collectives import zero1_resplit_rows

    def stacked_opt_abs(n):
        stacked = jax.eval_shape(
            lambda p: jax.tree.map(
                lambda x: _stack_param_shards(x, n), p), params)
        return jax.eval_shape(tx.init, stacked)

    old_abs = stacked_opt_abs(n_old)
    new_abs = stacked_opt_abs(n_new)
    ref_abs = jax.eval_shape(tx.init, params)

    def conv(leaf, old_s, new_s, ref_s):
        if tuple(old_s.shape) == tuple(new_s.shape):
            return leaf        # stacking-invariant (or n_old == n_new)
        size = int(np.prod(ref_s.shape)) if ref_s.shape else 1
        return zero1_resplit_rows(np.asarray(jax.device_get(leaf)),
                                  size, n_new)

    return jax.tree.map(conv, opt_state, old_abs, new_abs, ref_abs)


def zero1_opt_specs(opt_state, num_shards: int):
    """PartitionSpec pytree for a zero1 optimizer state: stacked
    ``[N, ...]`` array leaves shard over the data axis, scalars (step
    counts, schedule state) replicate."""
    return jax.tree.map(
        lambda x: (P(DATA_AXIS)
                   if getattr(x, "ndim", 0) >= 2
                   and x.shape[0] == num_shards else P()),
        opt_state)


def place_zero1_state(state: TrainState, mesh: Mesh) -> TrainState:
    """Place a zero1 TrainState: everything replicated except the
    optimizer state's stacked leaves, which shard over the data axis."""
    num_shards = mesh.shape[DATA_AXIS]
    repl = NamedSharding(mesh, P())
    specs = zero1_opt_specs(state.opt_state, num_shards)
    opt_state = jax.tree.map(
        lambda s, x: jax.device_put(x, NamedSharding(mesh, s)),
        specs, state.opt_state)
    return state.replace(
        step=jax.device_put(state.step, repl),
        params=jax.device_put(state.params, repl),
        batch_stats=jax.device_put(state.batch_stats, repl),
        opt_state=opt_state,
    )


def _zero1_state_specs(state: TrainState, opt_specs) -> TrainState:
    """A TrainState-shaped pytree of PartitionSpecs (shard_map
    in/out_specs for the zero1 step): replicated everywhere except the
    sharded optimizer leaves."""
    repl = lambda tree: jax.tree.map(lambda _: P(), tree)
    return TrainState(
        step=P(),
        params=repl(state.params),
        batch_stats=repl(state.batch_stats),
        opt_state=opt_specs,
        apply_fn=state.apply_fn,
        tx=state.tx,
    )


def prep_inputs(inputs):
    """uint8 wire format -> normalized float32, inside the compiled step.

    Companion of ``ImageNetDataset(wire_dtype="uint8")``: the host ships
    raw 8-bit crops (4x less host->device traffic), and the cast+normalize
    fuses into the step's first ops.  Float inputs pass through untouched;
    the dtype branch is static at trace time.
    """
    if inputs.dtype != jnp.uint8:
        return inputs
    from tpu_hc_bench.data.imagenet import IMAGENET_MEAN, IMAGENET_STD

    return (inputs.astype(jnp.float32) - IMAGENET_MEAN) / IMAGENET_STD


def _loss_and_updates(state: TrainState, params, batch, dropout_rng,
                      is_text: bool, fused_xent: bool = False,
                      ctc: bool = False):
    """Forward + loss; returns (loss, new_batch_stats)."""
    variables = {"params": params}
    has_stats = bool(state.batch_stats)
    if has_stats:
        variables["batch_stats"] = state.batch_stats
    rngs = {"dropout": dropout_rng}
    inputs = prep_inputs(batch[0])
    # "losses" collects sown auxiliary terms (MoE load-balance); models
    # without them just return an empty dict
    mutable = (["batch_stats"] if has_stats else []) + ["losses"]
    logits, updated = state.apply_fn(
        variables, inputs, train=True, rngs=rngs, mutable=mutable
    )
    new_stats = updated.get("batch_stats", {})
    aux_terms = jax.tree.leaves(updated.get("losses", {}))
    if ctc:
        # deepspeech2: CTC over logit frames (optax's forward-backward
        # scan); all frames are valid (fixed synthetic length), labels
        # carry per-example padding
        _, labels, label_paddings = batch
        logit_paddings = jnp.zeros(logits.shape[:2], jnp.float32)
        losses = optax.ctc_loss(logits, logit_paddings, labels,
                                label_paddings)
        loss = losses.mean()
    elif is_text:
        _, targets, weights = batch
        if fused_xent:
            # Pallas blocked CE: one pass over the [tokens, vocab] logits
            from tpu_hc_bench.ops import softmax_xent

            b, s, v = logits.shape
            losses = softmax_xent(
                logits.reshape(b * s, v), targets.reshape(b * s)
            ).reshape(b, s)
        else:
            losses = optax.softmax_cross_entropy_with_integer_labels(
                logits, targets
            )
        loss = (losses * weights).sum() / jnp.maximum(weights.sum(), 1.0)
    else:
        _, labels = batch
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()
    if aux_terms:
        from tpu_hc_bench.models.moe import AUX_LOSS_COEF

        loss = loss + AUX_LOSS_COEF * sum(jnp.sum(t) for t in aux_terms)
    return loss, new_stats


def build_train_step(
    mesh: Mesh,
    cfg: BenchmarkConfig,
    spec: ModelSpec,
    fab: fabric_mod.Fabric = fabric_mod.Fabric.ICI,
):
    """Return ``step(state, batch, rng) -> (state, metrics)`` for the fabric.

    The returned callable takes host or device arrays whose leading dim is
    the global batch; sharding/replication is handled inside.
    """
    is_text = spec.is_text
    ctc = getattr(spec, "ctc", False)
    fuse = cfg.variable_update == "psum"
    zero1 = cfg.variable_update == "zero1"
    # --overlap_grad_comm: backward-order buckets (XLA async collectives
    # overlap the remaining backward) vs a full-tree barrier (comm
    # strictly after the complete backward — the A/B control)
    overlap = getattr(cfg, "overlap_grad_comm", "on") == "on"
    guard = guards.guard_mode(cfg)      # --on_nonfinite: off|flag|skip
    from tpu_hc_bench.topology import DCN_AXIS, SEQ_AXIS as _SEQ

    # a bound seq axis (any size — size 1 is the degenerate-SP mode)
    # routes through the (data, seq) shard_map arm
    sp = (getattr(cfg, "sequence_parallel", 1) > 1
          or _SEQ in mesh.axis_names)
    tp = getattr(cfg, "model_parallel", 1) > 1

    dcn = DCN_AXIS in mesh.axis_names
    if dcn and (sp or tp or getattr(cfg, "expert_parallel", 1) > 1):
        raise ValueError(
            "multislice (dcn) currently composes with data parallelism "
            "only")
    if dcn and fab is fabric_mod.Fabric.HOST:
        raise ValueError("fabric=host has no multislice layout")

    accum = getattr(cfg, "gradient_accumulation_steps", 1)
    if accum > 1 and fab is fabric_mod.Fabric.HOST:
        # flags.resolve() rejects the other unsupported arms; the fabric
        # is only known here
        raise ValueError(
            "--gradient_accumulation_steps is not supported on the host "
            "(sock-analog) fabric step")
    if zero1:
        # flags.resolve rejects the TP/EP/PP/SP compositions at flag
        # time; these guards catch programmatic construction and the
        # layouts only known here (fabric, multislice)
        if fab is fabric_mod.Fabric.HOST:
            raise ValueError(
                "--variable_update=zero1 needs a device fabric (ici): "
                "the host (sock-analog) path has no sharded optimizer")
        if dcn:
            raise ValueError(
                "--variable_update=zero1 composes with single-slice data "
                "parallelism only (the multislice (dcn, data) hierarchical "
                "reduce has no reduce-scatter layout yet)")
        if sp or tp or getattr(cfg, "expert_parallel", 1) > 1:
            raise ValueError(
                "--variable_update=zero1 composes with plain data "
                "parallelism only")
    if fab is fabric_mod.Fabric.HOST:
        return _build_host_step(mesh, cfg, is_text, ctc=ctc)
    if not sp and (tp or getattr(cfg, "expert_parallel", 1) > 1):
        # TP/EP run on the GSPMD arm: params enter committed with
        # tp_param_spec shardings and jit follows them
        return _build_gspmd_step(mesh, cfg, is_text, follow_inputs=True,
                                 ctc=ctc)
    if not sp and cfg.variable_update == "replicated":
        return _build_gspmd_step(mesh, cfg, is_text, dcn=dcn, ctc=ctc)

    # --sequence_parallel: same explicit-psum step over a (data, seq) mesh
    # — batch sharded over both axes, gradients reduced (with the same
    # fusion buckets) over both; the model was built seq-axis-aware.
    # DP x SP x TP (3-D hybrid): data/seq stay *manual* shard_map axes
    # (the ring/Ulysses attention's explicit ppermutes need them) while the
    # model axis stays *auto* — params enter model-sharded per
    # tp_param_spec and GSPMD partitions the matmuls inside the manual
    # body, inserting the Megatron all-reduces itself.
    from tpu_hc_bench.topology import SEQ_AXIS

    # multislice: gradients reduce over (dcn, data) — XLA emits the
    # hierarchical allreduce with the cross-slice phase on DCN
    axes = (DATA_AXIS, SEQ_AXIS) if sp else (DATA_AXIS,)
    if dcn:
        axes = (DCN_AXIS,) + axes
    if sp and tp:
        # fusion buckets concatenate grad tensors, which would force
        # all-gathers of the model-sharded grads under the auto axis —
        # reduce per-tensor instead
        fuse = False

    acc_bf16 = getattr(cfg, "accum_dtype", "f32") == "bf16"

    def _accumulated_grads(state, batch, dropout_rng):
        """lax.scan over ``accum`` microbatches: per-microbatch forward +
        backward with microbatch-sized activations (the memory win remat
        buys by recompute, bought here by splitting), grads/loss/stats
        summed in explicit accumulator trees, ONE allreduce afterwards.

        Accumulator dtype (``--accum_dtype``): ``f32`` (default) sums in
        float32 regardless of the param/grad dtype and returns the mean
        cast back to the grad dtype — exact for the zoo's f32 params.
        ``bf16`` sums bfloat16-quantized microbatch grads and KEEPS the
        tree bf16 through the allreduce and into the optimizer (optax
        promotes against its f32 traces): the accumulator HBM footprint
        AND the gradient wire bytes halve — the lever for param-bound
        members whose +1x-params f32 tree OOMs (llama_1b, gpt2_moe).
        Precision depends on the accumulation count: each microbatch
        addition quantizes to bf16's ~2^-9 relative step, and the
        rounding errors random-walk, so the accumulated-gradient error
        grows ~sqrt(N)*2^-9 — ~3 significant digits at accum=2, but only
        ~1.5-2 digits (~1-3% relative) at the accum=16-64 configs
        sweep_zoo.py pins for the large members (pinned by the accum=32
        arm of tests/test_train.py's bf16-vs-f32 delta tests).  Loss and
        BN stats always accumulate in f32.

        Microbatch semantics (standard accumulation): each microbatch's
        loss is mean-normalized over its own examples/weights, then the
        N means are averaged — identical to the full batch for uniform
        weights, the usual approximation otherwise.

        BN running stats: each microbatch EMA-updates from the SAME
        starting stats and the results are averaged, i.e. the running
        statistics advance by ONE decay step per optimizer step (toward
        the mean of the microbatch statistics) — NOT the N chained
        decays a sequential-microbatch implementation (e.g. torch-style
        accumulation loops) would apply.  Train-mode forwards are
        unaffected (BN normalizes with per-microbatch batch stats
        either way); only the eval-time running-stat warm-up rate
        differs, and one decay per optimizer step is the consistent
        choice here.
        """
        local = jax.tree.leaves(batch)[0].shape[0]
        if local % accum:
            raise ValueError(
                f"per-device batch {local} is not divisible by "
                f"--gradient_accumulation_steps={accum}")
        micro = jax.tree.map(
            lambda x: x.reshape((accum, local // accum) + x.shape[1:]),
            batch)
        rngs = jax.random.split(dropout_rng, accum)

        def body(carry, xs):
            g_acc, l_acc, s_acc = carry
            mb, rng_i = xs

            def loss_fn(p):
                return _loss_and_updates(state, p, mb, rng_i, is_text,
                                         cfg.fused_xent, ctc)

            (loss, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)
            # cast-then-add keeps the bf16 arm's sum in bf16 (an f32 add
            # followed by a downcast would materialize the f32 tree the
            # arm exists to avoid); the f32 arm's cast is a promote
            g_acc = jax.tree.map(
                lambda a, g: a + g.astype(a.dtype), g_acc, grads)
            s_acc = jax.tree.map(
                lambda a, x: a + x.astype(a.dtype), s_acc, stats)
            return (g_acc, l_acc + loss, s_acc), None

        f32_like = lambda x: jnp.zeros(
            x.shape, jnp.promote_types(x.dtype, jnp.float32))
        init = (
            jax.tree.map(
                (lambda x: jnp.zeros(x.shape, jnp.bfloat16))
                if acc_bf16 else f32_like,
                state.params),
            jnp.zeros((), jnp.float32),
            jax.tree.map(f32_like, state.batch_stats),
        )
        (g, l, s), _ = jax.lax.scan(body, init, (micro, rngs))
        if acc_bf16:
            # mean stays bf16 end-to-end (allreduce + optimizer see bf16)
            grads = jax.tree.map(
                lambda x: (x.astype(jnp.float32) / accum
                           ).astype(jnp.bfloat16), g)
        else:
            grads = jax.tree.map(
                lambda x, p: (x / accum).astype(p.dtype), g, state.params)
        stats = jax.tree.map(
            lambda x, o: (x / accum).astype(o.dtype), s, state.batch_stats)
        return l / accum, stats, grads

    # zero1's shard_map specs depend on the optimizer-state STRUCTURE,
    # known only when the first state arrives; the lazy step wrapper
    # below fills this before device_step first traces
    zero1_specs: dict = {}

    def device_step(state: TrainState, batch, dropout_rng):
        # per-device: local shard of the batch, replicated state
        for a in axes:
            dropout_rng = jax.random.fold_in(
                dropout_rng, jax.lax.axis_index(a)
            )

        if accum > 1:
            loss, new_stats, grads = _accumulated_grads(
                state, batch, dropout_rng)
        else:
            def loss_fn(p):
                return _loss_and_updates(state, p, batch, dropout_rng,
                                         is_text, cfg.fused_xent, ctc)

            (loss, new_stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params)
        if zero1:
            # ZeRO-1: reduce-SCATTER the gradient buckets (each device
            # receives only its 1/N shard of the mean grads), update the
            # local optimizer-state + param shards, all-gather the
            # updated param shards back to replicated params
            num_shards = jax.lax.axis_size(DATA_AXIS)
            idx = jax.lax.axis_index(DATA_AXIS)
            grad_shards = reduce_scatter_tree(
                grads, axis_name=DATA_AXIS,
                threshold_bytes=cfg.fusion_threshold_bytes,
                average=True, overlap=overlap)
        else:
            grads = allreduce_gradients(
                grads,
                axis_name=axes,
                threshold_bytes=cfg.fusion_threshold_bytes,
                fuse=fuse,
                overlap=overlap,
            )
        loss = jax.lax.pmean(loss, axes)
        if new_stats:
            # sync running stats so replicated state stays identical —
            # through the SAME fusion buckets as the gradients (round 5:
            # the world=2 HLO count showed resnet20's 44 collectives vs
            # bert's 2 were per-tensor BN-stat pmeans; bucketing them
            # turns 42 latency-bound crossings into one)
            if fuse or zero1:
                new_stats = fused_psum_tree(
                    new_stats, axis_name=axes,
                    threshold_bytes=cfg.fusion_threshold_bytes,
                    average=True)
            else:
                new_stats = jax.tree.map(
                    lambda s: jax.lax.pmean(s, axes), new_stats
                )
        if zero1:
            opt_specs = zero1_specs["opt"]
            param_shards = jax.tree.map(
                lambda p: _local_param_shard(p, idx, num_shards),
                state.params)
            # the local view of a [N, k] P(data)-sharded opt leaf is
            # [1, k]: drop the shard dim for the update, restore it for
            # the out_specs
            local_opt = jax.tree.map(
                lambda s, x: x.reshape(x.shape[1:])
                if s == P(DATA_AXIS) else x,
                opt_specs, state.opt_state)
            updates, new_local_opt = state.tx.update(
                grad_shards, local_opt, param_shards)
            new_shards = optax.apply_updates(param_shards, updates)
            new_params = all_gather_tree(
                new_shards, state.params, axis_name=DATA_AXIS,
                threshold_bytes=cfg.fusion_threshold_bytes,
                overlap=overlap)
            new_opt = jax.tree.map(
                lambda s, x: x[None] if s == P(DATA_AXIS) else x,
                opt_specs, new_local_opt)
        else:
            updates, new_opt = state.tx.update(grads, state.opt_state,
                                               state.params)
            new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt,
        )
        if guard != "off":
            # --on_nonfinite: in-step non-finite detection on loss AND the
            # (post-allreduce) grad global norm; "skip" drops the update
            # with a select INSIDE this compiled program — the only
            # donation-safe spelling, since the input state's buffers are
            # donated to this step (resilience/guards.py)
            if zero1:
                # each device sees only its grad shards; the flag must
                # agree across devices or the skip-select would fork the
                # replicated state — sum the squared norm over the axis
                # (= global_norm**2 of the full mean-gradient tree)
                gsq = sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree.leaves(grad_shards))
                gsq = jax.lax.psum(gsq, DATA_AXIS)
                ok = guards.finite_flag(loss) & jnp.isfinite(gsq)
            else:
                ok = guards.finite_flag(loss, grads)
            if guard == "skip":
                new_state = guards.select_state(ok, new_state, state)
            return new_state, {"loss": loss,
                               "nonfinite": guards.nonfinite_metric(ok)}
        return new_state, {"loss": loss}

    if cfg.forward_only:
        def fwd_only(state, batch, dropout_rng):
            for a in axes:
                dropout_rng = jax.random.fold_in(
                    dropout_rng, jax.lax.axis_index(a)
                )
            loss, _ = _loss_and_updates(
                state, state.params, batch, dropout_rng, is_text,
                cfg.fused_xent, ctc,
            )
            return state, {"loss": jax.lax.pmean(loss, axes)}
        device_step = fwd_only

    replicated = P()
    # dcn+data both split the leading batch dim (one tuple group); the SP
    # pair splits batch dim 0 (data) and seq dim 1 separately
    sharded = P((DCN_AXIS, DATA_AXIS)) if dcn else P(*axes)
    if zero1:
        # the in/out specs must name each sharded optimizer leaf, and the
        # optimizer-state STRUCTURE is only known from a live state — so
        # the shard_map is built lazily on the first call and cached (the
        # structure is fixed for the run; a second structure would be a
        # driver bug and jit would reject it anyway)
        cell: dict = {}

        def step(state, batch, rng):
            fn = cell.get("fn")
            if fn is None:
                num_shards = mesh.shape[DATA_AXIS]
                zero1_specs["opt"] = zero1_opt_specs(state.opt_state,
                                                     num_shards)
                state_specs = _zero1_state_specs(state, zero1_specs["opt"])
                shard_fn = jax.shard_map(
                    device_step,
                    mesh=mesh,
                    in_specs=(state_specs, sharded, replicated),
                    out_specs=(state_specs, replicated),
                    check_vma=False,
                )
                fn = jax.jit(shard_fn, donate_argnums=(0,))
                cell["fn"] = fn
                # obs.efficiency AOT-lowers this handle (see below)
                step._jitted = fn
            return fn(state, batch, rng)

        return step
    manual: dict = {}
    if sp and tp:
        # partial-manual shard_map: data/seq manual, model auto (GSPMD)
        manual = {"axis_names": frozenset(axes)}
    shard_fn = jax.shard_map(
        device_step,
        mesh=mesh,
        in_specs=(replicated, sharded, replicated),
        out_specs=(replicated, replicated),
        check_vma=False,
        **manual,
    )
    jitted = jax.jit(shard_fn, donate_argnums=(0,))

    def step(state, batch, rng):
        return jitted(state, batch, rng)

    # obs.efficiency AOT-lowers the same jitted callable (on abstract
    # avals — donation-safe) for compiled.cost_analysis() measured FLOPs
    step._jitted = jitted
    return step


def _build_gspmd_step(mesh: Mesh, cfg: BenchmarkConfig, is_text: bool,
                      follow_inputs: bool = False, dcn: bool = False,
                      ctc: bool = False):
    """``--variable_update=replicated``: the pure-GSPMD arm.

    No shard_map, no explicit collectives: the step is written over the
    *global* batch, ``in_shardings`` marks the batch as split over the data
    axis and the state as replicated, and XLA's SPMD partitioner inserts
    the gradient all-reduce itself.  This is the idiomatic-JAX counterpart
    to the explicit Horovod-style psum path, and the A/B between them is
    the fusion-tuning experiment the reference ran via
    HOROVOD_FUSION_THRESHOLD (run-tf-sing-ucx-openmpi.sh:105).

    Semantics note: BatchNorm statistics here are computed over the global
    batch (sync-BN) rather than per-worker — the one observable difference
    from the Horovod-semantics psum path, inherent to GSPMD.
    """
    guard = guards.guard_mode(cfg)      # --on_nonfinite: off|flag|skip

    def step_fn(state: TrainState, batch, dropout_rng):
        if cfg.forward_only:
            loss, _ = _loss_and_updates(
                state, state.params, batch, dropout_rng, is_text,
                cfg.fused_xent, ctc,
            )
            return state, {"loss": loss}

        def loss_fn(p):
            return _loss_and_updates(state, p, batch, dropout_rng, is_text,
                                      cfg.fused_xent, ctc)

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        updates, new_opt = state.tx.update(grads, state.opt_state, state.params)
        new_state = state.replace(
            step=state.step + 1,
            params=optax.apply_updates(state.params, updates),
            batch_stats=new_stats,
            opt_state=new_opt,
        )
        if guard != "off":
            # same in-step guard as the psum arm (donation-safe select)
            ok = guards.finite_flag(loss, grads)
            if guard == "skip":
                new_state = guards.select_state(ok, new_state, state)
            return new_state, {"loss": loss,
                               "nonfinite": guards.nonfinite_metric(ok)}
        return new_state, {"loss": loss}

    if follow_inputs:
        # TP: inputs arrive committed (shard_state_tp / shard_batch); jit
        # follows those shardings and GSPMD inserts the TP collectives
        jitted = jax.jit(step_fn, donate_argnums=(0,))
    else:
        from tpu_hc_bench.topology import DCN_AXIS

        repl = NamedSharding(mesh, P())
        data = NamedSharding(
            mesh, P((DCN_AXIS, DATA_AXIS)) if dcn else P(DATA_AXIS))
        jitted = jax.jit(
            step_fn,
            in_shardings=(repl, data, repl),
            out_shardings=(repl, repl),
            donate_argnums=(0,),
        )

    def step(state, batch, rng):
        return jitted(state, batch, rng)

    # see build_train_step: the handle obs.efficiency AOT-lowers for
    # compiled.cost_analysis() measured FLOPs
    step._jitted = jitted
    return step


def _build_host_step(mesh: Mesh, cfg: BenchmarkConfig, is_text: bool,
                     ctc: bool = False):
    """The `sock` path: grads computed per device, reduced through the host.

    Deliberately slow (device->host->device every step) but exercises the
    identical forward/backward, so it both smoke-tests without collectives
    and provides the slow arm of the fabric A/B (README.md:70-73).
    """

    def local_grads(state: TrainState, batch, dropout_rng):
        dropout_rng = jax.random.fold_in(
            dropout_rng, jax.lax.axis_index(DATA_AXIS)
        )

        def loss_fn(p):
            return _loss_and_updates(state, p, batch, dropout_rng, is_text,
                                      cfg.fused_xent, ctc)

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        # add leading device axis so out_specs can concatenate
        expand = lambda t: jax.tree.map(lambda x: x[None], t)
        return expand(grads), loss[None], expand(new_stats)

    grads_fn = jax.jit(jax.shard_map(
        local_grads,
        mesh=mesh,
        in_specs=(P(), P(DATA_AXIS), P()),
        out_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        check_vma=False,
    ))

    @jax.jit
    def apply_update(state: TrainState, grads, new_stats):
        updates, new_opt = state.tx.update(grads, state.opt_state, state.params)
        return state.replace(
            step=state.step + 1,
            params=optax.apply_updates(state.params, updates),
            batch_stats=new_stats,
            opt_state=new_opt,
        )

    def step(state, batch, rng):
        stacked_grads, losses, stacked_stats = grads_fn(state, batch, rng)
        # ONE host reduce for grads+stats+loss: at world > 1 the stacked
        # arrays span hosts, and host_allreduce is the only fetch that
        # handles non-addressable shards (a bare device_get would throw)
        grads, stats, loss = fabric_mod.host_allreduce(
            (stacked_grads, stacked_stats, losses))
        state = apply_update(state, grads, stats)
        return state, {"loss": jnp.asarray(loss)}

    return step


def weighted_text_metrics(logits, targets, weights):
    """Per-shard weighted-CE numerator/denominator + weighted top-1
    correct count — THE one home of the text-eval metric formulas (the
    DP, TP/EP-GSPMD, and PP eval arms must all report the same numbers,
    so they all call this)."""
    losses = optax.softmax_cross_entropy_with_integer_labels(
        logits, targets)
    num = (losses * weights).sum()
    den = weights.sum()
    correct = jnp.sum(
        (jnp.argmax(logits, -1) == targets) * weights).astype(jnp.float32)
    return num, den, correct


def build_eval_step(mesh: Mesh, cfg: BenchmarkConfig, spec: ModelSpec,
                    follow_inputs: bool = False, sp: bool = False,
                    dcn: bool = False, tp: bool = False):
    """Eval step (tf_cnn_benchmarks --eval): forward pass, loss + top-1.

    Uses running BN statistics (``train=False``) and no dropout.  Returns
    ``(loss, correct_count)`` reduced over the mesh.

    ``follow_inputs=True`` is the TP/EP arm (same trick as
    ``_build_gspmd_step(follow_inputs=True)``): the step is written over
    the global batch with no shard_map, the model-sharded params enter
    committed (``shard_state_tp``) and jit follows them — GSPMD inserts
    the Megatron all-reduces in the forward, so a TP-trained state
    evaluates in its native sharding instead of being re-replicated.

    ``sp=True`` is the sequence-parallel arm: shard_map over
    ``(data, seq)`` with the batch's [B, S] dims split over both axes and
    metrics psummed over both — same numbers as the DP arm by the shared
    ``weighted_text_metrics`` formulas.

    ``dcn=True`` (round 4) is the multislice arm: the batch dim splits
    over BOTH (dcn, data) and metrics psum hierarchically over them —
    exactly the train step's multislice reduction, forward-only.

    ``sp=True, tp=True`` (round 4) is the DP x SP x TP hybrid arm: the
    same partial-manual shard_map as the hybrid train step — data/seq
    stay manual (metric psums), the model axis stays auto, so the
    committed model shardings of ``shard_state_tp`` flow through and
    GSPMD inserts the Megatron all-reduces inside the manual body.
    """
    is_text = spec.is_text
    from tpu_hc_bench.topology import DCN_AXIS, SEQ_AXIS

    if dcn and sp:
        raise ValueError("multislice eval composes with data parallelism "
                         "only (matching the train step)")
    axes = (DATA_AXIS, SEQ_AXIS) if sp else (DATA_AXIS,)
    if dcn:
        axes = (DCN_AXIS,) + axes

    def device_eval(state: TrainState, batch):
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        logits = state.apply_fn(variables, prep_inputs(batch[0]),
                                train=False)
        if is_text:
            _, targets, weights = batch
            num, den, correct = weighted_text_metrics(
                logits, targets, weights)
            if not follow_inputs:
                # psum numerator/denominator separately: the GLOBAL
                # weighted mean (a mean of per-shard means would weight
                # shards equally regardless of their valid-token counts,
                # and the DP vs TP eval arms must report the same number)
                num = jax.lax.psum(num, axes)
                den = jax.lax.psum(den, axes)
            loss = num / jnp.maximum(den, 1.0)
        else:
            _, labels = batch
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            ).mean()
            if not follow_inputs:
                loss = jax.lax.pmean(loss, axes)
            correct = jnp.sum(jnp.argmax(logits, -1) == labels)
        correct = correct.astype(jnp.float32)
        if follow_inputs:
            # global-batch program: loss/correct are already global
            return loss, correct
        return loss, jax.lax.psum(correct, axes)

    if follow_inputs:
        return jax.jit(device_eval)
    # multislice: the (dcn, data) pair splits the leading batch dim as one
    # tuple group; SP splits batch dim 0 (data) and seq dim 1 separately
    bspec = P((DCN_AXIS, DATA_AXIS)) if dcn else P(*axes)
    manual: dict = {}
    if sp and tp:
        manual = {"axis_names": frozenset(axes)}
    shard_fn = jax.shard_map(
        device_eval,
        mesh=mesh,
        in_specs=(P(), bspec),
        out_specs=(P(), P()),
        check_vma=False,
        **manual,
    )
    return jax.jit(shard_fn)


def tp_param_spec(path: str, ndim: int, mode: str = "tp") -> P:
    """Megatron-style tensor-parallel PartitionSpec for a transformer param.

    Column-parallel QKV/FFN-in (shard the output features over the model
    axis), row-parallel out-proj/FFN-down (shard the input features) — the
    classic layout where each block needs exactly one all-reduce per
    direction, which GSPMD inserts automatically.  Non-transformer params
    (and everything unmatched) replicate, so the rules are safe to apply to
    any model in the zoo.

    Matches all three naming schemes in the zoo: BERT's anonymous FFN
    denses (``Dense_0``/``Dense_1``), GPT's ``fc``/``proj``, and llama's
    ``wq``/``wk``/``wv``/``wo`` attention + ``gate``/``up``/``down``
    SwiGLU projections (Q/K/V and FFN-in column-parallel, out-proj and
    FFN-down row-parallel; GQA KV heads shard like Q heads, so the TP
    degree must divide ``num_kv_heads`` — ``jax.device_put`` rejects the
    uneven case loudly).

    ``mode="ep"`` (``--expert_parallel``) restricts the rules to the MoE
    expert tensors: whole experts shard over the model axis, the dense
    trunk (attention, norms, embeddings) stays replicated — pure expert
    parallelism rather than the TP+EP hybrid.
    """
    from tpu_hc_bench.topology import MODEL_AXIS as M

    rules = [
        ("qkv/kernel", P(None, None, M, None)),    # [C, 3, heads, d]
        ("qkv/bias", P(None, M, None)),            # [3, heads, d]
        ("out/kernel", P(M, None, None)),          # [heads, d, C]
        ("Dense_0/kernel", P(None, M)),            # FFN in  [C, ffn]
        ("Dense_0/bias", P(M)),
        ("Dense_1/kernel", P(M, None)),            # FFN out [ffn, C]
        ("fc/kernel", P(None, M)),
        ("fc/bias", P(M)),
        ("proj/kernel", P(M, None)),
        # llama family (models/llama.py): DenseGeneral QKV kernels are
        # [C, heads, head_dim] (kv: [C, kv_heads, head_dim]); wo is
        # [heads, head_dim, C]; SwiGLU gate/up [C, ffn], down [ffn, C]
        ("wq/kernel", P(None, M, None)),
        ("wk/kernel", P(None, M, None)),
        ("wv/kernel", P(None, M, None)),
        ("wo/kernel", P(M, None, None)),
        ("gate/kernel", P(None, M)),
        ("up/kernel", P(None, M)),
        ("down/kernel", P(M, None)),
        # expert parallelism: whole experts live on model-axis shards
        # (models/moe.py wi [E, H, F] / wo [E, F, H]); GSPMD turns the
        # [E]-sharded dispatch/combine einsums into expert all-to-alls
        ("moe/wi", P(M, None, None)),
        ("moe/wo", P(M, None, None)),
    ]
    if mode == "ep":
        rules = [r for r in rules if r[0].startswith("moe/")]
    for suffix, spec in rules:
        if path.endswith(suffix) and len(spec) == ndim:
            return spec
    return P()


def _param_specs(params, mode: str = "tp") -> dict:
    """Pytree of PartitionSpecs matching ``params`` via tp_param_spec."""
    return jax.tree_util.tree_map_with_path(
        lambda path, v: tp_param_spec(
            "/".join(getattr(k, "key", str(k)) for k in path), v.ndim, mode
        ),
        params,
    )


def shard_state_tp(state: TrainState, mesh: Mesh,
                   mode: str = "tp") -> TrainState:
    """Place the state with tensor/expert-parallel param shardings.

    Params (and the optimizer state, which mirrors the param tree — e.g.
    the momentum trace) are sharded per ``tp_param_spec``; everything else
    replicates.  The jitted GSPMD step then *follows* these committed
    shardings, so the same ``_build_gspmd_step`` serves DP, DP x TP, and
    DP x EP (``mode="ep"``).
    """
    specs = _param_specs(state.params, mode)
    if not any(
        s != P() for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    ):
        if mode == "ep":
            raise ValueError(
                "expert_parallel > 1 but no param matched an expert rule: "
                "the model has no MoE layers (use an moe member, e.g. "
                "gpt2_moe), so EP would only halve the data-parallel degree"
            )
        raise ValueError(
            "model_parallel > 1 but no param matched a tensor-parallel "
            "rule: this model's param names have no TP layout (only the "
            "transformer families do), so TP would silently replicate "
            "every param and degrade to DP with a smaller global batch"
        )

    def put(spec_tree, tree):
        return jax.tree.map(
            lambda spec, x: jax.device_put(x, NamedSharding(mesh, spec)),
            spec_tree, tree,
        )

    params = put(specs, state.params)
    # optimizer state: shard any subtree whose structure mirrors params
    # (momentum/adam moments), replicate the rest (counts, empty states)
    def put_opt(node):
        if jax.tree.structure(node) == jax.tree.structure(state.params):
            return put(specs, node)
        return jax.device_put(node, NamedSharding(mesh, P()))

    opt_state = jax.tree.map(
        put_opt, state.opt_state,
        is_leaf=lambda n: jax.tree.structure(n)
        == jax.tree.structure(state.params),
    )
    rest = NamedSharding(mesh, P())
    return state.replace(
        step=jax.device_put(state.step, rest),
        params=params,
        batch_stats=jax.device_put(state.batch_stats, rest),
        opt_state=opt_state,
    )


def replicate_state(state: TrainState, mesh: Mesh) -> TrainState:
    """Place the state replicated over the mesh (params live on-device)."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(state, sharding)


def shard_batch(batch: tuple, mesh: Mesh, spec: P | None = None) -> tuple:
    """Place a global host batch sharded over the data axis (or ``spec`` —
    e.g. ``P(DATA_AXIS, SEQ_AXIS)`` for sequence-parallel token batches).
    On a multislice mesh the batch dim splits over BOTH (dcn, data)."""
    from tpu_hc_bench.topology import DCN_AXIS

    if spec is None:
        spec = (P((DCN_AXIS, DATA_AXIS))
                if DCN_AXIS in mesh.axis_names else P(DATA_AXIS))
    sharding = NamedSharding(mesh, spec)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)


def shard_batch_local(batch: tuple, mesh: Mesh,
                      spec: P | None = None) -> tuple:
    """Place a batch from per-process LOCAL rows (round 14).

    ``shard_batch`` takes the full global batch from every process and
    lets ``device_put`` keep the local slice — bitwise-safe but W-fold
    redundant on the host (each worker decodes/ships rows its devices
    never hold).  Here each process passes only its own rows and
    ``jax.make_array_from_process_local_data`` assembles the global
    array.  At world=1 the two are identical (the local rows ARE the
    global batch).  ``shard_batch`` stays as the driver's
    ``--full_batch_identity`` arm.
    """
    from tpu_hc_bench.topology import DCN_AXIS

    if spec is None:
        spec = (P((DCN_AXIS, DATA_AXIS))
                if DCN_AXIS in mesh.axis_names else P(DATA_AXIS))
    sharding = NamedSharding(mesh, spec)
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(sharding, x),
        batch)
