"""tf_cnn_benchmarks-compatible flag surface, translated for TPU.

The reference drives ``tf_cnn_benchmarks.py`` with a fixed flag set assembled
in ``benchmark-scripts/run-tf-sing-ucx-openmpi.sh:62-81`` (identical at
``run-tf-sing-libfabric-intelmpi.sh:63-82``).  That flag set is the de-facto
API of the reference framework, so this module reproduces it: every flag the
reference passes parses here, with TPU-meaningful semantics where a literal
interpretation would be wrong for the hardware:

- ``--device=cpu`` / ``--mkl=TRUE``: the reference's compute engine selection
  (Intel-MKL CPU kernels).  On TPU the engine is XLA:TPU; ``device`` accepts
  ``cpu|tpu`` and controls the JAX platform, ``mkl`` parses as a no-op.
- ``--data_format=NCHW``: optimal for MKL-DNN, pessimal for TPU (the MXU wants
  NHWC so the channel dim lands on the 128-lane minor axis).  We parse both
  and *translate* to NHWC by default, recording the translation in the
  resolved config (see ``BenchmarkConfig.resolve``).
- ``--num_intra_threads`` / ``--num_inter_threads`` / ``--kmp_blocktime`` /
  ``--kmp_affinity``: CPU thread-pool tuning (reference lines :67-70,76).
  Parsed and preserved for log parity, but no-ops on TPU — XLA owns
  scheduling inside a compiled computation.
- ``--variable_update=horovod --horovod_device=cpu
  --local_parameter_device=cpu`` (reference :77-79): the reference's
  data-parallel engine selection.  Here ``variable_update`` accepts
  ``horovod|psum|replicated|zero1`` and maps to gradient ``psum`` over the
  mesh's data axis (the TPU-native equivalent of Horovod's fused MPI
  allreduce); ``zero1`` is the ZeRO-1 optimizer-state-sharding arm
  (reduce-scatter + sharded update + all-gather, train/step.py).

Defaults mirror the constants hardcoded in the reference launcher
(``run-tf-sing-ucx-openmpi.sh:32-35``): 50 warmup batches, 100 timed batches,
model resnet50, display every 10 steps (``:71``), momentum optimizer
(``:74``), imagenet data (``:81``).
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Any, Sequence

# Experiment constants pinned by the reference launcher
# (run-tf-sing-ucx-openmpi.sh:32-35).
DEFAULT_WARMUP_BATCHES = 50
DEFAULT_NUM_BATCHES = 100
DEFAULT_MODEL = "resnet50"
DEFAULT_DISPLAY_EVERY = 10  # --display_every=10 (:71)

# Horovod fusion buffer: 128 MiB (HOROVOD_FUSION_THRESHOLD=134217728,
# run-tf-sing-ucx-openmpi.sh:105).  The XLA analog is the all-reduce
# combine threshold; see tpu_hc_bench.parallel.fabric.
DEFAULT_FUSION_THRESHOLD_BYTES = 134217728

# attention impls that shard (or degenerately carry) a sequence axis —
# selecting one at --sequence_parallel=1 routes through the degenerate-SP
# block in resolve(), which translates variable_update replicated->psum
SEQ_SHARDED_IMPLS = ("ring", "ulysses", "ulysses_flash")

# --- serving lane (round 16) ------------------------------------------
# Training-only knobs that have no meaning under `python -m tpu_hc_bench
# serve`: a serving run that silently accepted --gradient_accumulation_
# steps or --on_nonfinite=rewind would wear a banner describing machinery
# that never ran, so resolve() rejects any of these the operator
# explicitly set (flag-time, the same loudness contract as every other
# invalid combination).  Knobs shared by both lanes (model, seed, dtype,
# data_dir for the prompt corpus, compile_cache, metrics_dir, device,
# hbm_budget, config) are deliberately absent: ``--use_fp16`` serves in
# bfloat16 (matrices and KV pages held so, a recurrent state in float32;
# float32 stays the default).
TRAIN_ONLY_FLAGS = (
    "batch_size", "num_warmup_batches", "num_batches", "num_epochs",
    "display_every", "optimizer", "forward_only", "eval",
    "init_learning_rate", "momentum", "data_format",
    "variable_update", "overlap_grad_comm", "fusion_threshold_bytes",
    "num_intra_threads", "num_inter_threads", "kmp_blocktime",
    "kmp_affinity", "datasets_num_private_threads",
    "datasets_repeat_cached_sample", "train_dir", "save_model_steps",
    "async_checkpoint", "prefetch_depth", "input_service",
    "service_decode_workers", "full_batch_identity", "on_nonfinite",
    "max_bad_steps", "resume", "step_timeout_s", "keep_checkpoints",
    "inject_fault", "profile_steps", "fabric_ceiling", "num_slices",
    "fused_conv", "fused_xent", "use_space_to_depth", "seq_len",
    "wire_dtype", "gradient_accumulation_steps", "accum_dtype",
    "model_parallel", "expert_parallel", "pipeline_parallel",
    "num_microbatches", "sequence_parallel", "gradient_checkpointing",
    "attention_impl", "moe_impl", "moe_capacity_factor", "moe_f_chunk",
    "scan_layers", "rnn_impl",
)

# The serving lane's own knobs — rejected with the mirror-image error
# when explicitly set on a TRAINING run, so neither lane ever silently
# ignores the other's flags.
SERVE_ONLY_FLAGS = (
    "arrival", "arrival_rate", "num_requests", "serve_buckets",
    "max_in_flight", "kv_page_size", "kv_pages", "max_prompt_len",
    "max_output_len", "batching", "decode_attention", "quant",
    "decode_block_pages", "slo_e2e_ms",
    # round 23: overload/failure survival — the serve lane's own
    # spellings (the train lane's inject_fault/resume/step_timeout_s
    # stay train-only; neither lane ever silently eats the other's)
    "deadline_ms", "shed", "kv_preempt", "serve_faults",
    "serve_journal", "serve_resume", "serve_step_timeout_s",
    # round 25: lazy KV reservation + shared-prefix cache
    "kv_reserve", "prefix_cache", "kv_growth_headroom",
)


def parse_serve_buckets(spec: str, max_in_flight: int) -> tuple[int, ...]:
    """Resolve ``--serve_buckets`` into the decode batch-bucket ladder.

    ``auto`` = the power-of-two ladder 1, 2, 4, ... up to
    ``max_in_flight`` (``max_in_flight`` itself appended when it is not
    a power of two), so every admissible in-flight count has a bucket
    within 2x.  An explicit spec is comma-separated positive ints
    (``"1,4,8"``); loud on malformed input.  The engine AOT-compiles
    one decode executable per bucket at warmup — the ladder IS the set
    of shapes that can ever run, so a request count above the top
    bucket is an admission-control clamp, never a new compile.
    """
    if max_in_flight < 1:
        raise ValueError(f"--max_in_flight must be >= 1: {max_in_flight}")
    if spec == "auto":
        ladder = []
        b = 1
        while b < max_in_flight:
            ladder.append(b)
            b *= 2
        ladder.append(max_in_flight)
        return tuple(ladder)
    try:
        vals = sorted({int(v) for v in spec.split(",") if v.strip()})
    except ValueError:
        raise ValueError(
            f"--serve_buckets must be 'auto' or comma-separated ints "
            f"(decode batch buckets): {spec!r}") from None
    if not vals or vals[0] < 1:
        raise ValueError(
            f"--serve_buckets needs at least one positive bucket: {spec!r}")
    return tuple(vals)


def parse_profile_steps(spec: str) -> tuple[int, int]:
    """Parse ``--profile_steps=a:b`` into an inclusive timed-step window.

    Loud on malformed input (resolve() calls this so a bad window dies at
    flag time, not after 50 warmup steps).  ``b`` may exceed the run
    length — the trace then simply stops when the run does.
    """
    parts = spec.split(":")
    try:
        if len(parts) != 2:
            raise ValueError
        a, b = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(
            f"--profile_steps must be 'a:b' (1-based timed-step bounds, "
            f"inclusive): {spec!r}") from None
    if a < 1 or b < a:
        raise ValueError(
            f"--profile_steps window must satisfy 1 <= a <= b: {spec!r}")
    return a, b


def _parse_bool(v: str | bool) -> bool:
    """tf_cnn_benchmarks accepts TRUE/False/true/... for boolean flags."""
    if isinstance(v, bool):
        return v
    s = str(v).strip().lower()
    if s in ("true", "t", "1", "yes"):
        return True
    if s in ("false", "f", "0", "no"):
        return False
    raise argparse.ArgumentTypeError(f"not a boolean: {v!r}")


@dataclasses.dataclass
class BenchmarkConfig:
    """Resolved benchmark configuration.

    Field names follow the reference flag names (minus leading dashes) so a
    log line of the resolved config reads like the reference's echoed command
    (run-tf-sing-ucx-openmpi.sh:111).
    """

    # --- core experiment knobs (reference :32-35, :62-66) ---
    batch_size: int = 64                      # per-worker batch (README.md:70)
    num_warmup_batches: int = DEFAULT_WARMUP_BATCHES
    # None = unset (resolve() fills DEFAULT_NUM_BATCHES) so an explicit
    # --num_batches=100 still conflicts with --num_epochs
    num_batches: int | None = None
    num_epochs: float = 0.0                   # tf_cnn_benchmarks --num_epochs:
                                              # when set, num_batches is
                                              # derived from the dataset size
                                              # and the resolved global batch
                                              # (driver, needs the layout)
    model: str = DEFAULT_MODEL
    display_every: int = DEFAULT_DISPLAY_EVERY
    optimizer: str = "momentum"               # --optimizer=momentum (:74)
    forward_only: bool = False                # --forward_only=False (:75)
    eval: bool = False                        # tf_cnn_benchmarks --eval:
                                              # forward + top-1 accuracy
    init_learning_rate: float = 0.01          # tf_cnn_benchmarks flag; the
                                              # reference leaves the default
    momentum: float = 0.9                     # tf_cnn_benchmarks default

    # --- data (reference :80-81) ---
    data_dir: str | None = None               # None => synthetic data
    data_name: str = "imagenet"
    data_format: str = "NHWC"                 # reference passes NCHW (:73);
                                              # translated, see resolve()

    # --- compute engine selection (reference :76-77) ---
    device: str = "tpu"                       # reference: cpu; ours: tpu
    mkl: bool = False                         # --mkl=TRUE no-ops on TPU
    use_fp16: bool = False                    # fp32 parity default; bf16 is
                                              # the TPU fast path (see
                                              # compute_dtype)

    # --- distribution (reference :77-79) ---
    variable_update: str = "psum"             # horovod|psum|replicated
    horovod_device: str = "tpu"               # parsed for parity
    local_parameter_device: str = "tpu"

    # --- CPU thread tuning: parsed, preserved, no-op on TPU (:67-70,76) ---
    num_intra_threads: int = 0
    num_inter_threads: int = 2
    kmp_blocktime: int = 1
    kmp_affinity: str = "granularity=fine,noverbose,compact,1,0"
    # tf_cnn_benchmarks' input-pipeline private threadpool — here it is the
    # REAL width of the host JPEG decode pool (data/imagenet.py); 0 = auto
    datasets_num_private_threads: int = 0
    # tf_cnn_benchmarks --datasets_repeat_cached_sample: decode a small set
    # of real batches ONCE, keep them device-resident, and cycle them every
    # step.  Measures the DEVICE-side real-data step cost (uint8 wire cast +
    # normalize inside the compiled step) with the host decode/transfer wall
    # taken out.  DELIBERATE DEVIATION from the reference flag's mechanics:
    # tf_cnn's version (ds.take(1).cache().repeat()) repeats one cached
    # record through the LIVE host pipeline, still paying the per-step
    # host->device transfer; here the batches are fully device-resident and
    # the decode pool is shut down, so transfer cost is removed too —
    # a stricter isolation, but numbers are NOT comparable to reference
    # runs of the same flag (BASELINE.md round-4 real-data note).
    datasets_repeat_cached_sample: bool = False

    # --- TPU-native additions (no reference analog) ---
    fusion_threshold_bytes: int = DEFAULT_FUSION_THRESHOLD_BYTES
    overlap_grad_comm: str = "on"             # psum/zero1 arms: pack the
                                              # gradient fusion buckets in
                                              # backward-completion order
                                              # so XLA's async collectives
                                              # overlap the remaining
                                              # backward ("on", default);
                                              # "off" barriers the full
                                              # grad tree first — comm
                                              # strictly after the
                                              # complete backward (the
                                              # serialized A/B control)
    seed: int = 0
    num_classes: int = 1000                   # imagenet label space
    trace_dir: str | None = None              # jax.profiler trace output; the
                                              # structured upgrade of the
                                              # reference's I_MPI_DEBUG tracing
    profile_steps: str | None = None          # "a:b": profile timed steps
                                              # a..b into --trace_dir (window
                                              # bounds observed via the
                                              # timeline's completion markers);
                                              # unset = the legacy first-
                                              # sync-window trace
    metrics_dir: str | None = None            # per-run observability artifact:
                                              # metrics.jsonl + manifest.json
                                              # (obs.metrics; worker 0 writes)
                                              # + per-host heartbeat files
                                              # metrics.<k>.jsonl (obs.fleet;
                                              # every process writes its own)
    flight_recorder: str = "on"               # on|off: the always-on host
                                              # span recorder (obs.timeline)
                                              # — bounded in-memory ring on
                                              # every run; with --metrics_dir
                                              # each rank also persists
                                              # spans.<k>.jsonl and the
                                              # watchdog/OOM/preempt paths
                                              # drop timeline_dump.json.
                                              # "off" is the bare-benchmark
                                              # paranoia switch (measured
                                              # overhead is <1% of a
                                              # steady-state step)
    fabric_ceiling: str | None = None         # measured-fabric sweep JSON
                                              # (microbench.osu --json): the
                                              # run judges its achieved
                                              # collective bandwidth against
                                              # this ceiling (obs.efficiency)
    hbm_budget: str | None = None             # device-memory budget for the
                                              # pre-run AOT check
                                              # (obs.memory): bytes with an
                                              # optional KB/MB/GB suffix, or
                                              # "auto" = the live device's
                                              # measured bytes_limit.  The
                                              # step program's
                                              # memory_analysis() is
                                              # compared at run start and a
                                              # loud WARNING fires when it
                                              # exceeds the budget — before
                                              # the full run's compile is
                                              # paid for.  unset = off
    num_slices: int = 0                       # fabric=dcn multislice layout:
                                              # slices x hosts/slice x chips
                                              # (0 = one slice per host)
    fused_conv: bool = False                  # Pallas fused BN-relu-conv3x3
                                              # bottleneck segment (v1
                                              # resnets; ops/fused_conv.py)
    fused_xent: bool = False                  # Pallas blocked cross-entropy
                                              # for large-vocab (MLM) heads
    use_space_to_depth: bool = False          # ResNet stem as 4x4/s1 conv on
                                              # 2x2-packed input (MXU-friendly)
    seq_len: int | None = None                # text models: override the
                                              # registry sequence length
                                              # (long-context runs)
    wire_dtype: str = "uint8"                 # real-data host->device wire
                                              # format; uint8 = 4x less
                                              # traffic, normalize on device
    gradient_accumulation_steps: int = 1      # split each step's batch into
                                              # N microbatches (lax.scan),
                                              # average grads, ONE allreduce
                                              # + optimizer update — batch
                                              # scaling without remat's
                                              # recompute or PP's pipeline
    accum_dtype: str = "f32"                  # microbatch grad-accumulator
                                              # dtype: f32 (exact mean) |
                                              # bf16 (halves the accumulator
                                              # tree AND the allreduce
                                              # bytes — the HBM lever for
                                              # param-bound members whose
                                              # f32 tree OOMs: llama_1b,
                                              # gpt2_moe; ~3 significant
                                              # digits per grad)
    model_parallel: int = 1                   # tensor-parallel degree over
                                              # the mesh "model" axis
                                              # (Megatron-style GSPMD
                                              # shardings; transformers)
    expert_parallel: int = 1                  # expert-parallel degree: MoE
                                              # expert dim sharded over the
                                              # mesh "model" axis (GSPMD
                                              # all-to-all dispatch);
                                              # exclusive with model_parallel
    pipeline_parallel: int = 1                # pipeline stages over the mesh
                                              # "pipe" axis (GPipe
                                              # microbatching via ppermute;
                                              # GPT decoder family)
    num_microbatches: int = 0                 # GPipe microbatches per step
                                              # (0 -> 2x pipeline stages)
    sequence_parallel: int = 1                # sequence shards over the mesh
                                              # "seq" axis (ring /
                                              # ulysses[_flash] attention;
                                              # text models)
    virtual_devices: int | None = None        # debug: provision N virtual
                                              # CPU devices (multi-chip
                                              # paths without hardware)
    gradient_checkpointing: bool = False      # remat transformer layers:
                                              # trade FLOPs for activation
                                              # HBM (long-context headroom)
    attention_impl: str = "dense"             # transformer attention kernel:
                                              # dense|flash single-device
                                              # (flash = Pallas blocked
                                              # softmax); ring|ulysses|
                                              # ulysses_flash under
                                              # --sequence_parallel
    moe_impl: str = "einsum"                  # einsum|ragged: MoE dispatch
                                              # (einsum = GShard GSPMD/EP;
                                              # ragged = grouped-matmul
                                              # ragged_dot fast DP path)
    moe_capacity_factor: float = 1.25         # einsum slots/expert =
                                              # ceil(cf*k*S/E): the
                                              # token-drop pressure valve
                                              # for long-context MoE
    moe_f_chunk: int = 0                      # ragged MoE: FFN-dim tile of
                                              # the grouped matmuls (0 =
                                              # full width, measured best;
                                              # BASELINE.md MoE round 4)
    scan_layers: bool = False                 # decoders: lax.scan over
                                              # stacked layers (one
                                              # compiled body; the
                                              # program-size lever for
                                              # deep/HLO-heavy stacks)
    rnn_impl: str = "hoisted"                 # hoisted|bidi|flax: RNN
                                              # members' GRU form (hoisted =
                                              # input projections batched
                                              # out of the scan; bidi = both
                                              # BiGRU directions in one scan,
                                              # a recorded-null A/B arm;
                                              # flax = linen.RNN control)
    train_dir: str | None = None              # tf_cnn_benchmarks --train_dir:
                                              # save checkpoints here during
                                              # training; --eval restores the
                                              # latest from it
    save_model_steps: int = 0                 # save every N timed steps
                                              # (0 = final state only; the
                                              # steps analog of tf_cnn's
                                              # --save_model_secs)

    # --- latency hiding (round 10) ---
    async_checkpoint: bool = True             # overlap checkpoint writes with
                                              # the step loop: snapshot blocks
                                              # (small), the Orbax write +
                                              # commit runs in a background
                                              # thread (in-flight <= 1).
                                              # Single-process DP/TP/EP/SP
                                              # only; emergency saves,
                                              # io_error@ckpt injection,
                                              # multi-host, and PP saves stay
                                              # synchronous (driver)
    compile_cache: str | None = None          # persistent XLA compile cache:
                                              # unset = on, at
                                              # $JAX_COMPILATION_CACHE_DIR or
                                              # <checkout>/.jax_cache
                                              # (utils.compile_cache); "off"
                                              # is the only value it takes
    prefetch_depth: int = 2                   # host->device input pipeline
                                              # lookahead (real-data runs):
                                              # batches kept in flight so
                                              # decode + DMA overlap the
                                              # running step; also the
                                              # double-buffer depth of the
                                              # host decode queue and the
                                              # input-service ring slots

    # --- host-level shared input service (round 13) ---
    input_service: str = "auto"               # on|off|auto: one decode pool
                                              # per host serving all local
                                              # workers over shared-memory
                                              # batch rings (data/service.py)
                                              # instead of a private pool
                                              # per process.  auto = on when
                                              # >1 worker shares the host;
                                              # off = the per-process
                                              # pipeline (the control arm)
    service_decode_workers: int = 0           # width of the HOST decode
                                              # pool under the service
                                              # (0 = auto: cpu_count-1 for
                                              # the whole host)

    # --- autotuner (round 14) ---
    config: str = "manual"                    # manual: flags mean what
                                              # they say (the reference
                                              # contract); auto: resolve()
                                              # loads this member's tuned
                                              # row from the registry
                                              # (artifacts/tuned/
                                              # <hardware_key>.json,
                                              # tpu_hc_bench.tune) and
                                              # applies its lever
                                              # overrides to every field
                                              # left at the default —
                                              # explicit flags win; no
                                              # row falls back LOUDLY to
                                              # BASELINE defaults
    full_batch_identity: bool = False         # multi-worker input: ship
                                              # each process the FULL
                                              # global batch and let
                                              # device_put keep the local
                                              # slice (the conservative
                                              # pre-round-14 arm, kept
                                              # for the bitwise A/B).
                                              # Default off: each process
                                              # builds the global array
                                              # from its LOCAL rows
                                              # (jax.make_array_from_
                                              # process_local_data) and
                                              # the input service serves
                                              # sliced rings — the W-fold
                                              # host-decode saving

    # --- resilience (round 8; no reference analog — SURVEY.md §5 notes
    # the reference just dies) ---
    on_nonfinite: str = "abort"               # non-finite loss/grad-norm
                                              # policy: abort (fail the run
                                              # loudly) | skip (drop the
                                              # update in-step, donation-
                                              # safe) | rewind (restore the
                                              # last checkpoint + skip a
                                              # window of batches)
    max_bad_steps: int = 10                   # consecutive-failure budget
                                              # for skip/rewind: a poisoned
                                              # run still terminates
    resume: str = "auto"                      # --train_dir restore policy:
                                              # auto (restore latest
                                              # complete checkpoint if any)
                                              # | never (fresh init) | must
                                              # (error if none — crash-loop
                                              # relaunches shouldn't
                                              # silently restart from step 0)
                                              # | elastic (must + the saved
                                              # topology sidecar may differ
                                              # from the live mesh: the
                                              # state is reassembled and
                                              # re-placed — zero1 opt
                                              # shards resplit to the new
                                              # world size — with a loud
                                              # one-line plan; genuinely
                                              # incompatible arm/layout
                                              # transitions refuse with an
                                              # actionable error)
    step_timeout_s: str | None = None         # hung-step watchdog: seconds,
                                              # "auto" (k x warmup mean step
                                              # time), unset/off = disabled
    keep_checkpoints: int = 0                 # retention GC: keep only the
                                              # newest N complete
                                              # checkpoints (0 = keep all)
    inject_fault: str | None = None           # deterministic fault
                                              # injection, e.g. nan_loss@40,
                                              # hang@80:30,sigterm@120,
                                              # io_error@ckpt
                                              # (resilience/inject.py)

    # --- serving lane (round 16; tpu_hc_bench.serve) ---
    workload: str = "train"                   # train|serve: which lane this
                                              # config drives.  Set by the
                                              # serve CLI (`python -m
                                              # tpu_hc_bench serve`), never a
                                              # user flag — the entry point
                                              # IS the workload selection.
                                              # resolve() rejects the other
                                              # lane's knobs loudly under
                                              # either value.
    arrival: str = "poisson"                  # synthetic request arrival
                                              # process: poisson (memoryless
                                              # open loop) | bursty (on/off
                                              # duty cycle) | diurnal
                                              # (sinusoidal rate — the
                                              # day/night traffic shape,
                                              # compressed)
    arrival_rate: float = 8.0                 # mean request arrival rate,
                                              # requests/second (the load
                                              # axis of the SLO report)
    num_requests: int = 64                    # requests in the closed-loop
                                              # run (the serving analog of
                                              # --num_batches)
    serve_buckets: str = "auto"               # decode batch-bucket ladder:
                                              # auto = powers of two up to
                                              # max_in_flight, or explicit
                                              # "1,2,8".  Every bucket is
                                              # AOT-compiled at warmup; the
                                              # ladder is the complete set
                                              # of shapes that can ever run
    max_in_flight: int = 8                    # continuous-batching admission
                                              # cap: requests decoding
                                              # concurrently (also the
                                              # static arm's batch size)
    kv_page_size: int = 16                    # tokens per KV-cache page
                                              # (vLLM-style paged KV: decode
                                              # members allocate cache in
                                              # fixed pages, never per-
                                              # sequence max-length slabs)
    kv_pages: int = 0                         # total pages in the pool
                                              # (0 = auto: enough for
                                              # max_in_flight sequences at
                                              # max_prompt_len +
                                              # max_output_len, + the
                                              # reserved trash page)
    max_prompt_len: int = 64                  # prompt-length ceiling; the
                                              # prefill bucket ladder pads
                                              # up to it
    max_output_len: int = 32                  # generation ceiling per
                                              # request (requests retire at
                                              # max_output_len tokens)
    batching: str = "continuous"              # continuous: admit/retire
                                              # per decode step (Orca-style)
                                              # | static: collect a full
                                              # batch, run it to completion,
                                              # only then admit again (the
                                              # A/B control arm)
    decode_attention: str = "gather"          # decode attention program
                                              # (round 18): gather = dense
                                              # page gather + softmax (the
                                              # parity reference) | paged =
                                              # Pallas flash-decode kernel
                                              # reading K/V directly
                                              # through the page tables
                                              # (ops.paged_attention)
    quant: str = "off"                        # serving quantization arm:
                                              # off | int8_w (per-channel
                                              # int8 weights, dequantized
                                              # AT the matmul) | int8_kv
                                              # (int8 KV pool + per-page
                                              # scales consumed inside the
                                              # paged kernel; requires
                                              # --decode_attention=paged)
    decode_block_pages: int = 0               # paged kernel block size:
                                              # KV pages streamed per grid
                                              # step (0 = auto: 1 page, the
                                              # page IS the block; tuned
                                              # like any other lever)
    slo_e2e_ms: float = 0.0                   # per-request e2e SLO target
                                              # (round 20): windowed
                                              # violation/burn-rate
                                              # tracking in the serve
                                              # summary distinguishes
                                              # sustained overload from a
                                              # transient burst (0 = off)
    deadline_ms: float = 0.0                  # per-request service
                                              # deadline (round 23): the
                                              # shed policies measure
                                              # "already dead" against it
                                              # (0 = fall back to
                                              # slo_e2e_ms)
    shed: str = "off"                         # load shedding: off |
                                              # admit (reject requests
                                              # whose deadline already
                                              # expired at admission) |
                                              # deadline (admit + predict
                                              # queue wait blowing the
                                              # deadline, and retire
                                              # already-expired residents
                                              # instead of decoding dead
                                              # tokens)
    kv_preempt: str = "off"                   # KV-pressure preemption:
                                              # when the pool cannot
                                              # serve an admit, preempt
                                              # the resident with most
                                              # pages per token of
                                              # progress, free its pages,
                                              # requeue it carrying its
                                              # generated prefix (off |
                                              # on)
    serve_faults: str | None = None           # deterministic serve-lane
                                              # fault injection:
                                              # hang@STEP:S,
                                              # nan_logits@RID,
                                              # sigterm@T,
                                              # pool_squeeze@T:PAGES
    serve_journal: str | None = None          # drain journal path
                                              # (default:
                                              # <metrics_dir>/
                                              # serve_journal.json)
    serve_resume: str | None = None           # replay every unfinished
                                              # request from a drain
                                              # journal exactly once
    serve_step_timeout_s: str | None = None   # scheduler-iteration
                                              # watchdog: no iteration
                                              # within this -> timeline/
                                              # memory dumps + exit 70
    kv_reserve: str = "worst"                 # KV reservation policy
                                              # (round 25): worst =
                                              # reserve every request's
                                              # worst-case page count at
                                              # admission (the r22-
                                              # measured 45%-waste
                                              # control) | lazy =
                                              # reserve ceil(prompt/
                                              # page) + kv_growth_
                                              # headroom and grow one
                                              # page on each crossed
                                              # boundary; a failed
                                              # growth falls back to
                                              # prefix-cache eviction,
                                              # then --kv_preempt
    prefix_cache: str = "off"                 # shared-prefix KV cache
                                              # (round 25): on = a
                                              # prefix trie keyed on
                                              # page-aligned prompt
                                              # chunks maps common
                                              # prefixes to shared,
                                              # refcounted physical
                                              # pages; cache-hit admits
                                              # skip the page WRITES
                                              # for shared slots and
                                              # the first append into a
                                              # shared page copies it
                                              # (COW).  Requires
                                              # --kv_reserve=lazy
    kv_growth_headroom: int = 1               # decode pages reserved
                                              # beyond the prompt at
                                              # lazy admission — the
                                              # slack that keeps the
                                              # first decode steps from
                                              # immediately growing

    # Populated by resolve():
    translations: dict[str, str] = dataclasses.field(default_factory=dict)
    # config provenance (resolve()): manual = hand-set flags, auto = a
    # tuned registry row was applied, baseline = --config=auto found no
    # row and fell back to the BASELINE defaults.  BENCH json and the
    # run manifest carry both fields so the perf trajectory can
    # distinguish tuned from hand-set runs.
    config_source: str = "manual"
    tuned_config: dict | None = None
    # Populated by parse_flags(): the flag names the operator actually
    # typed (the launcher's positional batch included).  --config=auto
    # consults this so an EXPLICIT --batch_size=64 pins the default
    # value against the tuned row; programmatic configs leave it None
    # and resolve_auto falls back to "non-default means set".
    explicit_flags: tuple | None = None

    @property
    def compute_dtype(self) -> str:
        """bfloat16 when fp16 requested (TPU has no fp16 MXU path), else f32."""
        return "bfloat16" if self.use_fp16 else "float32"

    def _explicitly_set(self, names: Sequence[str]) -> list[str]:
        """The subset of ``names`` the operator actually set: named in
        ``explicit_flags`` when the config came through ``parse_flags``
        (so an explicit flag typed at its default value still counts),
        else any field whose value differs from the dataclass default
        (the programmatic-construction fallback — the same two-tier
        rule ``tune.registry.resolve_auto`` uses for pinning)."""
        if self.explicit_flags is not None:
            return [n for n in names if n in self.explicit_flags]
        defaults = {f.name: f.default for f in dataclasses.fields(self)}
        return [n for n in names
                if n in defaults and getattr(self, n) != defaults[n]]

    def _resolve_serving(self, t: dict[str, str]) -> "BenchmarkConfig":
        """The ``workload="serve"`` half of resolve(): the serving lane
        shares the parser (every flag still parses) but owns its own
        validity matrix — a training-only knob silently ignored here
        would wear a banner describing machinery that never ran, so it
        dies at flag time instead."""
        bad = self._explicitly_set(TRAIN_ONLY_FLAGS)
        if bad:
            raise ValueError(
                "training-only flag(s) have no meaning under `python -m "
                "tpu_hc_bench serve`: "
                + ", ".join(f"--{b}" for b in bad)
                + " (the serving lane sizes work by --serve_buckets/"
                  "--max_in_flight/--max_prompt_len and owns its own "
                  "decode step; drop the flag or run the training lane)")
        # compute-engine translations shared with the training lane
        if self.mkl:
            t["mkl"] = "TRUE->no-op (XLA:TPU is the compute engine)"
            self.mkl = False
        if self.device == "cpu":
            t["device"] = "cpu->tpu (per-launcher target platform)"
            self.device = "tpu"
        if self.arrival not in ("poisson", "bursty", "diurnal"):
            raise ValueError(
                f"--arrival must be poisson|bursty|diurnal: {self.arrival!r}")
        if self.arrival_rate <= 0:
            raise ValueError(
                f"--arrival_rate must be > 0 req/s: {self.arrival_rate}")
        if self.num_requests < 1:
            raise ValueError(
                f"--num_requests must be >= 1: {self.num_requests}")
        if self.kv_page_size < 1:
            raise ValueError(
                f"--kv_page_size must be >= 1 token: {self.kv_page_size}")
        if self.kv_pages < 0:
            raise ValueError(
                f"--kv_pages must be >= 0 (0 = auto): {self.kv_pages}")
        if self.max_prompt_len < 1:
            raise ValueError(
                f"--max_prompt_len must be >= 1: {self.max_prompt_len}")
        if self.max_output_len < 1:
            raise ValueError(
                f"--max_output_len must be >= 1: {self.max_output_len}")
        if self.batching not in ("continuous", "static"):
            raise ValueError(
                f"--batching must be continuous|static: {self.batching!r}")
        if self.decode_attention not in ("gather", "paged"):
            raise ValueError(
                f"--decode_attention must be gather|paged: "
                f"{self.decode_attention!r}")
        if self.quant not in ("off", "int8_w", "int8_kv"):
            raise ValueError(
                f"--quant must be off|int8_w|int8_kv: {self.quant!r}")
        if self.quant == "int8_kv" and self.decode_attention != "paged":
            raise ValueError(
                "--quant=int8_kv stores per-page scales that are "
                "consumed INSIDE the paged decode kernel; set "
                "--decode_attention=paged (the gather reference has no "
                "scale-fused read path)")
        if self.decode_block_pages < 0:
            raise ValueError(
                f"--decode_block_pages must be >= 0 (0 = auto): "
                f"{self.decode_block_pages}")
        if self.decode_block_pages and self.decode_attention != "paged":
            raise ValueError(
                "--decode_block_pages sizes the paged kernel's page "
                "blocks; it has no meaning under "
                "--decode_attention=gather")
        if self.slo_e2e_ms < 0:
            raise ValueError(
                f"--slo_e2e_ms must be >= 0 ms (0 = no SLO tracking): "
                f"{self.slo_e2e_ms}")
        # round 23: the degradation/survival knobs
        if self.deadline_ms < 0:
            raise ValueError(
                f"--deadline_ms must be >= 0 ms (0 = use --slo_e2e_ms): "
                f"{self.deadline_ms}")
        if self.shed not in ("off", "admit", "deadline"):
            raise ValueError(
                f"--shed must be off|admit|deadline: {self.shed!r}")
        if self.shed != "off" and not (self.deadline_ms
                                       or self.slo_e2e_ms):
            raise ValueError(
                "--shed needs a deadline to shed against: set "
                "--deadline_ms (or --slo_e2e_ms, its fallback)")
        if self.kv_preempt not in ("off", "on"):
            raise ValueError(
                f"--kv_preempt must be off|on: {self.kv_preempt!r}")
        # round 25: lazy reservation + shared-prefix cache
        if self.kv_reserve not in ("worst", "lazy"):
            raise ValueError(
                f"--kv_reserve must be worst|lazy: {self.kv_reserve!r}")
        if self.prefix_cache not in ("off", "on"):
            raise ValueError(
                f"--prefix_cache must be off|on: {self.prefix_cache!r}")
        if self.prefix_cache == "on" and self.kv_reserve != "lazy":
            raise ValueError(
                "--prefix_cache=on shares pages a worst-case "
                "reservation would immediately duplicate; set "
                "--kv_reserve=lazy (sharing only saves pages when "
                "admission stops reserving the worst case)")
        if self.kv_growth_headroom < 0:
            raise ValueError(
                f"--kv_growth_headroom must be >= 0 pages: "
                f"{self.kv_growth_headroom}")
        if self.serve_faults:
            from tpu_hc_bench.serve.faults import parse_serve_plan

            parse_serve_plan(self.serve_faults)     # loud format check
        if self.serve_step_timeout_s is not None:
            from tpu_hc_bench.resilience.watchdog import resolve_timeout

            resolve_timeout(self.serve_step_timeout_s)  # loud check
        # loud format checks (raise on malformed spec; values re-read by
        # the engine)
        parse_serve_buckets(self.serve_buckets, self.max_in_flight)
        if self.hbm_budget is not None:
            from tpu_hc_bench.obs.memory import parse_hbm_budget

            parse_hbm_budget(self.hbm_budget)
        self.translations = t
        return self

    def resolve(self) -> "BenchmarkConfig":
        """Apply TPU translations of reference-literal flag values.

        Mirrors the judgment call in SURVEY.md §7 hard-parts (a): honor flag
        *semantics*, not literal values that would be wrong on TPU.
        """
        t: dict[str, str] = {}
        if self.config not in ("manual", "auto"):
            raise ValueError(
                f"--config must be manual|auto: {self.config!r}")
        if self.config == "auto":
            # the one deliberate exception to resolve()'s filesystem-
            # purity principle (--fabric_ceiling/--compile_cache defer
            # their reads to the driver): --config=auto IS a registry
            # read, and it must happen before the validations below so
            # an applied tuned row is checked like any hand-set flag.
            # Registry dir / hardware key honor TPU_HC_TUNE_REGISTRY /
            # TPU_HC_TUNE_HW env overrides (tune.registry).
            from tpu_hc_bench.tune import registry as tune_registry

            t["config"] = tune_registry.resolve_auto(self)
        if self.workload not in ("train", "serve"):
            raise ValueError(
                f"workload must be train|serve: {self.workload!r}")
        if self.flight_recorder not in ("on", "off"):
            # shared by both lanes, so validated before the serve branch
            raise ValueError(
                f"--flight_recorder must be on|off: "
                f"{self.flight_recorder!r}")
        if self.workload == "serve":
            # the serving lane (round 16): its own validity matrix, and
            # none of the training-lane translations/duration defaults
            # below apply
            return self._resolve_serving(t)
        extras = self._explicitly_set(SERVE_ONLY_FLAGS)
        if extras:
            raise ValueError(
                "serving-lane flag(s) have no meaning in the training "
                "lane: " + ", ".join(f"--{e}" for e in extras)
                + " — run `python -m tpu_hc_bench serve` for the "
                  "request-driven benchmark")
        if self.data_format.upper() == "NCHW":
            t["data_format"] = "NCHW->NHWC (MXU wants channels-minor)"
            self.data_format = "NHWC"
        if self.mkl:
            t["mkl"] = "TRUE->no-op (XLA:TPU is the compute engine)"
            self.mkl = False
        if self.device == "cpu":
            t["device"] = "cpu->tpu (per-launcher target platform)"
            self.device = "tpu"
        if self.variable_update == "horovod":
            t["variable_update"] = "horovod->psum (XLA allreduce over mesh)"
            self.variable_update = "psum"
        if self.variable_update == "zero1":
            # ZeRO-1 shards the optimizer state over the data axis; every
            # unsupported composition dies at flag time, not 50 warmup
            # steps in
            if self.model_parallel > 1 or self.expert_parallel > 1:
                raise ValueError(
                    "--variable_update=zero1 composes with plain data "
                    "parallelism only (TP/EP run on the GSPMD arm)")
            if self.pipeline_parallel > 1:
                raise ValueError(
                    "--variable_update=zero1 is not supported with "
                    "--pipeline_parallel (the GPipe arm owns its own "
                    "gradient path; no sharded-optimizer layout)")
            if (self.sequence_parallel > 1
                    or self.attention_impl in SEQ_SHARDED_IMPLS):
                raise ValueError(
                    "--variable_update=zero1 composes with plain data "
                    "parallelism only: the SP step reduces over "
                    "(data, seq) and the zero1 reduce-scatter layout is "
                    "data-axis only")
            if self.forward_only:
                raise ValueError(
                    "--variable_update=zero1 shards the OPTIMIZER state; "
                    "forward-only runs have none (use psum)")
        if self.horovod_device in ("cpu", "gpu"):
            t["horovod_device"] = f"{self.horovod_device}->tpu"
            self.horovod_device = "tpu"
        if self.local_parameter_device in ("cpu", "gpu"):
            t["local_parameter_device"] = f"{self.local_parameter_device}->tpu"
            self.local_parameter_device = "tpu"
        if self.num_intra_threads or self.kmp_blocktime != 1:
            t["thread_tuning"] = (
                "num_intra/inter_threads,kmp_* parsed but no-op on TPU"
            )
        if self.num_epochs and self.num_batches is not None:
            # tf_cnn_benchmarks semantics: the two duration flags conflict
            raise ValueError(
                "--num_batches and --num_epochs cannot both be set"
            )
        if self.num_epochs < 0:
            raise ValueError(f"--num_epochs must be >= 0: {self.num_epochs}")
        if self.num_batches is None and not self.num_epochs:
            self.num_batches = DEFAULT_NUM_BATCHES
        if self.profile_steps is not None:
            if not self.trace_dir:
                raise ValueError(
                    "--profile_steps selects WHICH timed steps to profile; "
                    "--trace_dir says where the trace goes — set both")
            if self.eval:
                # same loud-error principle as the other eval exclusions:
                # the window is defined over the timed TRAINING steps, and
                # accepting the flag under --eval would silently write no
                # trace
                raise ValueError(
                    "--profile_steps applies to the timed training loop; "
                    "it has no meaning under --eval")
            parse_profile_steps(self.profile_steps)  # loud format check
        # --fabric_ceiling is validated at RUN start (driver loads the
        # sweep before the banner): resolve() stays filesystem-pure so
        # configs parse on machines that don't hold the artifacts
        if self.model_parallel > 1 and self.expert_parallel > 1:
            raise ValueError(
                "--model_parallel and --expert_parallel are exclusive: both "
                "shard over the mesh 'model' axis"
            )
        if self.gradient_accumulation_steps < 1:
            raise ValueError(
                f"--gradient_accumulation_steps must be >= 1: "
                f"{self.gradient_accumulation_steps}")
        if self.gradient_accumulation_steps > 1:
            # accumulation lives in the explicit-psum DP/SP step (a
            # lax.scan over microbatches before the single fused
            # allreduce); the other arms reject loudly rather than run
            # with the flag silently ignored
            if self.pipeline_parallel > 1:
                raise ValueError(
                    "--gradient_accumulation_steps: pipeline parallelism "
                    "already microbatches (--num_microbatches)")
            if self.model_parallel > 1 or self.expert_parallel > 1:
                raise ValueError(
                    "--gradient_accumulation_steps is not supported on the "
                    "GSPMD TP/EP arm (supported: DP and DP x SP)")
            if (self.variable_update == "replicated"
                    and self.sequence_parallel <= 1
                    and self.attention_impl not in SEQ_SHARDED_IMPLS):
                # under SP — including the degenerate seq-1 axis the
                # seq-sharded attention impls select — replicated is
                # translated to psum further down (the SP blocks below),
                # and that combo is supported; only the true GSPMD arm
                # rejects
                raise ValueError(
                    "--gradient_accumulation_steps needs "
                    "--variable_update=psum or zero1 (the explicit "
                    "shard_map step)")
            if self.forward_only or self.eval:
                raise ValueError(
                    "--gradient_accumulation_steps is a training-step "
                    "knob; it has no meaning forward-only / under --eval")
        if self.accum_dtype not in ("f32", "bf16"):
            raise ValueError(
                f"--accum_dtype must be f32 or bf16: {self.accum_dtype!r}")
        if self.accum_dtype != "f32" and self.gradient_accumulation_steps == 1:
            raise ValueError(
                "--accum_dtype selects the microbatch grad-accumulator "
                "dtype; it has no meaning without "
                "--gradient_accumulation_steps > 1")
        # round 2: minor axes compose — supported hybrids are DPxPPxTP and
        # DPxSPxTP (model auto/GSPMD under a manual PP/SP shard_map); the
        # remaining pairings are rejected here and in run_benchmark
        if self.pipeline_parallel > 1 and self.sequence_parallel > 1:
            raise ValueError(
                "--pipeline_parallel x --sequence_parallel is not a "
                "supported composition (supported: DPxPPxTP, DPxSPxTP)"
            )
        if self.expert_parallel > 1 and (self.pipeline_parallel > 1
                                         or self.sequence_parallel > 1):
            raise ValueError(
                "--expert_parallel composes with data parallelism only"
            )
        if self.sequence_parallel > 1:
            if self.variable_update == "replicated":
                note = (
                    f"replicated->psum (sequence_parallel="
                    f"{self.sequence_parallel} runs the explicit shard_map "
                    f"step; gradients fuse-psum over both mesh axes)"
                )
                prior = t.get("variable_update")
                t["variable_update"] = f"{prior}; {note}" if prior else note
                self.variable_update = "psum"
            # SP needs a sequence-sharded attention impl; translate the
            # single-device names to their SP counterparts
            sp_map = {"dense": "ring", "flash": "ulysses_flash"}
            if self.attention_impl in sp_map:
                new = sp_map[self.attention_impl]
                t["attention_impl"] = (
                    f"{self.attention_impl}->{new} (sequence_parallel="
                    f"{self.sequence_parallel} shards the sequence axis)"
                )
                self.attention_impl = new
        elif self.attention_impl in SEQ_SHARDED_IMPLS:
            # DEGENERATE SP (round 3): the seq-sharded impls run on a
            # size-1 seq axis — world-1 collectives are no-ops, so this
            # measures the SP machinery's overhead on a single chip (the
            # performance-evidence run VERDICT #9 asks for).  The psum
            # step still reduces over (data, seq).  Plain DP only: the
            # PP/EP/TP compositions are keyed on sequence_parallel > 1
            # throughout, so a degenerate seq axis under them would
            # silently skip or misconfigure those paths.
            if (self.pipeline_parallel > 1 or self.expert_parallel > 1
                    or self.model_parallel > 1):
                raise ValueError(
                    f"--attention_impl={self.attention_impl} with "
                    "--sequence_parallel=1 (degenerate SP) composes with "
                    "plain data parallelism only; set "
                    "--sequence_parallel>1 for the SP hybrids")
            note = (f"sequence_parallel=1: degenerate seq axis (size 1) — "
                    f"{self.attention_impl} collectives are world-1 no-ops")
            t["sequence_parallel"] = note
            if self.variable_update == "replicated":
                note2 = ("replicated->psum (degenerate seq axis runs the "
                         "explicit (data, seq) shard_map step)")
                prior = t.get("variable_update")
                t["variable_update"] = (f"{prior}; {note2}" if prior
                                        else note2)
                self.variable_update = "psum"
        # --- resilience flag surface (round 8): every invalid combination
        # dies at flag time, not 50 warmup steps in ---
        if self.on_nonfinite not in ("abort", "skip", "rewind"):
            raise ValueError(
                f"--on_nonfinite must be abort|skip|rewind: "
                f"{self.on_nonfinite!r}")
        if self.on_nonfinite in ("skip", "rewind"):
            if self.forward_only or self.eval:
                raise ValueError(
                    "--on_nonfinite=skip/rewind guards the optimizer "
                    "update; forward-only/--eval runs have none (abort "
                    "still applies)")
            if self.pipeline_parallel > 1:
                raise ValueError(
                    "--on_nonfinite=skip/rewind is not supported on the "
                    "GPipe arm yet (the PP step owns its own update "
                    "loop); supported: DP / TP / EP / SP / multislice")
        if self.on_nonfinite == "rewind" and not self.train_dir:
            raise ValueError(
                "--on_nonfinite=rewind restores the last checkpoint — "
                "set --train_dir")
        if self.on_nonfinite == "rewind" and self.resume == "never":
            raise ValueError(
                "--on_nonfinite=rewind restores from --train_dir; "
                "--resume=never contradicts that (a rewind could "
                "resurrect the very checkpoints you asked to ignore)")
        if self.max_bad_steps < 1:
            raise ValueError(
                f"--max_bad_steps must be >= 1: {self.max_bad_steps}")
        if self.resume not in ("auto", "never", "must", "elastic"):
            raise ValueError(
                f"--resume must be auto|never|must|elastic: {self.resume!r}")
        if self.resume in ("must", "elastic") and not self.train_dir:
            raise ValueError(f"--resume={self.resume} needs --train_dir")
        if self.keep_checkpoints < 0:
            raise ValueError(
                f"--keep_checkpoints must be >= 0: {self.keep_checkpoints}")
        if self.prefetch_depth < 1:
            raise ValueError(
                f"--prefetch_depth must be >= 1 (1 = no lookahead): "
                f"{self.prefetch_depth}")
        # --- input service (round 13): config-resolvable exclusions
        # translate loudly here; world-shape ones (multi-host grouping)
        # are only known to the driver ---
        if self.input_service not in ("on", "off", "auto"):
            raise ValueError(
                f"--input_service must be on|off|auto: "
                f"{self.input_service!r}")
        if self.service_decode_workers < 0:
            raise ValueError(
                f"--service_decode_workers must be >= 0 (0 = auto): "
                f"{self.service_decode_workers}")
        if self.input_service == "on":
            is_text = False
            if self.data_dir is not None:
                from tpu_hc_bench.models import get_model_spec

                try:
                    is_text = get_model_spec(self.model).is_text
                except ValueError:
                    pass    # unknown model: let create_model raise later
            if self.data_dir is None:
                t["input_service"] = ("on->off (synthetic input has no "
                                      "host decode pipeline to share)")
                self.input_service = "off"
            elif is_text:
                # loud, not silent: the driver's service arm covers the
                # image TFRecord path; text members read a memmapped
                # corpus per-process (page-cache-shared, no decode) —
                # the packed-token service exists at the API level only
                # (data.service.make_packed_token_service)
                t["input_service"] = (
                    "on->off (text members read a memmapped corpus "
                    "per-process; the packed-token service is not "
                    "driver-wired yet — see "
                    "data.service.make_packed_token_service)")
                self.input_service = "off"
            elif self.datasets_repeat_cached_sample:
                t["input_service"] = (
                    "on->off (--datasets_repeat_cached_sample decodes a "
                    "handful of batches once and shuts the pipeline down "
                    "— nothing to serve)")
                self.input_service = "off"
            elif self.eval:
                t["input_service"] = (
                    "on->off (--eval reads the validation split "
                    "per-process; the service targets the sustained "
                    "training input plane)")
                self.input_service = "off"
        # --compile_cache stays filesystem-pure here (same principle as
        # --fabric_ceiling): only the value is checked; the lanes resolve
        # and create the directory at run start
        from tpu_hc_bench.utils.compile_cache import check_flag

        check_flag(self.compile_cache)
        if self.hbm_budget is not None:
            from tpu_hc_bench.obs.memory import parse_hbm_budget

            parse_hbm_budget(self.hbm_budget)   # loud format check;
            # "auto" resolves against the live device at run start
        if self.step_timeout_s is not None:
            from tpu_hc_bench.resilience.watchdog import resolve_timeout

            resolve_timeout(self.step_timeout_s)    # loud format check
        if self.inject_fault:
            from tpu_hc_bench.resilience.inject import parse_plan

            parse_plan(self.inject_fault)           # loud format check
        if self.moe_impl == "auto":
            from tpu_hc_bench.models import get_model_spec

            try:
                is_moe = get_model_spec(self.model).moe
            except ValueError:
                is_moe = False      # unknown model: let create_model raise
            if not is_moe:
                raise ValueError(
                    f"--moe_impl=auto only applies to MoE members, not "
                    f"{self.model}")
            # round 3: pick the dispatch by MEASUREMENT — einsum wins at
            # short/medium seq (49.2 vs 31.2 ex/s on gpt2_moe seq 1024,
            # BASELINE.md) and is the GSPMD path EP/TP require; ragged
            # grouped matmuls take over at long seq (the O(S) dispatch:
            # einsum needs the token-dropping capacity valve at seq 4096
            # and fails to compile beyond)
            long_seq = (self.seq_len or 0) >= 4096
            new = ("ragged" if (long_seq and self.expert_parallel == 1
                                and self.model_parallel == 1
                                and self.moe_capacity_factor == 1.25)
                   else "einsum")
            t["moe_impl"] = (f"auto->{new} (einsum short-seq/EP/TP, "
                             f"ragged at seq>=4096 single-shard)")
            self.moe_impl = new
        if self.moe_impl == "ragged" and self.moe_capacity_factor != 1.25:
            raise ValueError(
                "--moe_capacity_factor applies to the einsum dispatch only: "
                "the ragged grouped-matmul path has no capacity concept "
                "(zero token drops), so the flag would be silently ignored"
            )
        if self.moe_impl == "ragged" and (
                self.expert_parallel > 1 or self.model_parallel > 1):
            # TP also shards the expert tensors over the model axis
            # (tp_param_spec's moe/ rules), so both spellings are blocked
            raise ValueError(
                "--expert_parallel/--model_parallel require "
                "--moe_impl=einsum (ragged_dot grouped matmuls are "
                "single-shard; the GShard einsum dispatch is the "
                "GSPMD-shardable path)"
            )
        if self.pipeline_parallel > 1:
            note = (
                f"{self.variable_update}->n/a (pipeline_parallel="
                f"{self.pipeline_parallel} runs the dedicated GPipe "
                f"shard_map step with its own gradient psums)"
            )
            # append rather than overwrite: an earlier horovod->psum
            # record must stay in the audit trail
            prior = t.get("variable_update")
            t["variable_update"] = f"{prior}; {note}" if prior else note
        sharded = max(self.model_parallel, self.expert_parallel)
        # ...but NOT under the SP (or PP) hybrids: there the manual
        # shard_map step keeps running and the model axis rides auto/GSPMD
        # inside it, so variable_update stays on the psum path
        if (sharded > 1 and self.variable_update != "replicated"
                and self.sequence_parallel == 1
                and self.pipeline_parallel == 1):
            which = ("model_parallel" if self.model_parallel > 1
                     else "expert_parallel")
            t["variable_update"] = (
                f"{self.variable_update}->replicated ({which}={sharded} "
                f"runs on the GSPMD arm; the explicit fused-psum path and "
                f"fusion_threshold do not apply)"
            )
            self.variable_update = "replicated"
        if self.overlap_grad_comm not in ("on", "off"):
            raise ValueError(
                f"--overlap_grad_comm must be on|off: "
                f"{self.overlap_grad_comm!r}")
        if (self.overlap_grad_comm == "off"
                and self.variable_update == "replicated"
                and self.pipeline_parallel == 1):
            # the GSPMD arm's collectives are scheduled by XLA; the flag
            # only shapes the explicit psum/zero1 programs — record the
            # no-op instead of silently accepting it
            t["overlap_grad_comm"] = (
                "off->n/a (GSPMD schedules its own collectives; the flag "
                "applies to the psum/zero1 arms)")
        self.translations = t
        return self

    def summary_lines(self) -> list[str]:
        """Config header in the spirit of run-tf-sing-ucx-openmpi.sh:52-58."""
        if self.workload == "serve":
            buckets = ",".join(
                str(b) for b in parse_serve_buckets(self.serve_buckets,
                                                    self.max_in_flight))
            lines = [
                f"model={self.model} workload=serve "
                f"batching={self.batching} dtype={self.compute_dtype}",
                f"arrival={self.arrival} rate={self.arrival_rate}/s "
                f"requests={self.num_requests} "
                f"prompt<={self.max_prompt_len} output<={self.max_output_len}",
                f"buckets={buckets} max_in_flight={self.max_in_flight} "
                f"kv_page_size={self.kv_page_size} "
                f"kv_pages={self.kv_pages or 'auto'}",
                f"decode_attention={self.decode_attention} "
                f"quant={self.quant}"
                + (f" decode_block_pages={self.decode_block_pages}"
                   if self.decode_block_pages else ""),
            ]
            if self.kv_reserve != "worst" or self.prefix_cache != "off":
                lines.append(
                    f"kv_reserve={self.kv_reserve} "
                    f"prefix_cache={self.prefix_cache} "
                    f"growth_headroom={self.kv_growth_headroom}")
            if (self.shed != "off" or self.kv_preempt != "off"
                    or self.serve_faults or self.serve_resume
                    or self.serve_step_timeout_s):
                lines.append(
                    f"shed={self.shed} kv_preempt={self.kv_preempt}"
                    + (f" deadline_ms={self.deadline_ms:g}"
                       if self.deadline_ms else "")
                    + (f" faults={self.serve_faults}"
                       if self.serve_faults else "")
                    + (f" resume={self.serve_resume}"
                       if self.serve_resume else "")
                    + (f" watchdog={self.serve_step_timeout_s}s"
                       if self.serve_step_timeout_s else ""))
            for k, v in self.translations.items():
                lines.append(f"translated: {k}: {v}")
            return lines
        lines = [
            f"model={self.model} batch_size/worker={self.batch_size} "
            f"optimizer={self.optimizer} dtype={self.compute_dtype}",
            f"warmup={self.num_warmup_batches} timed={self.num_batches} "
            f"display_every={self.display_every} forward_only={self.forward_only}",
            f"data={'synthetic' if self.data_dir is None else self.data_dir}"
            + (" [repeat_cached_sample]"
               if self.datasets_repeat_cached_sample else "")
            + f" ({self.data_name}, {self.data_format})"
            + f" prefetch_depth={self.prefetch_depth}"
            + (f" input_service={self.input_service}"
               if self.data_dir is not None else ""),
            f"variable_update={self.variable_update} "
            f"fusion_threshold={self.fusion_threshold_bytes}B"
            + (f" overlap_grad_comm={self.overlap_grad_comm}"
               if self.variable_update in ("psum", "zero1") else "")
            + (f" model_parallel={self.model_parallel}"
               if self.model_parallel > 1 else "")
            + (f" expert_parallel={self.expert_parallel}"
               if self.expert_parallel > 1 else "")
            + (f" pipeline_parallel={self.pipeline_parallel}"
               f" num_microbatches={self.num_microbatches or 'auto'}"
               if self.pipeline_parallel > 1 else "")
            + (f" sequence_parallel={self.sequence_parallel}"
               if self.sequence_parallel > 1 else "")
            + (f" gradient_accumulation_steps="
               f"{self.gradient_accumulation_steps}"
               if self.gradient_accumulation_steps > 1 else "")
            + (f" accum_dtype={self.accum_dtype}"
               if self.accum_dtype != "f32" else ""),
        ]
        for k, v in self.translations.items():
            lines.append(f"translated: {k}: {v}")
        return lines


def build_parser() -> argparse.ArgumentParser:
    """Argument parser covering the full reference flag surface (§2d)."""
    p = argparse.ArgumentParser(
        prog="tpu_hc_bench",
        description="TPU-native tf_cnn_benchmarks-compatible benchmark driver",
    )
    d = BenchmarkConfig()
    p.add_argument("--batch_size", type=int, default=d.batch_size)
    p.add_argument("--num_warmup_batches", type=int, default=d.num_warmup_batches)
    p.add_argument("--num_batches", type=int, default=None)
    p.add_argument("--num_epochs", type=float, default=d.num_epochs)
    p.add_argument("--model", type=str, default=d.model)
    p.add_argument("--display_every", type=int, default=d.display_every)
    p.add_argument("--optimizer", type=str, default=d.optimizer,
                   choices=["momentum", "sgd", "adam", "adamw", "rmsprop"])
    p.add_argument("--forward_only", type=_parse_bool, default=d.forward_only)
    p.add_argument("--eval", type=_parse_bool, default=False)
    p.add_argument("--init_learning_rate", type=float, default=d.init_learning_rate)
    p.add_argument("--momentum", type=float, default=d.momentum)
    p.add_argument("--data_dir", type=str, default=None)
    p.add_argument("--data_name", type=str, default=d.data_name)
    p.add_argument("--data_format", type=str, default="NHWC",
                   choices=["NCHW", "NHWC", "nchw", "nhwc"])
    p.add_argument("--device", type=str, default=d.device,
                   choices=["cpu", "tpu"])
    p.add_argument("--mkl", type=_parse_bool, default=False)
    p.add_argument("--use_fp16", type=_parse_bool, default=False)
    p.add_argument("--variable_update", type=str, default="psum",
                   choices=["horovod", "psum", "replicated", "zero1"])
    p.add_argument("--overlap_grad_comm", type=str, default=d.overlap_grad_comm,
                   choices=["on", "off"])
    p.add_argument("--horovod_device", type=str, default=d.horovod_device)
    p.add_argument("--local_parameter_device", type=str,
                   default=d.local_parameter_device)
    p.add_argument("--num_intra_threads", type=int, default=d.num_intra_threads)
    p.add_argument("--num_inter_threads", type=int, default=d.num_inter_threads)
    p.add_argument("--kmp_blocktime", type=int, default=d.kmp_blocktime)
    p.add_argument("--kmp_affinity", type=str, default=d.kmp_affinity)
    p.add_argument("--datasets_num_private_threads", type=int,
                   default=d.datasets_num_private_threads)
    p.add_argument("--datasets_repeat_cached_sample", type=_parse_bool,
                   default=d.datasets_repeat_cached_sample)
    p.add_argument("--train_dir", type=str, default=None)
    p.add_argument("--save_model_steps", type=int, default=d.save_model_steps)
    p.add_argument("--async_checkpoint", type=_parse_bool,
                   default=d.async_checkpoint)
    p.add_argument("--compile_cache", type=str, default=d.compile_cache,
                   metavar="off")
    p.add_argument("--prefetch_depth", type=int, default=d.prefetch_depth)
    p.add_argument("--input_service", type=str, default=d.input_service,
                   choices=["on", "off", "auto"])
    p.add_argument("--service_decode_workers", type=int,
                   default=d.service_decode_workers)
    p.add_argument("--config", type=str, default=d.config,
                   choices=["manual", "auto"])
    p.add_argument("--full_batch_identity", type=_parse_bool,
                   default=d.full_batch_identity)
    p.add_argument("--on_nonfinite", type=str, default=d.on_nonfinite,
                   choices=["abort", "skip", "rewind"])
    p.add_argument("--max_bad_steps", type=int, default=d.max_bad_steps)
    p.add_argument("--resume", type=str, default=d.resume,
                   choices=["auto", "never", "must", "elastic"])
    p.add_argument("--step_timeout_s", type=str, default=d.step_timeout_s)
    p.add_argument("--keep_checkpoints", type=int,
                   default=d.keep_checkpoints)
    p.add_argument("--inject_fault", type=str, default=d.inject_fault,
                   metavar="CLASS@STEP[,...]")
    p.add_argument("--moe_capacity_factor", type=float,
                   default=d.moe_capacity_factor)
    p.add_argument("--fusion_threshold_bytes", type=int,
                   default=d.fusion_threshold_bytes)
    p.add_argument("--seed", type=int, default=d.seed)
    p.add_argument("--num_classes", type=int, default=d.num_classes)
    p.add_argument("--trace_dir", type=str, default=None)
    p.add_argument("--profile_steps", type=str, default=None,
                   metavar="A:B")
    p.add_argument("--metrics_dir", type=str, default=None)
    p.add_argument("--flight_recorder", type=str,
                   default=d.flight_recorder, choices=["on", "off"])
    p.add_argument("--fabric_ceiling", type=str, default=None,
                   metavar="SWEEP_JSON")
    p.add_argument("--hbm_budget", type=str, default=None,
                   metavar="BYTES|auto")
    p.add_argument("--num_slices", type=int, default=d.num_slices)
    p.add_argument("--fused_conv", type=_parse_bool, default=d.fused_conv)
    p.add_argument("--fused_xent", type=_parse_bool, default=False)
    p.add_argument("--use_space_to_depth", type=_parse_bool,
                   default=d.use_space_to_depth)
    p.add_argument("--seq_len", type=int, default=d.seq_len)
    p.add_argument("--wire_dtype", type=str, default=d.wire_dtype,
                   choices=["float32", "uint8"])
    p.add_argument("--gradient_accumulation_steps", type=int,
                   default=d.gradient_accumulation_steps)
    p.add_argument("--accum_dtype", type=str, default=d.accum_dtype,
                   choices=["f32", "bf16"])
    p.add_argument("--model_parallel", type=int, default=d.model_parallel)
    p.add_argument("--expert_parallel", type=int, default=d.expert_parallel)
    p.add_argument("--pipeline_parallel", type=int,
                   default=d.pipeline_parallel)
    p.add_argument("--num_microbatches", type=int, default=d.num_microbatches)
    p.add_argument("--sequence_parallel", type=int,
                   default=d.sequence_parallel)
    p.add_argument("--virtual_devices", type=int, default=d.virtual_devices)
    p.add_argument("--gradient_checkpointing", type=_parse_bool,
                   default=d.gradient_checkpointing)
    p.add_argument("--attention_impl", type=str, default=d.attention_impl,
                   choices=["dense", "flash", "ring", "ulysses",
                            "ulysses_flash"])
    p.add_argument("--moe_impl", type=str, default=d.moe_impl,
                   choices=["auto", "einsum", "ragged"])
    p.add_argument("--rnn_impl", type=str, default=d.rnn_impl,
                   choices=["hoisted", "bidi", "flax"])
    p.add_argument("--scan_layers", type=_parse_bool, default=d.scan_layers)
    p.add_argument("--moe_f_chunk", type=int, default=d.moe_f_chunk)
    # --- serving lane (round 16): parse everywhere, validated by
    # resolve() under workload="serve" only (and rejected loudly when
    # explicitly set on a training run) ---
    p.add_argument("--arrival", type=str, default=d.arrival,
                   choices=["poisson", "bursty", "diurnal"])
    p.add_argument("--arrival_rate", type=float, default=d.arrival_rate)
    p.add_argument("--num_requests", type=int, default=d.num_requests)
    p.add_argument("--serve_buckets", type=str, default=d.serve_buckets,
                   metavar="auto|B1,B2,...")
    p.add_argument("--max_in_flight", type=int, default=d.max_in_flight)
    p.add_argument("--kv_page_size", type=int, default=d.kv_page_size)
    p.add_argument("--kv_pages", type=int, default=d.kv_pages)
    p.add_argument("--max_prompt_len", type=int, default=d.max_prompt_len)
    p.add_argument("--max_output_len", type=int, default=d.max_output_len)
    p.add_argument("--batching", type=str, default=d.batching,
                   choices=["continuous", "static"])
    p.add_argument("--decode_attention", type=str,
                   default=d.decode_attention,
                   choices=["gather", "paged"])
    p.add_argument("--quant", type=str, default=d.quant,
                   choices=["off", "int8_w", "int8_kv"])
    p.add_argument("--decode_block_pages", type=int,
                   default=d.decode_block_pages)
    p.add_argument("--slo_e2e_ms", type=float, default=d.slo_e2e_ms)
    # --- round 23: overload/failure survival knobs ---
    p.add_argument("--deadline_ms", type=float, default=d.deadline_ms)
    p.add_argument("--shed", type=str, default=d.shed,
                   choices=["off", "admit", "deadline"])
    p.add_argument("--kv_preempt", type=str, default=d.kv_preempt,
                   choices=["off", "on"])
    p.add_argument("--serve_faults", type=str, default=None,
                   metavar="hang@N:S,nan_logits@RID,sigterm@T,"
                           "pool_squeeze@T:PAGES")
    p.add_argument("--serve_journal", type=str, default=None,
                   metavar="PATH")
    p.add_argument("--serve_resume", type=str, default=None,
                   metavar="JOURNAL")
    p.add_argument("--serve_step_timeout_s", type=str, default=None,
                   metavar="SECONDS")
    # --- round 25: lazy KV reservation + shared-prefix cache ---
    p.add_argument("--kv_reserve", type=str, default=d.kv_reserve,
                   choices=["worst", "lazy"])
    p.add_argument("--prefix_cache", type=str, default=d.prefix_cache,
                   choices=["off", "on"])
    p.add_argument("--kv_growth_headroom", type=int,
                   default=d.kv_growth_headroom)
    return p


def parse_flags(argv: Sequence[str] | None = None,
                workload: str = "train") -> BenchmarkConfig:
    """Parse a tf_cnn_benchmarks-style argv into a resolved BenchmarkConfig.

    ``workload`` is set by the entry point, not a flag: the serve CLI
    (`python -m tpu_hc_bench serve`) passes ``"serve"`` so resolve()
    runs the serving lane's validity matrix (and the tuned-config
    registry keys its lookup on the ``<model>@serve`` row).
    """
    if argv is None:
        import sys

        argv = sys.argv[1:]
    ns = build_parser().parse_args(argv)
    fields = {f.name for f in dataclasses.fields(BenchmarkConfig)}
    kwargs: dict[str, Any] = {
        k: v for k, v in vars(ns).items() if k in fields
    }
    kwargs["data_format"] = kwargs["data_format"].upper()
    cfg = BenchmarkConfig(**kwargs)
    cfg.workload = workload
    # record what the operator actually typed BEFORE resolve():
    # --config=auto must honor an explicit flag even when its value
    # equals the dataclass default
    cfg.explicit_flags = tuple(sorted(
        {a[2:].split("=", 1)[0] for a in argv if a.startswith("--")}
        & fields))
    return cfg.resolve()
