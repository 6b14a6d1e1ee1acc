"""tpu-hc-bench: a TPU-native distributed-training benchmark harness.

A brand-new framework with the capabilities of the reference repo
``md-k-sarker/azure-hc-intel-tf`` (an Azure HC-series InfiniBand cluster
bring-up + Intel-TF/Horovod CNN benchmark harness), re-designed TPU-first:

- Horovod/MPI allreduce over InfiniBand  ->  XLA collectives over the ICI mesh
  (``jax.lax.psum`` under ``jax.shard_map``/``jit``).
- lscpu socket/core layout math           ->  TPU device-topology mesh layout.
- tf_cnn_benchmarks flag surface + models ->  Flax model zoo driven by a
  compatible flag surface (``tpu_hc_bench.flags``).
- OSU MPI micro-benchmarks                ->  ICI collective latency/bandwidth
  sweeps (``tpu_hc_bench.microbench``).
- Singularity image + setenv registry     ->  TPU-VM setup scripts + generated
  env registry (``tpu_hc_bench.envfile``).

See SURVEY.md at the repo root for the full structural mapping with
file:line citations into the reference.
"""

__version__ = "0.1.0"
