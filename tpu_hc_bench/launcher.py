"""Positional launcher CLI — the reference's run-script contract.

The reference's user-facing entry is
``./run-tf-sing-ucx-openmpi.sh <NUM_NODES> <WORKERS_PER_SOCKET> <batch_size>
<fabric(ib,sock)>`` (``run-tf-sing-ucx-openmpi.sh:4,27-30``; README.md:62-73).
This module preserves that 4-arg positional signature::

    python -m tpu_hc_bench NUM_HOSTS WORKERS_PER_HOST BATCH_SIZE FABRIC [--tf_flags...]

with ``FABRIC in {ib, sock, ici, dcn, host}`` (reference names accepted) and
any tf_cnn_benchmarks-style ``--flag`` after the positionals overriding the
defaults the reference hardcodes (model, warmup, batches...).  Where mpirun
fanned ranks out over the hostfile (:99-109), here every TPU-VM host runs
this same command and ``jax.distributed`` coordinates (SPMD launch model);
on a single host it just runs.

Exit-code contract (``tpu_hc_bench.resilience``; README "Fault
tolerance" table) — distinct codes so schedulers/wrappers can react
without parsing logs:

- ``0``  clean success (nonzero throughput measured)
- ``1``  run completed but measured zero throughput
- ``70`` watchdog abort — no step completed within ``--step_timeout_s``
  (thread stacks were dumped to stderr; the process self-terminates
  with this code from the watchdog thread)
- ``75`` preempted — SIGTERM/SIGINT honored, emergency checkpoint
  written when ``--train_dir`` is set; relaunch with ``--resume=auto``
  to continue
"""

from __future__ import annotations

import sys
from pathlib import Path

from tpu_hc_bench import envfile, flags, resilience
from tpu_hc_bench.parallel import distributed, fabric as fabric_mod
from tpu_hc_bench.topology import discover_layout
from tpu_hc_bench.train import driver


def parse_positionals(argv: list[str]):
    """Split `[NUM_HOSTS WORKERS BATCH FABRIC] [--flags...]` like the
    reference's `$1 $2 $3 $4` parse (:27-30)."""
    pos = []
    rest = list(argv)
    while rest and not rest[0].startswith("-") and len(pos) < 4:
        pos.append(rest.pop(0))
    if len(pos) not in (0, 4):
        raise SystemExit(
            "usage: python -m tpu_hc_bench [NUM_HOSTS WORKERS_PER_HOST "
            "BATCH_SIZE FABRIC(ib|sock|ici|dcn|host)] [--tf_cnn_flags...]\n"
            "       python -m tpu_hc_bench serve [--serve_flags...]  "
            "(request-driven serving benchmark)\n"
            "       python -m tpu_hc_bench fleet run|status|report ...  "
            "(multi-job fleet orchestrator)"
        )
    return pos, rest


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "fleet":
        # the fleet orchestrator (round 19): many jobs, one device pool
        # — `python -m tpu_hc_bench fleet run|status|report ...`
        # (tpu_hc_bench.fleet); each job is itself a launcher
        # subprocess under the positional contract below
        from tpu_hc_bench.fleet import __main__ as fleet_cli

        return fleet_cli.main(argv[1:])
    if argv and argv[0] == "serve":
        # the serving lane (round 16): `python -m tpu_hc_bench serve
        # [--tf_flags...]` — request-driven benchmark with continuous
        # batching over AOT bucket shapes (tpu_hc_bench.serve).  The
        # subcommand replaces the positional NUM_HOSTS/WORKERS/BATCH/
        # FABRIC contract: serving sizes its own work (--serve_buckets/
        # --max_in_flight) and runs single-process for now.
        from tpu_hc_bench.serve import cli as serve_cli

        return serve_cli.main(argv[1:])
    pos, rest = parse_positionals(argv)
    if pos:
        num_hosts, workers_per_host = int(pos[0]), int(pos[1])
        rest = ["--batch_size", pos[2]] + rest
        fabric_name = pos[3]
    else:
        num_hosts, workers_per_host, fabric_name = None, 0, "ici"
    cfg = flags.parse_flags(rest)

    if cfg.virtual_devices:
        # only takes effect before the first backend query
        # (discover_layout)
        import jax

        jax.config.update("jax_num_cpu_devices", cfg.virtual_devices)

    if num_hosts is not None and num_hosts > 1:
        distributed.initialize()

    layout = discover_layout(
        num_hosts=num_hosts, workers_per_host=workers_per_host
    )
    fab = fabric_mod.resolve_fabric(fabric_name)

    # persist the resolved fabric config to the env registry (setenv role)
    fcfg = fabric_mod.FabricConfig(fab, cfg.fusion_threshold_bytes)
    try:
        envfile.register("launcher", fcfg.env_exports())
    except OSError:
        pass  # read-only home dirs shouldn't kill a benchmark run

    # tee-style log file per the reference's naming convention (:9-12)
    data = "synthetic" if cfg.data_dir is None else "real"
    log_path = Path.home() / "logs" / driver.log_name(
        layout.num_hosts, cfg.batch_size, data, fab.value
    )
    lines: list[str] = []

    def tee(msg: str):
        print(msg, flush=True)
        lines.append(msg)

    # full-command echo, as the reference does at :111
    tee(f"command: python -m tpu_hc_bench {' '.join(argv)}")
    rc = resilience.EXIT_OK
    try:
        result = driver.run_benchmark(
            cfg, layout=layout, fabric_name=fabric_name, print_fn=tee
        )
        if result.total_images_per_sec <= 0:
            rc = resilience.EXIT_ZERO_THROUGHPUT
        if cfg.metrics_dir:
            # the operator's next command, spelled out (goodput/MFU/
            # straggler/ceiling lines all render from the artifacts)
            tee("summarize: python -m tpu_hc_bench.obs summarize "
                + cfg.metrics_dir
                + (f" --fabric_ceiling {cfg.fabric_ceiling}"
                   if cfg.fabric_ceiling else ""))
    except resilience.PreemptedError as e:
        # graceful preemption: the emergency checkpoint is on disk (when
        # --train_dir is set) — exit EXIT_PREEMPTED so the relauncher
        # knows `--resume=auto` will continue, not restart
        tee(str(e))
        rc = resilience.EXIT_PREEMPTED
    finally:
        # the tee log is part of the contract even for preempted runs
        try:
            log_path.parent.mkdir(parents=True, exist_ok=True)
            log_path.write_text("\n".join(lines) + "\n")
            print(f"log: {log_path}")
        except OSError:
            pass
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
