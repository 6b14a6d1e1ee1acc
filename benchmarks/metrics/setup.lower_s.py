"""Seconds the program's set-up spent tracing and lowering: JAX's own
trace and MLIR-lowering events, each program's nested ones counted once,
summed over the programs of the set-up account
(``obs.timeline.setup_account``, read in the run's own process).  Mosaic
kernels lower inside the MLIR lowering, so a kernel lowered again and
again shows here."""


def read(ctx):
    if "setup_s" not in ctx:
        return None
    from tpu_hc_bench.obs import timeline

    account = getattr(timeline, "setup_account", None)
    acc = account() if account else None
    if not acc or not acc["phases"]:
        return None
    return sum(p["trace_s"] + p["lower_s"] for p in acc["programs"].values())
