"""Seconds of ``setup_s`` that no set-up span of the program covers:
``setup_s`` less the union of the account's top-level spans
(``covered_s``, on the same monotonic clock).  Imports, the device's
start, the benchmark's weights and traffic, its warm-up run and the
tracer's arm."""


def read(ctx):
    if "setup_s" not in ctx:
        return None
    from tpu_hc_bench.obs import timeline

    account = getattr(timeline, "setup_account", None)
    acc = account() if account else None
    if not acc or not acc["phases"]:
        return None
    return ctx["setup_s"] - acc["covered_s"]
