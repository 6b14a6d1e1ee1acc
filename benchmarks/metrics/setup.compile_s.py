"""Seconds the program's set-up spent in XLA's backend compile, reading
from the persistent cache included (a warm run's compile is its loads),
summed over the programs of the set-up account
(``obs.timeline.setup_account``, read in the run's own process)."""


def read(ctx):
    if "setup_s" not in ctx:
        return None
    from tpu_hc_bench.obs import timeline

    account = getattr(timeline, "setup_account", None)
    acc = account() if account else None
    if not acc or not acc["phases"]:
        return None
    return sum(p["compile_s"] for p in acc["programs"].values())
