"""Seconds of the program's own construction before its programs are
compiled: the set-up account's ``init`` phase (model and weights; the
train driver's goodput ``init``) and, in serve, its ``pool`` phase (the
KV pages and state slots), read in the run's own process."""

PHASES = ("init", "pool")


def read(ctx):
    if "setup_s" not in ctx:
        return None
    from tpu_hc_bench.obs import timeline

    account = getattr(timeline, "setup_account", None)
    acc = account() if account else None
    if not acc or not acc["phases"]:
        return None
    return sum(acc["phases"].get(p, (0, 0.0))[1] for p in PHASES)
