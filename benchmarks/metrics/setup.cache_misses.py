"""Backend compiles of the program's set-up whose executable was written
to the persistent cache: compiled now, loaded by the next run.  A warm
run reads 0 (the set-up account's ``compiled`` count, read in the run's
own process)."""


def read(ctx):
    if "setup_s" not in ctx:
        return None
    from tpu_hc_bench.obs import timeline

    account = getattr(timeline, "setup_account", None)
    acc = account() if account else None
    if not acc or not acc["phases"]:
        return None
    return acc["compiled"]
