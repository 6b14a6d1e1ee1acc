"""Median step time from the driver's completion markers; read only where
the markers resolved single steps (granularity 1)."""


def read(ctx):
    res = ctx.get("program_result")
    if not res or res["p50_step_granularity"] != 1:
        return None
    return res["p50_step_ms"]
