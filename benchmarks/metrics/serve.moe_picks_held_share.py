"""Of the decode steps' routed-expert picks, the share that landed on an
expert this chip holds, % (``moe_picks_held`` / ``moe_picks``): the
share held / published is expected (12.5 for 40 of 320), and a reading
far from it says the traffic's ids or the router are not what the
configuration's file states."""


def read(ctx):
    s = ctx.get("summary") or {}
    if not s.get("moe_picks"):
        return None
    return 100.0 * s["moe_picks_held"] / s["moe_picks"]
