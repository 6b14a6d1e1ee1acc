"""The part of the traced window in which a chip ran a collective and no
computation (``xplane.reduce_trace``'s ``collective_exposed_s``, averaged
over the chips) / the traced window, %.  None on one chip: there is no
collective to expose."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["window_s"] or ctx.get("chips", 1) < 2:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
