"""JAX backend-compile events inside the window; must read 0."""


def read(ctx):
    return ctx["compiles_in_window"]
