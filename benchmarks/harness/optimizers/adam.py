"""Adam as the train lane states it (``--optimizer=adam``: optax's
defaults, no weight decay), for both sides of the comparison: the plain
update the reference follows, and how the first gradient is read back out
of the program's optimizer state.  Another optimizer (momentum SGD for a
ResNet arm) adds a file like this one; a train arm names its file under
``"optimizer"``."""

from __future__ import annotations

B1, B2, EPS = 0.9, 0.999, 1e-8


def init(params):
    import jax
    import jax.numpy as jnp

    zeros = lambda: jax.tree.map(jnp.zeros_like, params)     # noqa: E731
    return zeros(), zeros()


def update(params, state, grads, t: float, lr):
    """One plain Adam step (``t`` counts from 1)."""
    import jax
    import jax.numpy as jnp

    m, v = state
    m = jax.tree.map(lambda a, g: B1 * a + (1 - B1) * g, m, grads)
    v = jax.tree.map(lambda a, g: B2 * a + (1 - B2) * g * g, v, grads)
    mh, vh = 1.0 - B1 ** t, 1.0 - B2 ** t
    params = jax.tree.map(
        lambda w, a, b: w - lr * (a / mh) / (jnp.sqrt(b / vh) + EPS),
        params, m, v)
    return params, (m, v)


def first_gradient(opt_state):
    """``(tree, scale)``: after ONE step the program's first moment is
    (1 - b1) x the first gradient as the optimizer got it, so the
    gradient's norms are ``scale`` x the norms of ``tree``."""
    for part in opt_state if isinstance(opt_state, tuple) else (opt_state,):
        if hasattr(part, "mu"):
            return part.mu, 1.0 / (1.0 - B1)
    raise RuntimeError("the optimizer state holds no Adam first moment "
                       "(the train arm states --optimizer=adam)")
