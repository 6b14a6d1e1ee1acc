"""Finds a configuration's family file (``benchmarks/families/<family>.py``,
named by the configuration's ``"family"`` key) and hands the benchmark's
weights to the program in the program's own layout.  Values are drawn by
the family's reference (``leaf_values``: one place, from the seed); the
family only renames and reshapes them, inside the same jitted call, so
nothing the program made reaches the reference and the two sides still
hold equal numbers."""

from __future__ import annotations

import importlib


def family_of(cfg: dict):
    return importlib.import_module("families." + cfg["family"])


def optimizer_of(arm: dict):
    """``benchmarks/harness/optimizers/<name>.py`` for the train arm's
    ``"optimizer"``."""
    return importlib.import_module("harness.optimizers." + arm["optimizer"])


def program_weights(cfg: dict, seed: int):
    """The program-layout weights on the device, in one jitted call from
    the seed, in float32 (the type both arms hold them in)."""
    import jax

    fam = family_of(cfg)
    ref = fam.reference
    fn = jax.jit(lambda key: fam.program_tree(ref.leaf_values(cfg, key), cfg))
    return fn(ref.seed_key(seed))
