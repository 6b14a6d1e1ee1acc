"""Shared plumbing for the Pallas kernel modules.

Every kernel in ``tpu_hc_bench.ops`` runs as a real Mosaic program on
TPU and in Pallas *interpreter* mode on the CPU — that is how the unit
tests exercise the kernels bit-for-bit on the virtual CPU mesh.  Any
other backend is an error: a kernel that silently interprets on a
device nobody named is a benchmark of the interpreter.  This is the one
shared copy of the backend probe (plus the tiny shape helpers).
"""

from __future__ import annotations

import jax

__all__ = ["interpret", "pad_up"]


def interpret() -> bool:
    """True on the CPU backend (Pallas interpreter mode), False on TPU
    (Mosaic); raises on any other backend."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for 'tpu' and interpret on 'cpu'; "
        f"this process runs the {backend!r} backend")


def pad_up(x: int, m: int) -> int:
    """``x`` rounded up to the next multiple of ``m``."""
    return (x + m - 1) // m * m
