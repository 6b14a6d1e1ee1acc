"""Operations and bytes of the KERNELS from shapes, and the roofline
arithmetic, kept with the benchmark so that no change to the program can
move them.  A multiply-add is 2 operations.  A model's own counts
(parameters, a step's operations) are its family's:
``benchmarks/families/<family>.py``."""

from __future__ import annotations


def flash_attention_call(batch: int, seq: int, heads: int, head_dim: int,
                         itemsize: int = 2, causal: bool = True) -> dict:
    """The flash kernel's forward and backward for one layer: operations
    and the bytes the algorithm must move (q, k, v, o and their
    gradients once each; the score matrix never leaves the chip).
    Backward = 5 matmuls (scores again, dP, dV, dQ, dK) against the
    forward's 2."""
    mm = 2.0 * batch * heads * seq * seq * head_dim
    if causal:
        mm /= 2
    tensor = batch * seq * heads * head_dim * itemsize
    return {"fwd_flops": 2 * mm, "bwd_flops": 5 * mm,
            "fwd_bytes": 4 * tensor, "bwd_bytes": 8 * tensor}


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds, which bound applies)."""
    tc = flops / peaks["bf16_flops"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "memory")
