"""Decode steps' share of the bf16 peak (float32 arm: cannot pass 100)."""
from harness import readers


def read(ctx):
    return readers.decode_mfu(ctx)
