"""Host-timed wall per decode step (host turn included)."""
from harness import readers


def read(ctx):
    return readers.decode_step_wall_ms(ctx)
