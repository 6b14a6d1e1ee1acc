"""The flash kernel's share of its roofline: least seconds the chip could
take for the calls the traced window holds (operations and bytes from
shapes) / the summed device durations of the kernel's events, %."""
from harness import readers


def read(ctx):
    return readers.flash_attention_roofline(ctx)
