"""Device time of the NM-Caesar Pallas kernel over the real (non-NOP)
instructions submitted to NM-Caesar tiles in the traced window."""

from bench import kernels


def read(ctx):
    return kernels.ns_per_instr(ctx, "caesar")
