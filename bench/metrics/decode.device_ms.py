"""Device time of the jitted decode program per execution in the traced
window."""

from bench import kernels


def read(ctx):
    secs, n = ctx.trace.module_seconds(kernels.is_decode_program)
    return secs / n * 1e3 if n else None
