"""Host self time of ``frontend.lower`` in the traced window, in ms per
``nmc.jit`` call (``frontend.call`` span): engine selection and engine
lowering of the tape, or of each shard of a scheduled wave, to a
Program."""

from bench import program_spans


def read(ctx):
    return program_spans.ms_per(ctx, "frontend.lower", "frontend.call")
