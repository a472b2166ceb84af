"""Host self time of ``frontend.trace`` in the traced window, in ms per
``nmc.jit`` call (``frontend.call`` span): the kernel function run over
tracers into a tape (``CompiledKernel.trace``)."""

from bench import program_spans


def read(ctx):
    return program_spans.ms_per(ctx, "frontend.trace", "frontend.call")
