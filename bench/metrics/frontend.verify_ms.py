"""Host self time of ``frontend.verify`` in the traced window, in ms per
``nmc.jit`` call (``frontend.call`` span): the static verifier
(``verify_lowered`` / ``verify_wave``) and its report."""

from bench import program_spans


def read(ctx):
    return program_spans.ms_per(ctx, "frontend.verify", "frontend.call")
