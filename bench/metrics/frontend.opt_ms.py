"""Host self time of ``frontend.opt`` in the traced window, in ms per
``nmc.jit`` call (``frontend.call`` span): the O1 IR optimizer
(``opt.optimize``) over the lowered program or each shard."""

from bench import program_spans


def read(ctx):
    return program_spans.ms_per(ctx, "frontend.opt", "frontend.call")
