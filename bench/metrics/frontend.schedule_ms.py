"""Host self time of ``frontend.schedule`` in the traced window, in ms per
``nmc.jit`` call (``frontend.call`` span): the wave planner
(``schedule.plan_wave``: plan key, cache lookup, search), its shard
lowerings excluded."""

from bench import program_spans


def read(ctx):
    return program_spans.ms_per(ctx, "frontend.schedule", "frontend.call")
