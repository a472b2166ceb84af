"""Mean host time of ``CompiledKernel.call_async`` in the window: trace,
lower, optimize, verify, schedule and submit of one ``nmc.jit`` call."""

from bench.harness import mean


def read(ctx):
    return mean(ctx.facts.get("frontend_ms", []))
