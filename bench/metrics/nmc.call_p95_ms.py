"""95th percentile of one NMC call from submit to the result on the host:
an ``nmc.jit`` call, or one ``run_builds`` pass of a program library."""

from bench.harness import percentile


def read(ctx):
    return percentile(ctx.facts.get("call_ms", []), 95)
