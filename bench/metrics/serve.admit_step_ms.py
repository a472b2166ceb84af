"""Mean host time of the ``ServeEngine.step`` calls in the window that
admitted at least one request (prefill, slot insert and decode)."""

from bench.harness import mean


def read(ctx):
    return mean(ctx.facts.get("admit_step_ms", []))
