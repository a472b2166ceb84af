"""Host self time of ``dispatch.wait`` in the traced window, in ms per work
item (``dispatch.submit`` span): ``jax.block_until_ready`` on the item's
final state (``NMCFuture.state``)."""

from bench import program_spans


def read(ctx):
    return program_spans.ms_per(ctx, "dispatch.wait", "dispatch.submit")
