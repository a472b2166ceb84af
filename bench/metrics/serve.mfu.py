"""Share of the chip's int8 peak that the window's useful model work
would fill: for every prompt admitted, every layer on every prompt token,
causal attention and the LM head on its last position; for every token
generated, every layer, attention over its context and the LM head.
Over the window's host-clock length.  The W8A8 GEMMs are nearly all the
operations, so the int8 peak is the one used."""


def read(ctx):
    f = ctx.facts
    return 100.0 * f["useful_ops"] / f["window_s"] / \
        ctx.peaks["int8_ops_per_s"]
