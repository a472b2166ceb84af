"""Share of the instruction slots dispatched in the window that were
padding: ``pad_waste / (useful_instrs + pad_waste)`` from the bucketed
pool's counters (NOP tails to the instruction bucket and replicated
lanes to the tile bucket)."""


def read(ctx):
    pad = ctx.facts.get("pad_waste")
    use = ctx.facts.get("useful_instrs")
    if pad is None or not use:
        return None
    return 100.0 * pad / (use + pad)
