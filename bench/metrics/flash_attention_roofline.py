"""Roofline share of the ``flash_attention`` kernel in the traced window:
causal attention of every prefill the traced steps ran, over all layers,
``max(ops / bf16 peak, bytes / HBM bandwidth)`` over the kernel's device
time."""

from bench import costs, kernels


def read(ctx):
    d = costs.dims(ctx.cfg)
    ops = nbytes = 0
    for p in ctx.facts["traced_prefill_lens"]:
        o, b = costs.flash_attention(p, p, d.heads, d.kv_heads, d.head_dim)
        ops += o * d.layers
        nbytes += b * d.layers
    if not ops:
        return None
    return kernels.roofline(ctx, "flash_attention", ops, nbytes,
                            "bf16_flops_per_s")
