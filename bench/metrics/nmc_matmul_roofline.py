"""Roofline share of the W8A8 ``nmc_matmul`` kernel in the traced window:
the least time the chip could take for the GEMMs the traced steps ran,
``max(ops / int8 peak, bytes / HBM bandwidth)``, over the kernel's device
time.  The GEMMs come from the model's shapes: every decode step runs
all layers and the LM head at ``n_slots`` rows, every prefill of ``p``
tokens runs them at ``p`` rows."""

from bench import costs, kernels


def read(ctx):
    d = costs.dims(ctx.cfg)
    f = ctx.facts
    gemms = costs.step_gemms(d, f["n_slots"], f["n_slots"]) * \
        f["traced_decode_steps"]
    for p in f["traced_prefill_lens"]:
        gemms += costs.step_gemms(d, p, p)
    ops, nbytes = costs.gemms_cost(gemms)
    return kernels.roofline(ctx, "nmc_matmul", ops, nbytes, "int8_ops_per_s")
