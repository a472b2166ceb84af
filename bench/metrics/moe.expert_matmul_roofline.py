"""Roofline share of the held experts' W8A8 banks in the traced window:
the least time the chip could take for the expert GEMMs the traced steps
needed, ``max(ops / int8 peak, bytes / HBM bandwidth)``, over the device
time of ``nmc_expert_matmul`` and of the operations that bring its banks
to it.  Ops and bytes (``bench/costs_mla_moe.py``) count every held
expert's weights once per expert layer called and the rows routed to it,
read from the expert layer's counters.

XLA slices each layer's banks out of the stacked weights into the
kernel's operand, in fast memory, as an operation of its own (result
``s8[held, k, n]``): that operation reads the weights from HBM, so its
time is the kernel's too.  A run whose program has no such kernel or
counter reads nothing."""

import re

from bench import costs_mla_moe

_KERNEL = re.compile(r"^%?nmc_expert_matmul\b")


def _bank_slices(d):
    shapes = "|".join(f"{d.held},{k},{n}"
                      for k, n in set(costs_mla_moe.expert_bank_gemms(d)))
    return re.compile(rf"^%?\S+ = s8\[(?:{shapes})\]")


def read(ctx):
    f = ctx.facts
    if not f.get("expert_calls"):
        return None
    d = costs_mla_moe.dims(ctx.cfg)
    banks = _bank_slices(d)
    secs = ctx.trace.op_seconds(
        lambda op: bool(_KERNEL.match(op) or banks.match(op)))
    if secs <= 0:
        return None
    ops, nbytes = costs_mla_moe.expert_matmul(d, f["expert_calls"],
                                              f["expert_rows_routed"])
    least = max(ops / ctx.peaks["int8_ops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
