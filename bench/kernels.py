"""Which device operations in a trace belong to which kernel or program,
and the readings built on that.

The names are those the chip's trace gives (``PERF.md`` lists them):
a Pallas kernel's operation is named after its kernel function, a jitted
program's module after the function that was jitted.
"""

from __future__ import annotations

import re

#: kernel -> (names the HLO instruction may carry, result shape of the
#: unnamed Pallas call).  On the chip the engines' ``pallas_call``s carry
#: no name (``%_unknown_.1 = s32[...] custom-call(...)``): NM-Caesar's
#: result is the flat word image ``s32[T*8192]``, NM-Carus's the VRF
#: ``s32[T,32,8,128]``.  The LM kernels carry their function's name.
KERNEL_OPS = {
    "caesar": (("caesar",), re.compile(r"^s32\[\d+\]$")),
    "carus": (("carus",), re.compile(r"^s32\[\d+,\d+,8,128\]$")),
    "nmc_matmul": (("nmc_matmul",), None),
    "flash_attention": (("flash_attention",), None),
}
_INSTR = re.compile(r"^%(?P<name>[^ ]+) = (?P<shape>[^{ ]+)")


def is_kernel(kernel: str):
    """Predicate on a trace operation's name (its HLO instruction)."""
    names, unnamed = KERNEL_OPS[kernel]

    def pred(op: str) -> bool:
        m = _INSTR.match(op)
        if m is None:
            return False
        name = m.group("name")
        if any(name.startswith(n) for n in names):
            return True
        return unnamed is not None and name.startswith("_unknown_") \
            and "custom-call(" in op and bool(unnamed.match(m.group("shape")))
    return pred


def is_decode_program(name: str) -> bool:
    return "decode_step" in name


def kernel_seconds(ctx, kernel: str) -> float:
    return ctx.trace.op_seconds(is_kernel(kernel))


def ns_per_instr(ctx, engine: str):
    """Device ns of ``engine``'s kernel per real instruction submitted to
    it in the traced window; None where the window ran none."""
    n = ctx.facts.get("real_instrs_traced", {}).get(engine, 0)
    secs = kernel_seconds(ctx, engine)
    if not n or secs <= 0:
        return None
    return secs * 1e9 / n


def roofline(ctx, kernel: str, ops: float, nbytes: float, peak_key: str):
    """Percent of the roofline: the least time the chip needs for ``ops``
    and ``nbytes`` over the kernel's device time; None where the traced
    window ran the kernel for no time."""
    secs = kernel_seconds(ctx, kernel)
    if secs <= 0 or not ops:
        return None
    least = max(ops / ctx.peaks[peak_key],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
