"""Driver: a library of lowered NMC programs fed through the tile array.

One client in a closed loop.  Set-up lowers every (kernel, SEW, engine)
of the mix with operands drawn from the seed, as the paper's Table V
sweep does (``opt="off"``: the programs as the paper wrote them).  The
window then feeds the whole library through
``DispatchQueue.run_builds(builds, n_tiles)`` again and again: the
continuously fed tile array, with no frontend work per pass.  Engine
time per program does not depend on the operands, so replaying one
library measures what fresh operands would.

Correctness: once the window has closed, every output of a sample of
passes drawn from the seed is compared with :mod:`bench.ref.nmc`.
"""

from __future__ import annotations

import time

import numpy as np

from bench import harness
from bench.drivers import nmc_library as lib
from bench.ref import nmc as ref

SAMPLE_PASSES = 8


def shape_of(cfg: dict, kernel: str, engine: str, sew: int) -> dict:
    """Sizes of one Table V kernel on one engine at one SEW, from the
    configuration (per-SEW entries are keyed by the SEW as a string)."""
    spec = cfg["table_v"][kernel]
    shape = {k: v for k, v in spec.items() if k not in ("caesar", "carus")}
    for k, v in spec[engine].items():
        shape[k] = v[str(sew)] if isinstance(v, dict) else v
    return shape


def build_library(cfg: dict, traffic: dict, seed: int):
    """``(instances, lowered programs)`` of the mix, operands from ``seed``."""
    from repro import nmc

    rng = np.random.default_rng(seed)
    insts, lowered = [], []
    for kernel in traffic["kernels"]:
        for sew in traffic["sews"]:
            for engine in traffic["engines"]:
                kind = cfg["table_v"][kernel].get("kind", kernel)
                inst = lib.instance(kind, sew, shape_of(cfg, kernel, engine,
                                                        sew), rng)
                lk = nmc.jit(inst.fn, engine=engine, sew=sew,
                             opt="off").lower(*inst.args)
                insts.append(inst)
                lowered.append(lk)
    return insts, lowered


def modeled(lowered: list) -> tuple[float, float]:
    """Modeled tile cycles and energy (pJ) of one pass of the library."""
    from repro.core import energy, timing

    cyc = sum(timing.program_cycles(lk.program, lk.host_cycles).total_cycles
              for lk in lowered)
    pj = sum(energy.program_energy(lk.program, lk.host_cycles).energy_pj
             for lk in lowered)
    return cyc, pj


def make_queue():
    from repro import nmc

    pool = nmc.BucketedPool(donate=True, backend="auto")
    return nmc.DispatchQueue(pool=nmc.ResidentPool(pool=pool))


def run(h: harness.Harness, cfg: dict, traffic: dict, seed: int
        ) -> harness.Result:
    n_tiles = int(cfg["n_tiles"])
    insts, lowered = build_library(cfg, traffic, seed)
    refs = [ref.reference(i.kind, i.args, i.sew, i.params) for i in insts]
    real = {}
    for lk in lowered:
        p = lk.program
        real[p.engine] = real.get(p.engine, 0) + p.n_instr - p.n_nops
    queue = make_queue()
    pool = queue.pool.pool
    queue.run_builds(lowered, n_tiles=n_tiles)        # warm every bucket

    pick = np.random.default_rng([seed, 1])
    sample: list[tuple[int, list]] = []
    pad0, use0 = pool.pad_waste, pool.useful_instrs
    passes = traced_passes = 0
    call_ms = []
    h.open_window()
    while not h.done():
        t = time.perf_counter()
        with h.span("nmc.run_builds"):
            outs = queue.run_builds(lowered, n_tiles=n_tiles)
        call_ms.append((time.perf_counter() - t) * 1e3)
        if h.traced is not None and h.traced[1] is None:
            traced_passes += 1
        # reservoir sample of whole passes, drawn from the seed
        if len(sample) < SAMPLE_PASSES:
            sample.append((passes, outs))
        else:
            j = int(pick.integers(passes + 1))
            if j < SAMPLE_PASSES:
                sample[j] = (passes, outs)
        passes += 1
    h.close_window()

    n_kernels = passes * len(lowered)
    bad = sum(ref.mismatches(o, r) for _, outs in sample
              for o, r in zip(outs, refs))
    cyc, pj = modeled(lowered)
    return harness.Result(
        attempted=n_kernels, failed=0,
        end_to_end={"nmc_kernels_per_s": n_kernels / h.window_s},
        compared=[harness.Compared("mismatched_elements", bad, 0)],
        facts={"call_ms": call_ms,
               "pad_waste": pool.pad_waste - pad0,
               "useful_instrs": pool.useful_instrs - use0,
               "real_instrs_traced": {e: n * traced_passes
                                      for e, n in real.items()},
               "kernels_traced": traced_passes * len(lowered)},
        notes=[f"library: {len(lowered)} programs, {passes} passes, "
               f"{len(sample)} passes checked "
               f"({sum(r.size for r in refs) * len(sample)} elements)",
               f"modeled work in the window: {cyc * passes!r} tile cycles, "
               f"{pj * passes!r} pJ ({cyc!r} cycles, {pj!r} pJ per pass)"])
