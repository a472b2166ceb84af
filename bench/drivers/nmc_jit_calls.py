"""Driver: ``nmc.jit`` kernel calls from one client, in a closed loop.

Set-up compiles one ``nmc.jit`` kernel per (family, SEW, tile count) of
the mix with the ``nmc.jit`` defaults, and calls each once so that every
device program is compiled.  The window then calls them round-robin,
each call with fresh operands drawn from the seed: trace, lower,
optimize, verify, schedule and submit run on every call
(``CompiledKernel.call_async``), and ``result()`` waits for the tiles
and gathers the output.

Correctness: once the window has closed, every call's output is
compared with :mod:`bench.ref.nmc`.
"""

from __future__ import annotations

import time

import numpy as np

from bench import harness
from bench.drivers import nmc_library as lib
from bench.ref import nmc as ref


def kernels(traffic: dict, runtime) -> list:
    """``[(kind, sew, tiles, shape, CompiledKernel)]``, in call order."""
    from repro import nmc

    out = []
    probe = np.random.default_rng(0)
    for kind in traffic["families"]:
        shape = traffic["shapes"][kind]
        for sew in traffic["sews"]:
            for tiles in traffic["tiles"]:
                fn = lib.instance(kind, sew, shape, probe).fn
                out.append((kind, sew, tiles, shape,
                            nmc.jit(fn, sew=sew, tiles=tiles,
                                    runtime=runtime)))
    return out


def run(h: harness.Harness, cfg: dict, traffic: dict, seed: int
        ) -> harness.Result:
    from repro import nmc

    rt = nmc.NmcRuntime()
    ks = kernels(traffic, rt)
    rng = np.random.default_rng(seed)
    for kind, sew, _, shape, ck in ks:              # warm every program
        ck(*lib.instance(kind, sew, shape, rng).args)

    real, programs = {}, []
    submit = rt.queue.submit

    def counting_submit(tile, program, *a, **kw):
        real[program.engine] = real.get(program.engine, 0) + \
            program.n_instr - program.n_nops
        programs.append(program)
        return submit(tile, program, *a, **kw)
    rt.queue.submit = counting_submit

    pool = rt.bucketed
    pad0, use0 = pool.pad_waste, pool.useful_instrs
    calls, call_ms = [], []
    traced_calls = 0
    h.open_window()
    traced_real = None
    while not h.done():
        kind, sew, _, shape, ck = ks[len(calls) % len(ks)]
        inst = lib.instance(kind, sew, shape, rng)
        t = time.perf_counter()
        with h.span("nmc.call_async"):
            fut = ck.call_async(*inst.args)
        with h.span("nmc.result"):
            out = fut.result()
        call_ms.append((time.perf_counter() - t) * 1e3)
        calls.append((inst, out))
        if h.traced is not None and h.traced[1] is None:
            traced_calls += 1
            traced_real = dict(real)
    h.close_window()
    rt.queue.submit = submit

    from repro.core import energy, timing
    cyc = sum(timing.program_cycles(p).total_cycles for p in programs)
    pj = sum(energy.program_energy(p).energy_pj for p in programs)
    bad = sum(ref.mismatches(out, ref.reference(i.kind, i.args, i.sew,
                                                i.params))
              for i, out in calls)
    return harness.Result(
        attempted=len(calls), failed=0,
        end_to_end={"nmc_kernels_per_s": len(calls) / h.window_s},
        compared=[harness.Compared("mismatched_elements", bad, 0)],
        facts={"call_ms": call_ms,
               "frontend_ms": [s * 1e3 for s in
                               h.spans.get("nmc.call_async", [])],
               "pad_waste": pool.pad_waste - pad0,
               "useful_instrs": pool.useful_instrs - use0,
               "real_instrs_traced": traced_real or {},
               "kernels_traced": traced_calls},
        notes=[f"{len(ks)} kernels, {len(calls)} calls, every output "
               f"checked",
               f"modeled work in the window: {cyc!r} tile cycles, {pj!r} pJ "
               f"({len(programs)} tile programs)"])
