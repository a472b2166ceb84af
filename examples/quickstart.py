"""Quickstart: the whole NMC stack as one function call (`nmc.jit`).

Write a kernel as numpy-style Python; calling it runs trace -> engine
auto-selection -> unified-IR lowering -> bucketed/resident scheduling ->
dispatch -> extraction, bit-exact against the pure-numpy oracle the
tracer evaluates alongside.  This demo:

1. compiles a fused elementwise kernel and runs it on BOTH engines, sync
   and async, comparing against the oracle and reporting modeled
   cycles/energy;
2. shows engine auto-selection picking NM-Caesar for bus-expressible
   bodies and NM-Carus for bodies the bus ALU cannot express — plus the
   `UnsupportedOnEngine` diagnostic for an explicit bad choice;
3. runs the paper's 8-bit matmul (Table V) through the same traced
   frontend (the kernel library is built on it) against the RV32IMC CPU
   baseline;
4. shards ONE kernel across the tile array (`tiles=N`): the partitioning
   planner splits the matmul's output rows over 4 tiles, the wave runs as
   one batched dispatch, a future-of-gathers reassembles the result
   bit-exactly, and the shared-bus timing model reports the wave speedup;
5. runs the same kernel on a second runtime with the other executor
   (`NmcRuntime(backend="pallas")`): the bucketed instruction stream runs
   as one fused `pl.pallas_call` instead of a per-instruction `lax.scan`,
   bit-exact and timed against the scan reference (DESIGN.md §10).

Run:  PYTHONPATH=src python examples/quickstart.py   (finishes in ~30 s)
"""

import numpy as np

from repro import nmc
from repro.core import energy, programs, timing
from repro.core.constants import F_CLK_BENCH_HZ


def main():
    rng = np.random.default_rng(0)
    print("=" * 64)
    print("1. one kernel, the whole stack: @nmc.kernel -> both engines")
    print("=" * 64)

    @nmc.kernel                       # trace + auto engine selection, SEW 8
    def fused(t, x, y):
        a, b = t.load(x, bank=0), t.load(y)
        t.store(((a * 3) + b).max(0))        # scaled-add + ReLU

    x = rng.integers(-128, 128, 2048, dtype=np.int8)
    y = rng.integers(-128, 128, 2048, dtype=np.int8)
    oracle = fused.oracle(x, y)

    for engine in ("caesar", "carus"):
        out = fused(x, y, engine=engine)            # synchronous call
        fut = fused.call_async(x, y, engine=engine)  # DispatchQueue future
        ok = (np.asarray(out) == oracle).all() and \
            (np.asarray(fut.result()) == np.asarray(out)).all()
        assert ok, f"{engine}: sync/async diverged from the numpy oracle"
        lk = fused.lower(x, y, engine=engine)
        t = timing.program_cycles(lk.program)
        e = energy.program_energy(lk.program)
        print(f"  {engine:6s}: {lk.program.n_instr:5d} instrs, "
              f"{t.total_cycles:7.0f} cyc "
              f"({t.total_cycles / F_CLK_BENCH_HZ * 1e6:5.1f} us @250MHz), "
              f"{e.energy_pj / 1e3:6.1f} nJ, sync==async==oracle: {ok}")

    print()
    print("=" * 64)
    print("2. engine auto-selection + diagnostics")
    print("=" * 64)

    @nmc.kernel
    def bus_friendly(t, x):
        t.store((t.load(x) + 1).max(0))

    @nmc.kernel
    def needs_vector_isa(t, x):
        t.store(t.load(x).maxu(100))         # unsigned max: xvnmc only

    print(f"  (x + 1).relu()  -> {bus_friendly.select_engine(x)}"
          f"   (bus-expressible: host-streamed micro-ops, no eCPU boot)")
    print(f"  x.maxu(100)     -> {needs_vector_isa.select_engine(x)}"
          f"   (the bus ALU has no unsigned compare)")
    try:
        needs_vector_isa.lower(x, engine="caesar")
    except nmc.UnsupportedOnEngine as err:
        print(f"  explicit engine='caesar' raises: {err}")

    # every lower() also runs the static verifier (DESIGN.md §11);
    # corrupt a lowered stream and the checker names the pass, the rule
    # and the offending instruction (with tracer-op provenance)
    from repro.nmc import check
    lk = bus_friendly.lower(x, check="off")
    lk.program.entries["op"][2] = 63          # smash one opcode
    diag = check.verify_lowered(lk).errors[0]
    print(f"  tampered stream  -> {diag}")

    print()
    print("=" * 64)
    print("3. Table V matmul (8-bit) through the same traced frontend")
    print("=" * 64)
    kb = programs.build("matmul", 8)      # kernel library = traced kernels
    ok = programs.verify(kb)
    print(f"  functional (bit-exact vs quantized oracle): {ok}")
    assert all(ok.values()), ok
    t = timing.kernel_timing(kb)
    e = energy.kernel_energy(kb)
    cpu_cyc = t["cpu"].total_cycles
    print(f"  {'target':8s} {'cycles':>9s} {'us @250MHz':>11s} "
          f"{'energy nJ':>10s} {'vs CPU':>7s}")
    for name in ("cpu", "caesar", "carus"):
        cyc = t[name].total_cycles
        outs = kb.n_outputs if name == "cpu" else getattr(kb, name).n_outputs
        speed = (cpu_cyc / kb.n_outputs) / (cyc / outs)
        print(f"  {name:8s} {cyc:9.0f} {cyc/F_CLK_BENCH_HZ*1e6:11.1f} "
              f"{e[name].energy_pj/1e3:10.1f} {speed:6.1f}x")

    print()
    print("=" * 64)
    print("4. tile-parallel partitioned execution (tiles=N, DESIGN.md §9)")
    print("=" * 64)

    @nmc.kernel                       # same kernel; sharding is a kwarg
    def matmul8(t, A, B):
        a = t.consts(A)
        rows = [t.load(B[r]) for r in range(8)]
        for i in range(8):
            acc = None
            for kk in range(8):
                acc = nmc.mac(acc, a[i, kk], rows[kk])
            t.store(acc)

    A = rng.integers(-128, 128, (8, 8), dtype=np.int8)
    B = rng.integers(-128, 128, (8, 256), dtype=np.int8)
    base = matmul8(A, B)                        # single tile
    fut = matmul8.call_async(A, B, tiles=4)     # 4-tile wave (auto: rows)
    out = fut.result()                          # future-of-gathers
    assert (np.asarray(out) == np.asarray(base)).all(), \
        "partitioned result diverged from the single-tile kernel"
    pplan, lks = matmul8.lower_wave(A, B, tiles=4)
    single = timing.stage_cost(matmul8.lower(A, B))
    shards = [timing.stage_cost(lk) for lk in lks]
    speedup = timing.wave_speedup(single, shards, pplan.n_shards)
    print(f"  strategy={pplan.strategy} shards={pplan.n_shards} "
          f"(one {lks[0].program.n_instr}-instr bucket, one compile)")
    print(f"  bit-exact vs single tile: True   modeled wave speedup "
          f"(shared-bus model): {speedup:.2f}x")

    rt = nmc.default_runtime()
    print(f"\n  shared runtime: {rt.bucketed.compiles} XLA compiles, "
          f"{rt.resident.dispatches} dispatches, "
          f"{rt.queue.submitted} queued kernel calls (sync + async + "
          f"partitioned waves share the dispatch queue)")

    print()
    print("=" * 64)
    print("5. Pallas fast-path backend (NmcRuntime(backend=...), "
          "DESIGN.md §10)")
    print("=" * 64)
    # same kernel, one runtime per executor: the pool picks it once, and
    # on the Pallas one the whole bucketed instruction stream fuses into
    # one pl.pallas_call (interpret mode on CPU, native kernels on TPU;
    # backend='auto', the default, picks per device)
    import time

    rts = {bk: nmc.NmcRuntime(backend=bk) for bk in nmc.BACKENDS}
    ref = np.asarray(nmc.jit(matmul8.fn, runtime=rts["scan"])(A, B))
    fast = np.asarray(nmc.jit(matmul8.fn, runtime=rts["pallas"])(A, B))
    assert (fast == ref).all(), "pallas backend diverged from scan"

    def best_of(fn, n=3):
        t = [None] * n
        for i in range(n):
            t0 = time.perf_counter()
            fn()
            t[i] = time.perf_counter() - t0
        return min(t) * 1e6

    lk = matmul8.lower(A, B)
    us = {bk: best_of(lambda r=r: r.queue.submit(
              r.jit_tile, lk.program, image=lk.mem, out_slice=lk.out_slice,
              post=lk.post).result())
          for bk, r in rts.items()}
    dev = "CPU interpret mode" if nmc.resolve_backend("auto") == "scan" \
        else "native kernels"
    print("  matmul8 bit-exact scan == pallas: True")
    print(f"  dispatch: scan {us['scan']:8.0f} us   pallas "
          f"{us['pallas']:8.0f} us   ({us['scan'] / us['pallas']:.1f}x, "
          f"{dev})")

    print()
    print("=" * 64)
    print("6. Analysis-driven IR optimizer (opt='O1', DESIGN.md §13)")
    print("=" * 64)
    # a naively-written kernel: the accumulator is loaded like any other
    # operand (forcing a register copy on NM-Carus) and no bank hints are
    # given (landing all operands in one NM-Caesar bank).  opt="O1" —
    # the default — reclaims both, translation-validating every rewrite:
    # each applied rule re-runs the full static verifier AND a numpy
    # oracle differential before the cheaper program is accepted.

    @nmc.kernel
    def axpy(t, c0, w, x):
        t.store(nmc.mac(t.load(c0), t.load(w), t.load(x)))

    c0 = rng.integers(-100, 100, 2048, dtype=np.int8)
    w = rng.integers(-100, 100, 2048, dtype=np.int8)
    x = rng.integers(-100, 100, 2048, dtype=np.int8)
    for eng in ("caesar", "carus"):
        off = axpy.lower(c0, w, x, engine=eng, opt="off")
        o1 = axpy.lower(c0, w, x, engine=eng)       # default: O1
        assert (np.asarray(axpy(c0, w, x, engine=eng))
                == np.asarray(axpy(c0, w, x, engine=eng, opt="off"))).all()
        cyc_off = timing.program_cycles(off.program).cycles
        cyc_o1 = timing.program_cycles(o1.program).cycles
        rep = o1.opt_report
        rules = ",".join(r.rule for r in rep.rewrites) if rep else "-"
        print(f"  {eng:<7} {off.program.n_instr:>5} -> "
              f"{o1.program.n_instr:<5} instrs   {cyc_off:>6.0f} -> "
              f"{cyc_o1:<6.0f} cycles "
              f"(-{100 * (cyc_off - cyc_o1) / cyc_off:.0f}%)   [{rules}]")
    print("  bit-exact vs opt='off' on both engines: True "
          "(every rewrite translation-validated)")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
