"""Table V reproduction: kernel throughput/energy improvements vs the CPU.

Runs every kernel x bitwidth functionally (bit-exact check on both engines),
derives cycles/energy from the calibrated mechanistic models, and compares
the improvement factors against the paper's published Table V.

The whole sweep rides the public ``repro.nmc`` stack (DESIGN.md §7): the
kernel builders are traced-frontend kernels (their instruction streams are
emitted by :mod:`repro.nmc.frontend` lowering, their oracles by the
tracer's ``alu.*_np`` evaluation), and the functional sweep dispatches
through a :class:`repro.nmc.ResidentPool`: all (kernel x SEW x engine)
instances NOP-pad to power-of-two instruction buckets and run as batched
multi-tile groups — one XLA compile per ``(engine, sew, bucket)`` instead
of one per kernel instance or exact program shape.  ``run`` asserts the
compile bound (compiles <= #buckets) on the pool counters, so the CI smoke
subset gates the scheduling property, not just functional correctness.
"""

from __future__ import annotations

from repro.core import energy, programs, timing
from repro.nmc import ResidentPool
from benchmarks import paper_data as PD

ALL_SEWS = (8, 16, 32)


def sweep_buckets(kbs: list) -> set[tuple]:
    """The distinct (engine, sew, instr-bucket) buckets of a kernel sweep —
    the compile-count bound of the bucketed pool."""
    return {getattr(kb, eng).program.bucket_key
            for kb in kbs for eng in ("caesar", "carus")
            if getattr(kb, eng) is not None}


def run(verify_functional: bool = True,
        kernels: tuple = programs.TABLE_V_KERNELS,
        sews: tuple = ALL_SEWS,
        pool: ResidentPool | None = None) -> list[dict]:
    kbs = [programs.build(name, sew) for name in kernels for sew in sews]
    func_ok: dict = {}
    if verify_functional:
        pool = pool if pool is not None else ResidentPool()
        compiles0 = pool.compiles
        func_ok = programs.verify_sweep(kbs, pool)
        bad = {k: v for k, v in func_ok.items() if not all(v.values())}
        assert not bad, bad
        # the scheduling property of DESIGN.md §5: the whole sweep
        # compiles at most once per (engine, sew, bucket)
        n_buckets = len(sweep_buckets(kbs))
        assert pool.compiles - compiles0 <= n_buckets, \
            (pool.compiles - compiles0, n_buckets)
    rows = []
    for kb in kbs:
        name, sew = kb.name, kb.sew
        ok = func_ok.get((name, sew), {"caesar": None, "carus": None})
        t = timing.kernel_timing(kb)
        e = energy.kernel_energy(kb)
        cpu_cpo = t["cpu"].total_cycles / kb.n_outputs
        cpu_epo = e["cpu"].energy_pj / kb.n_outputs
        row = {"kernel": name, "sew": sew,
               "functional_ok": all(v for v in ok.values() if v
                                    is not None)}
        for eng in ("caesar", "carus"):
            nout = getattr(kb, eng).n_outputs
            thr = cpu_cpo / (t[eng].total_cycles / nout)
            en = cpu_epo / (e[eng].energy_pj / nout)
            p_thr, p_en = (PD.TABLE_V_THROUGHPUT[name][sew],
                           PD.TABLE_V_ENERGY[name][sew])
            i = 0 if eng == "caesar" else 1
            row[f"thr_{eng}"] = thr
            row[f"thr_{eng}_paper"] = p_thr[i]
            row[f"thr_{eng}_err"] = thr / p_thr[i] - 1
            row[f"en_{eng}"] = en
            row[f"en_{eng}_paper"] = p_en[i]
            row[f"en_{eng}_err"] = en / p_en[i] - 1
            row[f"erratum_{eng}"] = (name, sew, eng, "energy") in \
                PD.SUSPECTED_ERRATA
        rows.append(row)
    return rows


def main():
    pool = ResidentPool()
    rows = run(pool=pool)
    print(f"{'kernel':12s} sew | thrC model/paper | thrK model/paper |"
          f" enC model/paper | enK model/paper")
    errs = []
    for r in rows:
        print(f"{r['kernel']:12s} {r['sew']:3d} |"
              f" {r['thr_caesar']:6.1f}/{r['thr_caesar_paper']:6.1f} |"
              f" {r['thr_carus']:6.1f}/{r['thr_carus_paper']:6.1f} |"
              f" {r['en_caesar']:6.1f}/{r['en_caesar_paper']:6.1f} |"
              f" {r['en_carus']:6.1f}/{r['en_carus_paper']:6.1f}"
              + ("  [suspected paper erratum]" if r["erratum_carus"] else ""))
        for k in ("thr_caesar_err", "thr_carus_err", "en_caesar_err",
                  "en_carus_err"):
            if not (r["erratum_carus"] and k == "en_carus_err"):
                errs.append(abs(r[k]))
    import statistics
    print(f"\nvalidation vs Table V ({len(errs)} cells, erratum excluded): "
          f"mean |err| {100*statistics.mean(errs):.1f}%, "
          f"median {100*statistics.median(errs):.1f}%, "
          f"max {100*max(errs):.1f}%")
    print(f"tile pool: {pool.programs_run} programs in {pool.dispatches} "
          f"batched dispatches, {pool.compiles} compiles "
          f"({len(pool.pool.shape_keys_compiled)} buckets, "
          f"pad_waste={pool.pool.pad_waste} instr slots, "
          f"bytes_moved={pool.bytes_moved})")
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
