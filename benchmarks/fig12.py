"""Fig. 12 reproduction: matmul throughput/energy scaling with matrix shape.

Sweeps P for the A[8,8] x B[8,P] kernel on both engines and checks the two
published saturation points (8-bit): NM-Carus 0.48 outputs/cycle and
~66 pJ/output; NM-Caesar 0.25 outputs/cycle and ~175 pJ/output, plus the
crossover (Caesar beats Carus at small P because of the eCPU bootstrap).
"""

from __future__ import annotations

from repro.core import energy, programs, timing
from repro.nmc.pool import ResidentPool
from benchmarks import paper_data as PD


def run(sew: int = 8, verify: bool = False,
        pool: ResidentPool | None = None) -> list[dict]:
    kbs = [programs.build_matmul(sew, p=p, seed=11)
           for p in (8, 16, 32, 64, 128, 256, 512, 1024)]
    if verify:
        # whole P-sweep through the resident tile pool, bit-exact; the
        # P-sweep's ragged instruction counts share power-of-two buckets
        res = programs.verify_sweep(kbs, pool)
        assert all(all(v.values()) for v in res.values()), res
    rows = []
    for p, kb in zip((8, 16, 32, 64, 128, 256, 512, 1024), kbs):
        t = timing.kernel_timing(kb)
        e = energy.kernel_energy(kb)
        rows.append({
            "P": p,
            "caesar_out_per_cyc": kb.caesar.n_outputs /
            t["caesar"].total_cycles,
            "carus_out_per_cyc": kb.carus.n_outputs / t["carus"].total_cycles,
            "cpu_out_per_cyc": kb.n_outputs / t["cpu"].total_cycles,
            "caesar_pj_per_out": e["caesar"].energy_pj / kb.caesar.n_outputs,
            "carus_pj_per_out": e["carus"].energy_pj / kb.carus.n_outputs,
        })
    return rows


def main():
    rows = run()
    print(f"{'P':>6s} {'CPU out/cyc':>12s} {'Caesar':>8s} {'Carus':>8s} "
          f"{'Caesar pJ/out':>14s} {'Carus pJ/out':>13s}")
    for r in rows:
        print(f"{r['P']:6d} {r['cpu_out_per_cyc']:12.4f} "
              f"{r['caesar_out_per_cyc']:8.3f} {r['carus_out_per_cyc']:8.3f} "
              f"{r['caesar_pj_per_out']:14.1f} {r['carus_pj_per_out']:13.1f}")
    sat = rows[-1]
    print(f"\nsaturation checks (paper): Carus {sat['carus_out_per_cyc']:.3f}"
          f" vs {PD.FIG12_CARUS_SAT_OUT_PER_CYC} out/cyc; "
          f"Caesar {sat['caesar_out_per_cyc']:.3f} vs "
          f"{PD.FIG12_CAESAR_SAT_OUT_PER_CYC}; "
          f"Carus {sat['carus_pj_per_out']:.0f} vs "
          f"{PD.FIG12_CARUS_SAT_PJ_PER_OUT} pJ/out; "
      f"Caesar {sat['caesar_pj_per_out']:.0f} vs "
          f"{PD.FIG12_CAESAR_SAT_PJ_PER_OUT} pJ/out")
    small = rows[0]
    print(f"crossover check: at P=8 Caesar ({small['caesar_out_per_cyc']:.3f}"
          f" out/cyc) should beat Carus ({small['carus_out_per_cyc']:.3f}) "
          f"— eCPU bootstrap overhead (Fig. 12 discussion)")
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
