"""Table VIII reproduction: matmul A[10,10] x B[10,P] cycles + pJ/MAC vs
BLADE / C-SRAM (their published numbers) and our NM-Caesar / NM-Carus models.
"""

from __future__ import annotations

from repro.core import constants as C
from repro.core import energy, programs, timing
from benchmarks import paper_data as PD


def run() -> list[dict]:
    rows = []
    for sew in (8, 16, 32):
        p = PD.TABLE_VIII_P[sew]
        kb = programs.build_matmul(sew, p=p, seed=7)
        # Table VIII uses A[10,10]; our builder is A[8,8] — scale MAC count
        # and cycles by (10*10*P)/(8*8*P) analytically.
        scale = (10 * 10) / (8 * 8)
        t_caesar = timing.caesar_cycles(kb.caesar).cycles * scale
        t_carus = timing.carus_cycles(kb.carus, sew).cycles * scale
        n_mac = 10 * 10 * p
        e_caesar = energy.caesar_macro_energy_pj(kb) * scale / n_mac
        e_carus = energy.carus_macro_energy_pj(kb) * scale / n_mac
        rows.append({
            "sew": sew, "P": p,
            "caesar_cycles": t_caesar,
            "caesar_cycles_paper": PD.TABLE_VIII_CYCLES["caesar"][sew],
            "carus_cycles": t_carus,
            "carus_cycles_paper": PD.TABLE_VIII_CYCLES["carus"][sew],
            "caesar_pj_mac": e_caesar,
            "caesar_pj_mac_paper": PD.TABLE_VIII_PJ_PER_MAC_65NM["caesar"][sew],
            "carus_pj_mac": e_carus,
            "carus_pj_mac_paper": PD.TABLE_VIII_PJ_PER_MAC_65NM["carus"][sew],
            "blade_multi_cycles": PD.TABLE_VIII_CYCLES["blade_multi"][sew],
            "csram_cycles": PD.TABLE_VIII_CYCLES["csram"][sew],
        })
    return rows


def peak_efficiency_gops_w() -> dict:
    """Carus peak efficiency cross-check (Table VII: 306.7 GOPS/W)."""
    kb = programs.build_matmul(8, p=1024, seed=7)
    e_pj = energy.carus_macro_energy_pj(kb)
    n_ops = 2 * 8 * 8 * 1024          # 1 MAC = 2 ops (paper convention)
    gops_w = n_ops / (e_pj * 1e-12) / 1e9
    return {"model_gops_w": gops_w, "paper_gops_w": C.CARUS_PEAK_GOPS_W,
            "peak_gops_model": C.CARUS_N_LANES * 2 * C.F_CLK_MAX_HZ / 1e9,
            "peak_gops_paper": C.CARUS_PEAK_GOPS}


def main():
    rows = run()
    print(f"{'sew':>4s} {'P':>5s} | {'Caesar kcyc m/p':>16s} |"
          f" {'Carus kcyc m/p':>15s} | {'Caesar pJ/MAC m/p':>18s} |"
          f" {'Carus pJ/MAC m/p':>17s}")
    for r in rows:
        print(f"{r['sew']:4d} {r['P']:5d} |"
              f" {r['caesar_cycles']/1e3:7.1f}/{r['caesar_cycles_paper']/1e3:6.1f} |"
              f" {r['carus_cycles']/1e3:7.1f}/{r['carus_cycles_paper']/1e3:5.1f} |"
              f" {r['caesar_pj_mac']:8.1f}/{r['caesar_pj_mac_paper']:7.1f} |"
              f" {r['carus_pj_mac']:8.1f}/{r['carus_pj_mac_paper']:6.1f}")
    pk = peak_efficiency_gops_w()
    print(f"\nCarus peak efficiency: model {pk['model_gops_w']:.1f} GOPS/W "
          f"vs paper {pk['paper_gops_w']} (macro-level; see EXPERIMENTS.md "
          f"for the system-vs-macro accounting note)")
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
