"""Fig. 13 reproduction: average system power breakdown (8-/32-bit conv2d).

Checks the paper's three qualitative claims about the power structure:
  1. CPU system: memory accesses ~ the CPU's own power,
  2. NM-Caesar: ~70 % of power in memory, half of it instruction fetch,
  3. NM-Carus: VRF banks ~60 % of total, eCPU negligible.
"""

from __future__ import annotations

from repro.core import energy, programs, timing


def run(sew: int = 8) -> dict:
    kb = programs.build("conv2d", sew)
    tr = timing.carus_cycles(kb.carus, sew)
    acc = timing.carus_vrf_accesses(kb.carus, sew)
    acc_rate = acc / tr.cycles
    out = {
        "cpu": energy.power_breakdown_mw("cpu"),
        "caesar": energy.power_breakdown_mw("caesar"),
        "carus": energy.power_breakdown_mw("carus", acc_rate),
    }
    return out


def main():
    for sew in (8, 32):
        bd = run(sew)
        print(f"--- conv2d {sew}-bit: average power breakdown (mW) ---")
        for eng, comps in bd.items():
            total = sum(comps.values())
            parts = ", ".join(f"{k} {v:.2f} ({100*v/total:.0f}%)"
                              for k, v in comps.items())
            print(f"{eng:8s} total {total:5.2f} mW: {parts}")
        cpu = bd["cpu"]
        assert abs(cpu["system_mem"] / cpu["host_cpu"] - 1) < 0.15, \
            "claim 1: CPU-system memory ~ CPU power"
        cz = bd["caesar"]
        mem_frac = (cz["instr_fetch"] + cz["system_mem"] + cz["nmc_mem"]) \
            / sum(cz.values())
        assert 0.6 < mem_frac < 0.8, f"claim 2: {mem_frac}"
        ka = bd["carus"]
        vrf_frac = ka["vrf"] / sum(ka.values())
        assert 0.45 < vrf_frac < 0.7, f"claim 3: {vrf_frac}"
        assert ka["ecpu"] / sum(ka.values()) < 0.06, "eCPU negligible"
    print("\nFig. 13 qualitative structure reproduced "
          "(claims 1-3 of Section V-B1).")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
