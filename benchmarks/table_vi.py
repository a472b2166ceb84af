"""Table VI reproduction: the MLCommons-tiny Anomaly Detection autoencoder
(10 FC layers + ReLU) end-to-end on CPU cluster vs NM-Caesar vs NM-Carus.

Two parts:
  1. A *functional* reduced autoencoder executed on the Carus engine
     (weights tiled through the 32 KiB VRF exactly as the full app would),
     verified bit-exact against the quantized numpy oracle.
  2. An analytic full-size model (640-128-...-8-...-640, 264k MACs, int8)
     built on the calibrated timing/energy constants:
       * NM-Carus: vmacc matvecs (4 MACs/cyc) + serial weight reload through
         the single-port banks (no overlap: every vector register interleaves
         across all 4 banks, so DMA writes conflict with compute — Fig. 6).
       * NM-Caesar: the 66k-microinstruction stream cannot be precompiled
         (264 KiB of code), so the CV32E20 host assembles commands online at
         ~5 cycles/instruction (Section I: "the CPU [spends] significant
         time encoding such operations at runtime").
       * CPU baseline: the paper's measured 561k cycles (RV32IMCXcv).
"""

from __future__ import annotations

import numpy as np

from repro.core import constants as C
from benchmarks import paper_data as PD

LAYERS = [(640, 128), (128, 128), (128, 128), (128, 128), (128, 8),
          (8, 128), (128, 128), (128, 128), (128, 128), (128, 640)]
E20_ENCODE_CYC_PER_INSTR = 5.0
BASE_CYCLES = PD and 561e3


def model_carus() -> dict:
    compute = 0.0
    vrf_acc = 0
    n_instr = 0
    for din, dout in LAYERS:
        words = -(-dout // 4)
        wpl = -(-words // C.CARUS_N_LANES)
        per_vmacc = max(C.CARUS_ALU_WORD_CYCLES["macc"][8], 3) * wpl
        compute += din * (per_vmacc + 1)       # + emvx of x[k]
        vrf_acc += din * 3 * words
        n_instr += din
        compute += C.CARUS_KERNEL_OVERHEAD_CYCLES
    load = sum(din * dout for din, dout in LAYERS) / 4.0   # 1 word/cycle DMA
    cycles = compute + load
    t = cycles / C.F_CLK_BENCH_HZ
    e_pj = (C.P_CARUS_FIX_MW * 1e-3 * t * 1e12
            + vrf_acc * C.E_CARUS_VRF_ACCESS_PJ
            + load * C.E_CARUS_VRF_ACCESS_PJ           # DMA writes banks
            + C.P_CPU_SYS_MW * 0.4 * 1e-3 * t * 1e12)  # E20 + sys mem share
    return {"cycles": cycles, "energy_uj": e_pj / 1e6}


def model_caesar() -> dict:
    n_instr = sum(din * (-(-dout // 4)) for din, dout in LAYERS)
    compute = 2.0 * n_instr                     # 2 cyc/op, banks split
    encode = E20_ENCODE_CYC_PER_INSTR * n_instr  # online command assembly
    load = sum(din * dout for din, dout in LAYERS) / 4.0
    splats = sum(din for din, _ in LAYERS) * 2.0
    cycles = max(compute, encode) + load + splats
    t = cycles / C.F_CLK_BENCH_HZ
    e_pj = C.P_CAESAR_SYS_MW * 1e-3 * t * 1e12
    return {"cycles": cycles, "energy_uj": e_pj / 1e6}


def run() -> list[dict]:
    rows = []
    ours = {"caesar_e20": model_caesar(), "carus_e20": model_carus()}
    for cfgname, p in PD.TABLE_VI.items():
        row = {"config": cfgname,
               "paper_cycle_factor": p["cycles"],
               "paper_energy_factor": p["energy"],
               "paper_area_factor": p["area"]}
        if cfgname in ours:
            m = ours[cfgname]
            row["model_cycles"] = m["cycles"]
            row["model_cycle_factor"] = PD and 561e3 / m["cycles"]
            row["model_energy_uj"] = m["energy_uj"]
            row["model_energy_factor"] = 13.5 / m["energy_uj"]
        rows.append(row)
    return rows


def functional_demo() -> bool:
    """Reduced autoencoder (fits the 32 KiB VRF) run on the Carus engine."""
    import jax.numpy as jnp
    from repro.core import alu, carus, isa
    from repro.core.carus import trace_entry
    from repro.core.isa import VOp

    rng = np.random.default_rng(3)
    dims = [64, 32, 8, 32, 64]
    ws = [rng.integers(-4, 5, (dims[i], dims[i + 1])).astype(np.int8)
          for i in range(4)]
    x = rng.integers(-8, 9, dims[0]).astype(np.int8)

    # oracle: int8 wrap matvec + relu between layers
    a = x
    for i, w in enumerate(ws):
        a = (a.astype(np.int64) @ w.astype(np.int64)).astype(np.int8)
        if i < 3:
            a = np.maximum(a, 0)
    oracle = a

    vpu = carus.CarusVPU()
    vrf = np.zeros((32, 256), np.int32)
    # v0: activation; weights columns per input: v8+...: W rows packed per k
    cur = x
    act_reg, tmp_reg = 0, 1
    vrf[act_reg, :len(x) // 4] = alu.pack_np(x)
    for li, w in enumerate(ws):
        din, dout = w.shape
        # load weight rows into regs 8.. (host memory-mode writes)
        # executed functionally by poking the VRF between segments
        tr = [trace_entry(VOp.VSETVL, sval1=dout)]
        for k in range(din):
            row = np.pad(w[k].astype(np.int8), (0, (-dout) % 4))
            vrf[8 + k % 16, :len(row) // 4] = alu.pack_np(row)
            op = VOp.VMUL if k == 0 else VOp.VMACC
            tr.append(trace_entry(op, vd=tmp_reg, vs2=8 + k % 16,
                                  sval1=int(cur[k]), mode=isa.MODE_VX))
            if (k % 16 == 15) or k == din - 1:   # flush segment
                out, _, _ = vpu.run_trace(jnp.asarray(vrf),
                                          carus.trace_to_arrays(tr), 8)
                vrf = np.array(out)
                tr = [trace_entry(VOp.VSETVL, sval1=dout)]
        if li < 3:
            tr = [trace_entry(VOp.VSETVL, sval1=dout),
                  trace_entry(VOp.VMAX, vd=tmp_reg, vs2=tmp_reg, sval1=0,
                              mode=isa.MODE_VX)]
            out, _, _ = vpu.run_trace(jnp.asarray(vrf),
                                      carus.trace_to_arrays(tr), 8)
            vrf = np.array(out)
        cur = alu.unpack_np(vrf[tmp_reg], np.int8)[:dout]
        vrf[act_reg] = 0
        vrf[act_reg, : (-(-dout // 4))] = alu.pack_np(
            np.pad(cur, (0, (-dout) % 4)))
    return bool((cur == oracle).all())


def main():
    ok = functional_demo()
    print(f"functional reduced autoencoder on NM-Carus engine: "
          f"{'BIT-EXACT' if ok else 'MISMATCH'}")
    assert ok
    rows = run()
    print(f"\n{'config':14s} {'paper cyc x':>12s} {'model cyc x':>12s} "
          f"{'paper en x':>11s} {'model en x':>11s}")
    for r in rows:
        mc = r.get("model_cycle_factor")
        me = r.get("model_energy_factor")
        print(f"{r['config']:14s} {r['paper_cycle_factor']:12.2f} "
              f"{mc if mc is None else round(mc, 2)!s:>12s} "
              f"{r['paper_energy_factor']:11.2f} "
              f"{me if me is None else round(me, 2)!s:>11s}")
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
