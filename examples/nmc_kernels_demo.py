"""NMC execution demo: the traced frontend, the bucketed tile scheduler
and the Pallas kernels.

0. ``nmc.kernel`` — author a custom fused kernel as numpy-style Python:
   one decorator gives tracing, engine auto-selection, unified-IR
   lowering, pooled scheduling and sync/async dispatch (DESIGN.md §7).
1. Bucketed multi-tile dispatch — a heterogeneous kernel sweep runs through
   :class:`repro.nmc.ResidentPool`: instruction streams NOP-pad to
   power-of-two buckets, so the whole sweep compiles once per
   ``(engine, sew, bucket)`` instead of once per kernel shape.
2. Resident tile array — :class:`repro.nmc.ResidentPool` keeps tile
   memories on device across dispatches (the paper's memory-mode /
   compute-mode duality): re-dispatching a program moves only instruction
   bytes, never tile state.
3. ``vrf_alu`` — the NM-Carus VPU as a fused vector-program kernel: an
   N-instruction program executes against a VMEM-resident register file in
   ONE pallas_call (one HBM round-trip instead of N), with the program as
   runtime data (the indirect-addressing property: no retrace per program).
4. ``nmc_matmul`` — the W8A8 vmacc loop on the MXU with fused
   dequant+bias+activation epilogue.

The Pallas kernels run here in interpret mode (CPU container); on TPU
hardware the same calls lower to Mosaic.

Run:  PYTHONPATH=src python examples/nmc_kernels_demo.py
"""

import numpy as np
import jax.numpy as jnp

from repro import nmc
from repro.core import programs, timing
from repro.kernels import ref
from repro.kernels.nmc_matmul import nmc_matmul
from repro.kernels.vrf_alu import make_prog, vrf_alu
from repro.nmc import DispatchQueue, ResidentPool


def frontend_demo():
    rng = np.random.default_rng(1)
    print("nmc.kernel: numpy-style authoring, the whole stack in one call")

    @nmc.kernel
    def leaky_gate(t, x, g):
        xv, gv = t.load(x, bank=0), t.load(g)
        t.store(xv.max(xv >> 2) & gv)        # leaky-relu, gated

    x = rng.integers(-128, 128, 1024, dtype=np.int8)
    g = rng.integers(-128, 128, 1024, dtype=np.int8)
    picked = leaky_gate.select_engine(x, g)
    sync = leaky_gate(x, g)
    futs = [leaky_gate.call_async(x, g, engine=e)
            for e in ("caesar", "carus")]
    agree = all((np.asarray(f.result()) == np.asarray(sync)).all()
                for f in futs)
    oracle_ok = (np.asarray(sync) == leaky_gate.oracle(x, g)).all()
    assert agree and oracle_ok, "frontend sync/async/oracle diverged"
    print(f"  auto-selected engine: {picked}; sync == async(caesar) == "
          f"async(carus) == numpy oracle: {agree and oracle_ok}")

    @nmc.kernel
    def needs_carus(t, x, g):
        t.store(t.load(x).maxu(t.load(g)))
    print(f"  x.maxu(g) auto-selects: {needs_carus.select_engine(x, g)} "
          f"(unsigned compares are xvnmc-only)")


def nmc_scheduler_demo():
    small = {"caesar_bytes": 2048, "carus_bytes": 4096}
    kbs = [programs.build(name, 8, **small)
           for name in ("xor", "mul", "relu", "leaky_relu")]
    # a ragged size: 384 bus ops pad into the same 512 bucket as the others
    kbs.append(programs.build("add", 8, caesar_bytes=1536, carus_bytes=4096))
    builds = [eb for kb in kbs for eb in (kb.caesar, kb.carus)]

    print("bucketed scheduler: heterogeneous sweep, one compile per bucket")
    pool = ResidentPool()
    outs = pool.run_builds(builds)
    exact = all((got.reshape(-1)[: eb.oracle.size]
                 == eb.oracle.reshape(-1)).all()
                for got, eb in zip(outs, builds))
    assert exact, "bucketed sweep diverged from the kernel oracles"
    shapes = {eb.program.shape_key for eb in builds}
    buckets = {eb.program.bucket_key for eb in builds}
    print(f"  {len(builds)} kernel instances, {len(shapes)} exact shapes -> "
          f"{len(buckets)} buckets, {pool.compiles} compiles, "
          f"pad_waste={pool.pool.pad_waste} slots, bit-exact={exact}")

    print("resident tile array: load once, dispatch many (compute mode)")
    rpool = ResidentPool()
    rpool.run_builds(builds[:2])
    loaded = rpool.bytes_moved
    rpool.dispatch([(t, eb.program)
                    for t, eb in zip(rpool.tiles, builds[:2])])
    print(f"  initial load+run moved {loaded} B; re-dispatch moved "
          f"{rpool.bytes_moved - loaded} B (instruction stream only), "
          f"{rpool.compiles} compiles total")

    print("async dispatch queue: double-buffered futures over a 2-tile array")
    queue = DispatchQueue()
    async_outs = queue.run_builds(builds, n_tiles=2)
    async_ok = all((got.reshape(-1)[: eb.oracle.size]
                    == eb.oracle.reshape(-1)).all()
                   for got, eb in zip(async_outs, builds))
    assert async_ok, "async futures diverged from the kernel oracles"
    stages = [timing.stage_cost(eb) for eb in builds]
    ser = timing.dispatch_cycles(stages, "serial")
    ovl = timing.dispatch_cycles(stages, "overlapped")
    print(f"  {queue.submitted} work items in {queue.waves} waves, "
          f"{queue.staged_while_busy} images staged while the tile was "
          f"busy, bit-exact={async_ok}")
    print(f"  modeled dispatch cost: serial {ser:.0f} cyc -> overlapped "
          f"{ovl:.0f} cyc ({ovl / ser:.2f}x, max(dma, compute) per stage)")


def main():
    rng = np.random.default_rng(0)

    frontend_demo()
    print()

    nmc_scheduler_demo()
    print()

    print("vrf_alu: one kernel, arbitrary programs (program = data)")
    vrf = jnp.asarray(rng.integers(-100, 100, (32, 4096)).astype(np.int16))
    # program A: leaky-relu of v1 into v3 via the paper's max(x, x>>2) trick
    prog_a = make_prog([("sra", 2, 0, 1, 2, ref.VRF_MODE_VX),
                        ("max", 3, 2, 1, 0, ref.VRF_MODE_VV)])
    # program B: fused (v1*v4 + v5) ^ v1, then clamp
    prog_b = make_prog([("mul", 6, 4, 1, 0, ref.VRF_MODE_VV),
                        ("add", 6, 5, 6, 0, ref.VRF_MODE_VV),
                        ("xor", 7, 1, 6, 0, ref.VRF_MODE_VV),
                        ("min", 7, 0, 7, 100, ref.VRF_MODE_VX),
                        ("max", 7, 0, 7, -100, ref.VRF_MODE_VX)])
    for name, prog in (("leaky_relu", prog_a), ("fused_chain", prog_b)):
        out = vrf_alu(vrf, prog, block_vl=1024, interpret=True)
        pd = {k: np.asarray(prog[:, i]) for i, k in
              enumerate(("op", "vd", "vs1", "vs2", "scalar", "mode"))}
        exp = ref.vrf_alu(vrf, pd)
        ok = bool((np.asarray(out) == np.asarray(exp)).all())
        assert ok, f"vrf_alu {name} diverged from the reference"
        print(f"  {name}: {prog.shape[0]} instrs, one HBM round-trip, "
              f"bit-exact={ok}")

    print("\nnmc_matmul: W8A8 with fused epilogue (int32 accumulation)")
    m, k, n = 512, 1024, 512
    w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32)) * 0.05
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    wq, sw = ref.quantize_rowwise(w)
    xq, sx = ref.quantize_dynamic(x)
    y = nmc_matmul(xq, wq, sw * sx, None, act="relu", interpret=True)
    exact = jnp.maximum(x @ w, 0)
    rel = float(jnp.linalg.norm(y - exact) / jnp.linalg.norm(exact))
    print(f"  {m}x{k}x{n}: relative error vs fp32 {rel:.4f} "
          f"(int8 weights: {k*n/2**20:.1f} MiB vs fp32 {4*k*n/2**20:.1f})")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
