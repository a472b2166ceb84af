"""Tile-array scaling sweep: one kernel sharded across tiles (DESIGN.md §9).

The paper's headline property is *scalability* — arrays of identical
NM-Caesar / NM-Carus tiles behind an edge node's SRAM macros.  This
benchmark exercises the partitioning planner end to end: each Table V
kernel family (elementwise, relu, matmul, conv2d, maxpool) is authored as
an ``nmc.jit`` traced kernel and executed at tiles ∈ {1, 2, 4, 8, 16},
asserting three properties exactly where they are claimed:

* **bit-exactness** — every partitioned execution (sync *and* async
  futures-of-gathers) equals the single-tile output, which equals the
  traced numpy oracle;
* **compile discipline** — the whole sweep compiles at most once per
  ``(engine, sew, instr-bucket, tile-bucket)``: shard programs pre-pad to
  one common bucket per wave, so scaling the tile count never multiplies
  XLA compiles (``compiles <= #buckets``);
* **modeled scaling shape** — ``timing.wave_cycles`` (one shared system
  bus serializing DMA against overlapped per-tile compute) yields a wave
  speedup that rises monotonically with the tile count until the bus
  binds, and is strictly > 1 at tiles=4 on the matmul kernel.

Run:  PYTHONPATH=src python -m benchmarks.scaling [--smoke]
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

ALL_TILES = (1, 2, 4, 8, 16)
ALL_SEWS = (8, 16, 32)
ALL_KERNELS = ("mul", "relu", "matmul", "conv2d", "maxpool")


def make_kernels(sew: int, seed: int = 0, names=ALL_KERNELS) -> dict:
    """The five Table V kernel families as traced-kernel closures, sized
    for a quick sweep.  Returns ``{name: (kfn, args, host_post)}`` —
    ``host_post`` is the host-side finishing stage (maxpool's horizontal
    reduction, Section V-B1), applied identically after single-tile and
    gathered multi-tile outputs so it never affects bit-exactness."""
    import numpy as np
    from repro import nmc
    from repro.core import alu

    rng = np.random.default_rng(seed)
    dt = alu.NP_DTYPES[sew]
    info = np.iinfo(dt)

    def rand(shape):
        return rng.integers(info.min, info.max + 1, shape, dtype=dt)

    out: dict = {}
    if "mul" in names:
        def mul(t, x, y):
            t.store(t.load(x, bank=0) * t.load(y))
        out["mul"] = (mul, (rand(1536), rand(1536)), None)
    if "relu" in names:
        def relu(t, x):
            t.store(t.load(x).max(0))
        out["relu"] = (relu, (rand(1536),), None)
    if "matmul" in names:
        def matmul(t, A, B, m=8, k=8):
            a = t.consts(A)
            rows = [t.load(B[r]) for r in range(k)]
            for i in range(m):
                acc = None
                for kk in range(k):
                    acc = nmc.mac(acc, a[i, kk], rows[kk])
                t.store(acc)
        out["matmul"] = (matmul, (rand((8, 8)), rand((8, 256))), None)
    if "conv2d" in names:
        # shape constants bind as defaults: the closures must not read
        # loop-shared names at call time (late binding)
        def conv2d(t, A, F, rows_n=8, nn=128, f=3):
            fw = t.consts(F)
            av = [t.load(A[r]) for r in range(rows_n)]
            sh = {(dj, r): av[r].slide_down(dj)
                  for dj in range(1, f) for r in range(rows_n)}
            for i in range(rows_n - f + 1):
                acc = None
                for di in range(f):
                    for dj in range(f):
                        src = av[i + di] if dj == 0 else sh[(dj, i + di)]
                        acc = nmc.mac(acc, fw[di, dj], src)
                t.store(acc, n=nn - f + 1)
        out["conv2d"] = (conv2d, (rand((8, 128)), rand((3, 3))), None)
    if "maxpool" in names:
        pr, width = 16, 64
        X = rand((pr, width))
        even = np.ascontiguousarray(X[0::2]).reshape(-1)
        odd = np.ascontiguousarray(X[1::2]).reshape(-1)

        def maxpool(t, e, o):           # vertical stage on the tile array
            t.store(t.load(e, bank=0).max(t.load(o)))

        def horiz(v, pr=pr, width=width):   # horizontal stage on the host
            v = np.asarray(v).reshape(pr // 2, width)
            return np.maximum(v[:, 0::2], v[:, 1::2])
        out["maxpool"] = (maxpool, (even, odd), horiz)
    return out


def run(tiles=ALL_TILES, sews=ALL_SEWS, kernels=ALL_KERNELS,
        engines=("caesar", "carus"), smoke: bool = False,
        runtime=None) -> list[dict]:
    from repro import nmc
    from repro.core import timing
    from repro.nmc.pool import tile_bucket

    if smoke:
        tiles = (1, 2, 4)
        sews = (8,)
        kernels = ("mul", "matmul")
    rt = runtime if runtime is not None else nmc.NmcRuntime()
    compiles0 = rt.bucketed.compiles
    pad0, useful0 = rt.bucketed.pad_waste, rt.bucketed.useful_instrs
    expected_keys: set = set()
    rows: list[dict] = []

    for sew in sews:
        for name, (kfn, args, host_post) in make_kernels(sew,
                                                         names=kernels).items():
            kern = nmc.jit(kfn, sew=sew, runtime=rt)
            post = host_post if host_post is not None else np.asarray
            for engine in engines:
                base = np.asarray(post(kern(*args, engine=engine)))
                single = timing.stage_cost(kern.lower(*args, engine=engine))
                for n in tiles:
                    pplan, lks = kern.lower_wave(*args, engine=engine,
                                                 tiles=n)
                    progs = [lk.program for lk in lks]
                    assert len({p.bucket_key for p in progs}) == 1, \
                        "wave shards straddle instruction buckets"
                    expected_keys.add((*progs[0].bucket_key,
                                       tile_bucket(len(progs))))
                    sync = np.asarray(post(kern(*args, engine=engine,
                                                tiles=n)))
                    fut = kern.call_async(*args, engine=engine, tiles=n)
                    asyn = np.asarray(post(fut.result()))
                    ok = (sync == base).all() and (asyn == base).all()
                    assert ok, (name, sew, engine, n)
                    stages = [timing.stage_cost(lk) for lk in lks]
                    rows.append({
                        "kernel": name, "sew": sew, "engine": engine,
                        "tiles_requested": n, "shards": pplan.n_shards,
                        "strategy": pplan.strategy, "bitexact": bool(ok),
                        "wave_cycles": timing.wave_cycles(stages,
                                                          pplan.n_shards),
                        "single_cycles": timing.wave_cycles([single], 1),
                    })
    compiled = rt.bucketed.compiles - compiles0
    # the scheduling property: scaling the tile count costs at most one
    # XLA compile per (engine, sew, instr-bucket, tile-bucket)
    assert compiled <= len(expected_keys), (compiled, len(expected_keys))

    # modeled scaling shape on the matmul kernel (NM-Caesar): within each
    # contiguous run of one partition strategy, speedup rises monotonically
    # to its peak, then the serialized bus binds.  A strategy switch (rows
    # -> axis once the 8 output rows stop dividing the tile count) restarts
    # the curve: axis shards slice B instead of replicating it, so the bus
    # stream shrinks and the speedup jumps.
    mm = [r for r in rows
          if r["kernel"] == "matmul" and r["engine"] == engines[0]
          and r["sew"] == sews[0]]
    speedups = [r["single_cycles"] / r["wave_cycles"] for r in mm]
    strategies = [r["strategy"] for r in mm]
    i = 0
    while i < len(mm):
        j = i
        while j + 1 < len(mm) and strategies[j + 1] == strategies[i]:
            j += 1
        seg = speedups[i:j + 1]
        peak = max(range(len(seg)), key=seg.__getitem__)
        assert all(a <= b + 1e-9 for a, b in zip(seg[:peak],
                                                 seg[1:peak + 1])), speedups
        i = j + 1
    at4 = next(r["single_cycles"] / r["wave_cycles"] for r in mm
               if r["tiles_requested"] == 4)
    assert at4 > 1.0, at4
    for r in rows:
        r["wave_speedup"] = r["single_cycles"] / r["wave_cycles"]
    # ragged-tail waste visibility: every dispatch above (base calls,
    # partitioned sync waves, async gathers) reported its NOP padding into
    # the runtime's bucketed counters — surface and bound it here.  The
    # power-of-two bucket rule guarantees < 1x waste per program stream
    # and replicated padding lanes only appear at non-power-of-two shard
    # counts, so total waste must stay under 2x the useful instructions.
    pad_waste = rt.bucketed.pad_waste - pad0
    useful = rt.bucketed.useful_instrs - useful0
    if smoke:
        assert pad_waste < 2 * useful, (pad_waste, useful)
    r0 = {"compiles": compiled, "buckets": len(expected_keys),
          "matmul_speedup_at_4": at4, "pad_waste": pad_waste,
          "useful_instrs": useful}
    rows.append({"kernel": "_summary", **r0})
    return rows


def main(smoke: bool = False):
    rows = run(smoke=smoke)
    summary = rows.pop()
    print(f"{'kernel':8s} sew engine tiles shards strat  bitexact "
          f"wave-speedup")
    for r in rows:
        print(f"{r['kernel']:8s} {r['sew']:3d} {r['engine']:6s} "
              f"{r['tiles_requested']:5d} {r['shards']:6d} "
              f"{r['strategy']:6s} {str(r['bitexact']):8s} "
              f"{r['wave_speedup']:6.2f}x")
    print(f"\ncompiles={summary['compiles']} <= buckets="
          f"{summary['buckets']}; matmul wave speedup @4 tiles = "
          f"{summary['matmul_speedup_at_4']:.2f}x")
    print(f"pad_waste={summary['pad_waste']} instr slots over "
          f"useful={summary['useful_instrs']} "
          f"({summary['pad_waste'] / max(summary['useful_instrs'], 1):.2f}x"
          f" bucketing overhead)")
    return rows


if __name__ == "__main__":
    if __package__ in (None, ""):
        _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, _root)
        sys.path.insert(0, os.path.join(_root, "src"))
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced CI subset (mul+matmul @ sew=8, tiles<=4)")
    main(smoke=ap.parse_args().smoke)
