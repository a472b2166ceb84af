"""Benchmark harness: one entry per paper table/figure + the roofline table.

Prints a ``name,us_per_call,derived`` CSV line per benchmark (the harness
contract), followed by each benchmark's detail table.  The NMC engines run
at f_clk = 250 MHz (the paper's benchmarking frequency), so us_per_call is
the modeled wall-clock of the 8-bit matmul kernel on each target.

The ``nmc_jit_frontend`` line gates the public one-call path (DESIGN.md
§7): a traced ``nmc.kernel`` must auto-select its engine and run bit-exact
vs the tracer's numpy oracle on both engines via both sync and async call
styles.  All functional sweeps dispatch through one shared
:class:`repro.nmc.ResidentPool` — the jit-cache/compile stats it
reports (and ``table_v.run`` asserts) verify the one-compile-per-bucket
property of the scheduler, and a second :class:`repro.nmc.ResidentPool`
re-dispatch demonstrates the residency contract: steady-state dispatches
move only instruction bytes, never tile memories.  The async
:class:`repro.nmc.DispatchQueue` section feeds a 2-tile array a
heterogeneous kernel stream (double-buffered staging, futures) and asserts
bit-exactness vs synchronous dispatch plus the overlapped-DMA timing win.

Run from the repo root as ``PYTHONPATH=src python -m benchmarks.run``
(pytest picks up ``src`` automatically via pyproject.toml).  Pass ``--smoke``
for the reduced CI subset.
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys
import time


def main(smoke: bool = False) -> None:
    import numpy as np
    from repro import nmc
    from repro.core import constants as C
    from repro.core import programs, timing
    from repro.nmc import ResidentPool
    from benchmarks import fig12, table_v, table_vi, table_viii

    pool = ResidentPool()
    lines = []

    # -- Traced frontend (nmc.jit): the public one-call path ------------------
    # A fused kernel authored against the frontend must auto-select, lower,
    # and run bit-exact vs the tracer's numpy oracle on BOTH engines via
    # both call styles — the public-API gate for everything below (the
    # Table V builders themselves are traced kernels).
    rng = np.random.default_rng(3)

    @nmc.kernel
    def fused(t, x, y):
        a, b = t.load(x, bank=0), t.load(y)
        t.store(((a * 3) + b).max(a >> 1))

    fx = rng.integers(-128, 128, 2048, dtype=np.int8)
    fy = rng.integers(-128, 128, 2048, dtype=np.int8)
    assert fused.select_engine(fx, fy) == "caesar"   # bus-expressible body
    oracle = fused.oracle(fx, fy)
    t0 = time.perf_counter()
    jit_ok = True
    for eng in ("caesar", "carus"):
        sync = np.asarray(fused(fx, fy, engine=eng))
        fut = fused.call_async(fx, fy, engine=eng)
        jit_ok &= (sync == oracle).all() and \
            (np.asarray(fut.result()) == sync).all()
    assert jit_ok, "nmc.jit sync/async diverged from the numpy oracle"
    jit_wall_s = time.perf_counter() - t0
    lines.append(("nmc_jit_frontend", jit_wall_s * 1e6 / 4,
                  f"bitexact={jit_ok},auto_engine=caesar,"
                  f"engines=2,call_styles=sync+async"))

    # -- Table V ------------------------------------------------------------
    kernels = ("xor", "matmul", "maxpool") if smoke \
        else programs.TABLE_V_KERNELS
    sews = (8,) if smoke else table_v.ALL_SEWS
    t0 = time.perf_counter()
    # table_v.run asserts compiles <= #buckets on this pool (CI smoke gate)
    rows_v = table_v.run(verify_functional=True, kernels=kernels, sews=sews,
                         pool=pool)
    sweep_wall_s = time.perf_counter() - t0
    # snapshot the pool counters here so the nmc_tile_pool line reports the
    # Table V sweep only (fig12 shares the pool below)
    sweep_stats = (pool.programs_run, pool.dispatches, pool.compiles,
                   len(pool.pool.shape_keys_compiled), pool.pool.pad_waste,
                   pool.bytes_moved)
    errs = []
    for r in rows_v:
        for k in ("thr_caesar_err", "thr_carus_err", "en_caesar_err",
                  "en_carus_err"):
            if not (r["erratum_carus"] and k == "en_carus_err"):
                errs.append(abs(r[k]))
    kb = programs.build("matmul", 8)
    us_caesar = timing.caesar_cycles(kb.caesar).total_cycles \
        / C.F_CLK_BENCH_HZ * 1e6
    us_carus = timing.carus_cycles(kb.carus, 8).total_cycles \
        / C.F_CLK_BENCH_HZ * 1e6
    lines.append(("table_v_matmul8_caesar", us_caesar,
                  f"mean_abs_err_vs_paper={100*statistics.mean(errs):.1f}%"))
    lines.append(("table_v_matmul8_carus", us_carus,
                  f"median_abs_err={100*statistics.median(errs):.1f}%"))

    # -- Fig 12 ---------------------------------------------------------------
    rows_12 = fig12.run(verify=smoke, pool=pool)
    sat = rows_12[-1]
    lines.append(("fig12_saturation", 0.0,
                  f"carus_out_per_cyc={sat['carus_out_per_cyc']:.3f}"
                  f"_paper_0.48"))

    # -- Tile pool (bucketed multi-tile scheduler) ----------------------------
    # Table V sweep only: us_per_call is sweep wall-clock per program, and
    # the counters are the snapshot taken right after that sweep
    (programs_n, dispatches_n, compiles_n, buckets_n, pad_waste_n,
     bytes_moved_n) = sweep_stats
    lines.append(("nmc_tile_pool", sweep_wall_s * 1e6 / max(programs_n, 1),
                  f"programs={programs_n},dispatches={dispatches_n},"
                  f"compiles={compiles_n},buckets={buckets_n},"
                  f"pad_waste={pad_waste_n},bytes_moved={bytes_moved_n}"))

    # -- Resident tile array (memory-mode / compute-mode duality) -------------
    # Load two tiles once, then dispatch the same programs twice: the second
    # compute-mode dispatch must move only instruction bytes (no tile-memory
    # re-upload) and hit the already-traced bucket (no new compile).
    kb8 = programs.build("xor", 8, caesar_bytes=2048, carus_bytes=4096)
    rpool = ResidentPool()
    t0 = time.perf_counter()
    first = rpool.run_builds([kb8.caesar, kb8.carus])
    moved_after_load = rpool.bytes_moved
    compiles_after_load = rpool.compiles
    rpool.dispatch([(t, eb.program) for t, eb in
                    zip(rpool.tiles, (kb8.caesar, kb8.carus))])
    resident_wall_s = time.perf_counter() - t0
    instr_bytes = rpool.bytes_moved - moved_after_load
    assert rpool.compiles == compiles_after_load, "re-dispatch retraced"
    state_bytes = sum(int(rpool.state(t).size) * 4 for t in rpool.tiles)
    assert instr_bytes < state_bytes, (instr_bytes, state_bytes)
    ok_first = all((got.reshape(-1)[: eb.oracle.size]
                    == eb.oracle.reshape(-1)).all()
                   for got, eb in zip(first, (kb8.caesar, kb8.carus)))
    lines.append(("nmc_resident_pool", resident_wall_s * 1e6 / 4,
                  f"bitexact={ok_first},redispatch_bytes={instr_bytes},"
                  f"tile_state_bytes={state_bytes},"
                  f"compiles={rpool.compiles}"))

    # -- Async double-buffered dispatch runtime (DESIGN.md §5.2) ------------
    # A 2-tile array continuously fed with a heterogeneous kernel stream:
    # images stage into shadow buffers while the previous programs run
    # (staged_while_busy > 0), results resolve through futures, and the
    # outputs must be bit-exact vs the synchronous ResidentPool path.
    from repro.nmc import DispatchQueue
    small = dict(caesar_bytes=2048, carus_bytes=4096)
    akbs = [programs.build(n, 8, **small)
            for n in ("xor", "add", "mul", "relu")]
    abuilds = [getattr(kb, e) for kb in akbs for e in ("caesar", "carus")]
    queue = DispatchQueue()
    queue.run_builds(abuilds, n_tiles=2)    # warm-up: trace the buckets
    # snapshot after warm-up: the derived counters below cover the timed
    # run only (same discipline as the nmc_tile_pool sweep_stats above)
    waves0, staged0 = queue.waves, queue.staged_while_busy
    t0 = time.perf_counter()
    async_out = queue.run_builds(abuilds, n_tiles=2)
    async_wall_s = time.perf_counter() - t0
    # the sync reference shares the queue's jit cache: same traces, no
    # recompiles — the comparison isolates the dispatch discipline
    sync_ref = ResidentPool(pool=queue.pool.pool).run_builds(abuilds)
    async_ok = all((np.asarray(a) == np.asarray(b)).all()
                   for a, b in zip(sync_ref, async_out))
    assert async_ok, "async futures diverged from synchronous dispatch"
    assert queue.staged_while_busy > 0, "no double-buffered staging happened"
    # modeled dispatch cost: overlapped-DMA mode must beat the serial mode
    # on the matmul sweep (strictly) and never exceed it
    mm_stages = [timing.stage_cost(getattr(programs.build("matmul", s), e))
                 for s in (8, 16, 32) for e in ("caesar", "carus")]
    ser = timing.dispatch_cycles(mm_stages, "serial")
    ovl = timing.dispatch_cycles(mm_stages, "overlapped")
    assert ovl < ser, (ovl, ser)
    lines.append(("nmc_async_dispatch", async_wall_s * 1e6 / len(abuilds),
                  f"bitexact={async_ok},waves={queue.waves - waves0},"
                  f"staged_while_busy={queue.staged_while_busy - staged0},"
                  f"matmul_overlap_cycle_ratio={ovl / ser:.3f}"))

    # -- Tile-parallel partitioned execution (DESIGN.md §9) -------------------
    # One kernel sharded across the tile array: scaling.run asserts
    # bit-exactness of every partitioned execution (sync + async gathers)
    # vs the single-tile output, the compile bound (pre-padded waves land
    # in one bucket each), and the wave-speedup shape of the shared-bus
    # timing model (monotone to the peak, > 1 at tiles=4 on matmul).
    from benchmarks import scaling
    t0 = time.perf_counter()
    rows_sc = scaling.run(smoke=True) if smoke else scaling.run(
        tiles=(1, 2, 4, 8), sews=(8,),
        kernels=("mul", "matmul", "conv2d"))
    scaling_wall_s = time.perf_counter() - t0
    sc = rows_sc[-1]
    n_cfg = len(rows_sc) - 1
    lines.append(("nmc_scaling", scaling_wall_s * 1e6 / max(n_cfg, 1),
                  f"bitexact=True,configs={n_cfg},"
                  f"compiles={sc['compiles']},buckets={sc['buckets']},"
                  f"matmul_speedup_at4={sc['matmul_speedup_at_4']:.2f}"))

    # -- Wave-scheduler autotuning (DESIGN.md §14) ----------------------------
    # tune_bench.run sweeps the scheduler's (strategy x chunk skew x
    # engine assignment x dispatch order) search: every tuned plan must
    # stay bit-exact vs the uniform plan (sync + async), matmul/conv2d
    # must win >= 5% modeled wave cycles at tiles in {4, 8}, and the
    # heterogeneous qrelu tape must ride one genuinely mixed
    # Caesar+Carus launch wave.
    from benchmarks import tune_bench
    t0 = time.perf_counter()
    rows_tn, mixed = tune_bench.run(sew=8, smoke=smoke)
    tune_wall_s = time.perf_counter() - t0
    fails = tune_bench.gate_failures(rows_tn, mixed, tune_bench.BOUND_PCT)
    assert not fails, "tune gate: " + "; ".join(fails)
    best_tn = max(r["win_vs_uniform_pct"] for r in rows_tn)
    lines.append(("nmc_tune", tune_wall_s * 1e6 / max(len(rows_tn), 1),
                  f"bitexact=True,best_win_pct={best_tn:.2f},"
                  f"mixed_engines={'+'.join(sorted(set(mixed['engines'])))},"
                  f"mixed_one_launch={mixed['one_launch']}"))

    if not smoke:
        # -- Table VI -------------------------------------------------------
        ok = table_vi.functional_demo()
        rows_vi = table_vi.run()
        carus_row = next(r for r in rows_vi if r["config"] == "carus_e20")
        lines.append(("table_vi_anomaly_carus",
                      carus_row["model_cycles"] / C.F_CLK_BENCH_HZ * 1e6,
                      f"functional={'bitexact' if ok else 'FAIL'},"
                      f"cycle_factor={carus_row['model_cycle_factor']:.2f}"
                      f"_vs_paper_{carus_row['paper_cycle_factor']}"))

        # -- Table VIII -------------------------------------------------------
        rows_viii = table_viii.run()
        pk = table_viii.peak_efficiency_gops_w()
        lines.append(("table_viii_matmul8_carus",
                      rows_viii[0]["carus_cycles"] / C.F_CLK_BENCH_HZ * 1e6,
                      f"pj_per_mac={rows_viii[0]['carus_pj_mac']:.1f}"
                      f"_paper_{rows_viii[0]['carus_pj_mac_paper']}"))
        lines.append(("table_vii_peak_gops_w", 0.0,
                      f"model={pk['model_gops_w']:.1f}_paper="
                      f"{pk['paper_gops_w']}"))

        # -- Fig 13 -----------------------------------------------------------
        from benchmarks import fig13
        bd = fig13.run(8)
        vrf_frac = bd["carus"]["vrf"] / sum(bd["carus"].values())
        lines.append(("fig13_power_breakdown", 0.0,
                      f"carus_vrf_share={vrf_frac:.2f}_paper_~0.6"))

        # -- Roofline (reads dry-run artifacts if present) --------------------
        try:
            from benchmarks import roofline
            rows_rf = roofline.main(out_csv="results/roofline.csv") \
                if os.path.isdir("results/dryrun") else []
            if rows_rf:
                worst = min((r for r in rows_rf if r["shape"] == "train_4k"),
                            key=lambda r: r["mfu_bound"])
                lines.append(("roofline_cells", 0.0,
                              f"n={len(rows_rf)},worst_train_mfu_bound="
                              f"{worst['mfu_bound']:.3f}@{worst['arch']}"))
        except Exception as e:  # roofline needs dry-run artifacts
            lines.append(("roofline_cells", 0.0, f"skipped:{type(e).__name__}"))

    print("\n" + "=" * 60)
    print("name,us_per_call,derived")
    for name, us, derived in lines:
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    if __package__ in (None, ""):
        # direct-script invocation (`python benchmarks/run.py`): bootstrap
        # the same import roots `python -m benchmarks.run` gets from the
        # repo root + pyproject; a no-op under `-m` or an installed package.
        _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, _root)
        sys.path.insert(0, os.path.join(_root, "src"))
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced CI subset (Table V @ sew=8 + Fig 12)")
    main(smoke=ap.parse_args().smoke)
