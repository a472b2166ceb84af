"""Serving-scale sweep: tokens/s vs concurrent users on the tile array.

The resident-block serving claim of DESIGN.md §12 measured: one W8A8
decoder block (qwen1.5-0.5b smoke shapes) kept resident on the NMC tile
array via :class:`repro.serve.block.ResidentBlock`, swept over
``users x nmc_tiles`` — ``users`` concurrent decode rows advance one token
per block step, sharded ``tiles``-wide per projection.

Per configuration:

* **bit-exactness** — three chained steps of the resident path compared
  bit-for-bit against the per-projection
  :meth:`repro.serve.engine.ServeEngine.nmc_project` path and the pure-JAX
  int32 matmul reference (asserted, not just reported);
* **residency** — :class:`repro.nmc.pool.ResidentPool` counters prove the
  quantized weights DMA once (``loads == n_shards`` after the first step,
  unchanged after; later steps add exactly ``patch_bytes_per_call``);
* **modeled throughput** — steady-state block-step cycles through
  :func:`repro.core.timing.chained_wave_cycles` at the paper's benchmark
  clock: ``tok/s = users * F_CLK_BENCH_HZ / steady_cycles``.

Results append to ``BENCH_serving.json`` (one entry per run — the
trajectory CI uploads as an artifact).

Run from the repo root: ``PYTHONPATH=src python -m benchmarks.serving``
(``--smoke`` for the reduced CI gate).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

SWEEP_USERS = (1, 2, 4, 8)
SWEEP_TILES = (2, 4)
# at the qwen smoke shapes (d_ff=128) the MLP up/gate shards outgrow a
# tile's bank at tiles=2 once users>=4, so the smoke curve runs at tiles=4
SMOKE_USERS = (1, 4)
SMOKE_TILES = (4,)
VERIFY_STEPS = 3


def _bench_config(cfg, qparams, users: int, tiles: int) -> dict:
    """One (users, tiles) point: build the resident block on a private
    queue, verify three-way bit-exactness and residency, model tokens/s."""
    import numpy as np
    from repro import nmc
    from repro.core import constants as C
    from repro.serve.engine import ServeEngine

    own = nmc.DispatchQueue(pool=nmc.ResidentPool(
        pool=nmc.default_runtime().bucketed))
    eng = ServeEngine(cfg, qparams, n_slots=users, max_len=32,
                      nmc_queue=own, nmc_tiles=tiles)
    blk = eng.resident_block(layer=0, rows=users, tiles=tiles)
    rng = np.random.default_rng(7)
    x0 = rng.normal(size=(users, cfg.d_model)).astype(np.float32)

    def chain(mm):
        x, st = x0.copy(), blk.init_state(16)
        outs = []
        for _ in range(VERIFY_STEPS):
            x, st = blk.step(x, st, mm=mm)
            outs.append(x.copy())
        return outs

    # resident chain first, under the residency counters
    out_res = chain(None)
    assert blk.static, "value-independence proof failed"
    assert own.pool.loads == blk.n_shards, \
        (own.pool.loads, blk.n_shards)
    loads0, pb0 = own.pool.loads, own.pool.patch_bytes
    t0 = time.perf_counter()
    extra = chain(None)                        # steady-state wall clock
    wall_us = (time.perf_counter() - t0) / VERIFY_STEPS * 1e6
    assert own.pool.loads == loads0, "weights re-crossed the bus"
    assert own.pool.patch_bytes - pb0 \
        == VERIFY_STEPS * blk.patch_bytes_per_call
    assert all(np.array_equal(a, b) for a, b in zip(out_res, extra)), \
        "resident path not deterministic"
    # comparison chains (these reload per projection — after the asserts)
    out_jax = chain(blk.jax_mm)
    out_prj = chain(blk.project_mm(eng))
    exact = all(np.array_equal(a, b) for a, b in zip(out_res, out_jax)) \
        and all(np.array_equal(a, b) for a, b in zip(out_prj, out_jax))
    assert exact, f"users={users} tiles={tiles}: backends diverged"

    steady = blk.step_cycles(steady=True)
    cold = blk.step_cycles(steady=False)
    assert steady < cold, (steady, cold)
    return {"users": users, "tiles": tiles, "n_shards": blk.n_shards,
            "steady_cycles": round(steady, 1), "cold_cycles": round(cold, 1),
            "tok_s": round(users * C.F_CLK_BENCH_HZ / steady, 1),
            "patch_kb_per_step": round(blk.patch_bytes_per_call / 1024, 3),
            "wall_us_per_step": round(wall_us, 1),
            "bitexact": bool(exact), "resident": True}


def run(users_sweep=SWEEP_USERS, tiles_sweep=SWEEP_TILES,
        smoke: bool = False) -> list[dict]:
    import jax
    from repro import nmc
    from repro.configs import base as cb
    from repro.models import lm
    from repro.serve.engine import quantize_params

    cfg = cb.get("qwen1.5-0.5b", smoke=True).scaled(nmc_mode="w8a8")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_params(params, cfg)
    rows: list[dict] = []
    for tiles in tiles_sweep:
        for users in users_sweep:
            try:
                rows.append(_bench_config(cfg, qparams, users, tiles))
            except (nmc.PartitionError, nmc.LoweringError) as e:
                # a shard that outgrows a tile's SRAM macro at this width
                # is a capacity fact, not a failure — report the skip
                print(f"# skip users={users} tiles={tiles}: {e}")
    assert rows, "every configuration skipped — sweep is vacuous"
    if smoke:
        assert all(r["bitexact"] and r["resident"] for r in rows), rows
    return rows


def main(smoke: bool = False, out_json: str = "BENCH_serving.json") -> None:
    import jax

    t0 = time.perf_counter()
    rows = run(users_sweep=SMOKE_USERS if smoke else SWEEP_USERS,
               tiles_sweep=SMOKE_TILES if smoke else SWEEP_TILES,
               smoke=smoke)
    wall_s = time.perf_counter() - t0

    print("\n" + "=" * 60)
    print("name,us_per_call,derived")
    for r in rows:
        print(f"serving_u{r['users']}_t{r['tiles']},"
              f"{r['wall_us_per_step']:.1f},"
              f"tok_s={r['tok_s']:.1f},"
              f"steady_cyc={r['steady_cycles']:.0f},"
              f"cold_cyc={r['cold_cycles']:.0f},"
              f"patch_kb={r['patch_kb_per_step']},"
              f"bitexact={r['bitexact']},resident={r['resident']}")

    entry = {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
             "device": jax.default_backend(), "smoke": smoke,
             "wall_s": round(wall_s, 2), "rows": rows}
    history = []
    if os.path.exists(out_json):
        try:
            with open(out_json) as f:
                history = json.load(f)
        except (json.JSONDecodeError, OSError):
            history = []
    history.append(entry)
    with open(out_json, "w") as f:
        json.dump(history, f, indent=1)
    print(f"# wrote {out_json} ({len(history)} run(s))")


if __name__ == "__main__":
    if __package__ in (None, ""):
        _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, _root)
        sys.path.insert(0, os.path.join(_root, "src"))
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced CI gate (users 1/4, tiles 4; asserts "
                         "bit-exactness and residency per configuration)")
    ap.add_argument("--out", default="BENCH_serving.json",
                    help="JSON trajectory path")
    a = ap.parse_args()
    main(smoke=a.smoke, out_json=a.out)
