"""Backend wall-clock comparison: scan interpreters vs the Pallas fast path.

The tentpole claim of DESIGN.md §10 measured: the same bucketed Program
waves executed by the ``lax.scan`` reference interpreters and by the fused
``pl.pallas_call`` backend (interpret mode on CPU, native kernels on
TPU), over the matmul / conv2d / elementwise traced builders from
``benchmarks/scaling.py`` and the tiles∈{1..16} scaling sweep.

Two numbers per (kernel, tiles, backend) configuration:

* ``dispatch_us`` — pre-lowered programs resubmitted through the shared
  :class:`repro.nmc.runtime.DispatchQueue`: the pure engine-execution
  path (what the backend changes).  The ``--smoke`` gate asserts
  Pallas <= scan on the matmul builder here.
* ``e2e_us``    — full ``CompiledKernel.__call__`` wall-clock including
  per-call tracing/lowering (backend-independent Python work), for
  context on how much of the end-to-end budget the engine is.

Every configuration is also cross-checked bit-exact between the two
backends before it is timed.  Results append to ``BENCH_backends.json``
(one entry per run — the trajectory CI uploads as an artifact).

Run from the repo root: ``PYTHONPATH=src python -m benchmarks.backend_bench``
(``--smoke`` for the reduced CI gate).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

SWEEP_TILES = (1, 2, 4, 8, 16)
SMOKE_TILES = (1, 4)
BACKENDS = ("scan", "pallas")


def _time_calls(fn, repeats: int) -> float:
    """Best-of-N wall-clock of ``fn()`` in microseconds (post warm-up)."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def bench_config(kern, args, tiles: int, backend: str, repeats: int):
    """Returns ``(dispatch_us, e2e_us, outputs)`` for one configuration.

    The dispatch loop resubmits pre-lowered shard programs through the
    kernel's runtime queue — same tiles, same jit cache, same padded
    buckets as a real call — isolating executor time from trace time.
    """
    import numpy as np

    rt = kern.runtime
    if tiles == 1:
        lks = [kern.lower(*args)]
        ids = [rt.jit_tile]
    else:
        _, lks = kern.lower_wave(*args, tiles=tiles)
        ids = rt.jit_tiles(len(lks))

    def dispatch_once():
        futs = [rt.queue.submit(t, lk.program, image=lk.mem,
                                out_slice=lk.out_slice, post=lk.post,
                                backend=backend)
                for t, lk in zip(ids, lks)]
        return [np.asarray(f.result()) for f in futs]

    out = dispatch_once()                       # warm-up: compile the bucket
    dispatch_us = _time_calls(dispatch_once, repeats)
    kern(*args, tiles=tiles, backend=backend)   # warm e2e (same cache)
    e2e_us = _time_calls(
        lambda: kern(*args, tiles=tiles, backend=backend), repeats)
    return dispatch_us, e2e_us, out


def run(kernels=("mul", "matmul", "conv2d"), tiles_sweep=SWEEP_TILES,
        sew: int = 8, repeats: int = 5, smoke: bool = False) -> list[dict]:
    import numpy as np
    from repro import nmc
    from benchmarks.scaling import make_kernels

    built = make_kernels(sew, names=kernels)
    rows: list[dict] = []
    for name, (kfn, args, _post) in built.items():
        # one runtime per kernel family: both backends share its bucketed
        # jit cache (keys differ per backend) and its resident tile set
        rt = nmc.NmcRuntime()
        kern = nmc.jit(kfn, sew=sew, runtime=rt)
        engine = kern.select_engine(*args)
        for tiles in tiles_sweep:
            try:
                cfg = {}
                for backend in BACKENDS:
                    dispatch_us, e2e_us, out = bench_config(
                        kern, args, tiles, backend, repeats)
                    cfg[backend] = (dispatch_us, e2e_us, out)
            except nmc.PartitionError as e:
                print(f"# skip {name} tiles={tiles}: {e}")
                continue
            a, b = cfg["scan"][2], cfg["pallas"][2]
            exact = all((x == y).all() for x, y in zip(a, b))
            assert exact, f"{name} tiles={tiles}: backends diverged"
            for backend in BACKENDS:
                dispatch_us, e2e_us, _ = cfg[backend]
                rows.append({"kernel": name, "engine": engine,
                             "backend": backend, "tiles": tiles, "sew": sew,
                             "dispatch_us": round(dispatch_us, 2),
                             "e2e_us": round(e2e_us, 2), "bitexact": exact})
    if smoke:
        # the CI gate: the fused fast path must not lose to the scan
        # interpreter on the matmul builder (pure dispatch wall-clock)
        mm = {r["backend"]: r["dispatch_us"] for r in rows
              if r["kernel"] == "matmul" and r["tiles"] == 1}
        assert mm["pallas"] <= mm["scan"], \
            f"Pallas slower than scan on matmul: {mm}"
    return rows


def main(smoke: bool = False, out_json: str = "BENCH_backends.json") -> None:
    import jax

    t0 = time.perf_counter()
    if smoke:
        rows = run(kernels=("mul", "matmul"), tiles_sweep=SMOKE_TILES,
                   repeats=2, smoke=True)
    else:
        rows = run(smoke=False)
    wall_s = time.perf_counter() - t0

    by_cfg: dict = {}
    for r in rows:
        by_cfg.setdefault((r["kernel"], r["tiles"]), {})[r["backend"]] = r
    print("\n" + "=" * 60)
    print("name,us_per_call,derived")
    for (name, tiles), cfg in sorted(by_cfg.items()):
        s, p = cfg["scan"], cfg["pallas"]
        speedup = s["dispatch_us"] / max(p["dispatch_us"], 1e-9)
        print(f"backend_{name}_t{tiles},{p['dispatch_us']:.2f},"
              f"scan_us={s['dispatch_us']:.2f},"
              f"pallas_us={p['dispatch_us']:.2f},"
              f"speedup={speedup:.2f},bitexact={p['bitexact']}")

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
                    help="reduced CI gate (mul+matmul, tiles 1/4, asserts "
                         "Pallas <= scan on matmul)")
    ap.add_argument("--out", default="BENCH_backends.json",
                    help="JSON trajectory path")
    a = ap.parse_args()
    main(smoke=a.smoke, out_json=a.out)
