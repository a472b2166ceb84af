"""Static-verifier overhead benchmark (DESIGN.md §11 acceptance gate).

``nmc.jit(fn, check="error")`` — the default — verifies every lowering.
The verifier is numpy-vectorized (one in-place event-key sort, no
per-instruction Python loop) and memoizes the verdict on a content
fingerprint of the lowered program, so repeated lowerings of the same
kernel/signature pay one 64 KiB hash, not the pass pipeline.  This
benchmark measures both regimes: paired, interleaved
``lower(check="off")`` vs ``lower(check="error")`` timings over the
quickstart-style kernels on both engines give the steady-state overhead
(paired medians cancel machine drift, which on shared CI runners dwarfs
the effect being measured), and a ``clear_memo()``-per-iteration loop
gives the cold verify cost per configuration.

Results append to ``BENCH_check.json``; ``--assert`` enforces the
acceptance gate.  The gate is dual-bound: the relative bound (default
5%) applies to configurations whose baseline lowering takes at least
``REL_FLOOR_MS`` — the quickstart path (engine auto-selection picks
NM-Caesar, whose per-word bus programs run thousands of instructions
through the verifier).  NM-Carus lowers the same kernels to a handful
of vector instructions in ~0.2 ms, so a percentage there only measures
the verifier's fixed numpy dispatch floor; those configurations are
instead held to an absolute ceiling of ``ABS_BOUND_MS`` added latency.

Run from the repo root: ``PYTHONPATH=src python -m benchmarks.check_bench``.
"""

from __future__ import annotations

import argparse
import json
import os
import time

BOUND_PCT = 5.0     # relative bound for substantial lowerings
REL_FLOOR_MS = 1.0  # below this baseline, a percentage is meaningless
ABS_BOUND_MS = 0.6  # absolute added-latency ceiling for tiny lowerings
OUT_JSON = os.path.join(os.path.dirname(__file__), "BENCH_check.json")


def _paired_overhead(kern, args, engine: str, pairs: int) -> dict:
    """Interleaved off/error lowering timings -> median paired stats."""
    for _ in range(3):  # warm both paths (imports, caches)
        kern.lower(*args, engine=engine, check="off")
        kern.lower(*args, engine=engine, check="error")
    offs, deltas = [], []
    for _ in range(pairs):
        t0 = time.perf_counter()
        kern.lower(*args, engine=engine, check="off")
        t1 = time.perf_counter()
        kern.lower(*args, engine=engine, check="error")
        t2 = time.perf_counter()
        offs.append(t1 - t0)
        deltas.append((t2 - t1) - (t1 - t0))
    # median of the per-pair deltas: each delta is taken under the same
    # instantaneous machine load, so drift cancels where independently
    # sorted medians would not
    offs.sort()
    deltas.sort()
    off_ms = offs[len(offs) // 2] * 1e3
    delta_ms = deltas[len(deltas) // 2] * 1e3

    from repro.nmc import check
    lk = kern.lower(*args, engine=engine, check="off")
    colds = []
    for _ in range(max(pairs // 2, 10)):
        check.clear_memo()
        t0 = time.perf_counter()
        check.verify_lowered(lk)
        colds.append(time.perf_counter() - t0)
    colds.sort()
    return {"off_ms": round(off_ms, 4),
            "error_ms": round(off_ms + delta_ms, 4),
            "delta_ms": round(delta_ms, 4),
            "overhead_pct": round(100.0 * delta_ms / off_ms, 2),
            "cold_verify_ms": round(colds[len(colds) // 2] * 1e3, 4)}


def main() -> int:
    ap = argparse.ArgumentParser(
        description="lowering-time overhead of check='error' vs 'off'")
    ap.add_argument("--pairs", type=int, default=40,
                    help="interleaved off/error timing pairs per config")
    ap.add_argument("--n", type=int, default=4096,
                    help="elements per input vector")
    ap.add_argument("--assert", dest="enforce", action="store_true",
                    help=f"fail if any config with a >= {REL_FLOOR_MS} ms "
                         f"baseline exceeds {BOUND_PCT}%% overhead, or any "
                         f"smaller one adds > {ABS_BOUND_MS} ms")
    ap.add_argument("--bound", type=float, default=BOUND_PCT,
                    help="relative overhead bound in percent for --assert")
    ap.add_argument("--abs-bound", type=float, default=ABS_BOUND_MS,
                    help="absolute delta bound in ms for sub-floor configs")
    args = ap.parse_args()

    import numpy as np
    from repro import nmc

    @nmc.kernel
    def fused(t, x, y):
        t.store((t.load(x) * 3 + t.load(y)).max(0))

    @nmc.kernel
    def scaled(t, x):
        t.store(t.load(x) * 3 + 1)

    rng = np.random.default_rng(0)
    xs = rng.integers(-100, 100, args.n).astype(np.int8)
    ys = rng.integers(-100, 100, args.n).astype(np.int8)

    configs = [("fused", fused, (xs, ys), "caesar"),
               ("fused", fused, (xs, ys), "carus"),
               ("scaled", scaled, (xs,), "caesar"),
               ("scaled", scaled, (xs,), "carus")]
    results = []
    print(f"{'kernel':<8} {'engine':<7} {'off ms':>9} {'error ms':>9} "
          f"{'overhead':>9} {'cold ms':>8}")
    for name, kern, kargs, engine in configs:
        r = _paired_overhead(kern, kargs, engine, args.pairs)
        r.update(kernel=name, engine=engine, n=args.n)
        results.append(r)
        print(f"{name:<8} {engine:<7} {r['off_ms']:>9.3f} "
              f"{r['error_ms']:>9.3f} {r['overhead_pct']:>8.2f}% "
              f"{r['cold_verify_ms']:>8.3f}")

    history = []
    if os.path.exists(OUT_JSON):
        with open(OUT_JSON) as f:
            history = json.load(f)
    history.append({"ts": time.time(), "results": results})
    with open(OUT_JSON, "w") as f:
        json.dump(history, f, indent=1)
    print(f"results appended to {OUT_JSON}")

    failures = []
    for r in results:
        tag = f"{r['kernel']}/{r['engine']}"
        if r["off_ms"] >= REL_FLOOR_MS:
            if r["overhead_pct"] > args.bound:
                failures.append(f"{tag}: {r['overhead_pct']:.2f}% "
                                f"> {args.bound:.1f}% relative bound")
        elif r["delta_ms"] > args.abs_bound:
            failures.append(f"{tag}: +{r['delta_ms']:.3f} ms "
                            f"> {args.abs_bound:.2f} ms absolute bound")
    rel = [r["overhead_pct"] for r in results if r["off_ms"] >= REL_FLOOR_MS]
    if rel:
        print(f"worst relative overhead (baselines >= {REL_FLOOR_MS} ms): "
              f"{max(rel):.2f}% (bound {args.bound:.1f}%)")
    if failures:
        print("gate:", "FAIL" if args.enforce else "would fail (no --assert)")
        for line in failures:
            print(" ", line)
        return 1 if args.enforce else 0
    print("gate: PASS")
    return 0


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
