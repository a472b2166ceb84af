"""IR-optimizer benchmark (DESIGN.md §13 acceptance gate).

Sweeps registry kernels through ``repro.nmc.opt.optimize`` and records,
per target, the instruction count and modeled engine cycles before vs
after ``O1`` plus a functional bit-exactness verdict (the optimized
program re-executes on its real engine and must reproduce the registry
oracle).  The optimizer's own translation-validation gate already ran
inside ``optimize`` — this benchmark demonstrates the *win* and
re-checks the *safety* end to end.

Results append to ``BENCH_opt.json``; ``--assert`` enforces the gate:
every target bit-exact, and at least one registry kernel at least
``BOUND_PCT``% cheaper in modeled cycles (the paper's GEMM epilogue
constants sit in the accumulator bank, so bank-aware placement wins
~10% there; the naive ``axpy`` builder wins on both engines).

Run from the repo root: ``PYTHONPATH=src python -m benchmarks.opt_bench``.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import time

BOUND_PCT = 5.0     # >= one registry kernel must win this much
OUT_JSON = os.path.join(os.path.dirname(__file__), "BENCH_opt.json")

#: Registry targets with reclaimable slack plus a no-slack control group
#: (the optimizer must be a provable no-op there, not a small regression).
TARGETS = (("gemm", "caesar"), ("axpy", "caesar"), ("axpy", "carus"),
           ("xor", "caesar"), ("relu", "carus"))


def _measure(name: str, engine: str, sew: int) -> dict:
    import numpy as np
    from repro.core import programs, timing
    from repro.nmc import opt

    kb = programs.build(name, sew)
    eb = getattr(kb, engine)
    lk = copy.deepcopy(eb.lowered)      # registry stays opt="off" pristine
    before_c = timing.program_cycles(lk.program).cycles
    before_n = lk.program.n_instr
    t0 = time.perf_counter()
    rep = opt.optimize(lk, "O1")
    opt_ms = (time.perf_counter() - t0) * 1e3
    after_c = timing.program_cycles(lk.program).cycles
    after_n = lk.program.n_instr
    # end-to-end safety: the optimized program on the real engine must
    # reproduce the registry oracle bit-exactly
    from repro.nmc.engine import get_engine
    eng = get_engine(lk.engine)
    final = eng.run(eng.init_state(lk.mem), lk.program)
    got = lk.post(eng.extract(final, lk.out_slice, lk.sew))
    bitexact = bool(np.array_equal(np.asarray(got), eb.oracle))
    return {"kernel": name, "engine": engine, "sew": sew,
            "n_instr_before": int(before_n), "n_instr_after": int(after_n),
            "cycles_before": float(before_c), "cycles_after": float(after_c),
            "cycle_reduction_pct":
                round(100.0 * (before_c - after_c) / before_c, 2),
            "rules": [r.rule for r in rep.rewrites] if rep else [],
            "validated": rep.validated if rep else 0,
            "opt_ms": round(opt_ms, 3), "bitexact": bitexact}


def main() -> int:
    ap = argparse.ArgumentParser(
        description="instrs/cycles before vs after opt='O1' on registry "
                    "kernels, with end-to-end bit-exactness")
    ap.add_argument("--sew", type=int, default=8,
                    help="element width for the sweep")
    ap.add_argument("--assert", dest="enforce", action="store_true",
                    help=f"fail unless every target is bit-exact and at "
                         f"least one registry kernel wins >= {BOUND_PCT}%% "
                         f"modeled cycles")
    ap.add_argument("--bound", type=float, default=BOUND_PCT,
                    help="required best-case cycle reduction in percent")
    args = ap.parse_args()

    results = [_measure(name, engine, args.sew)
               for name, engine in TARGETS]

    print(f"{'kernel':<8} {'engine':<7} {'instrs':>13} {'cycles':>17} "
          f"{'win':>7}  {'rules':<28} exact")
    for r in results:
        print(f"{r['kernel']:<8} {r['engine']:<7} "
              f"{r['n_instr_before']:>6}->{r['n_instr_after']:<6} "
              f"{r['cycles_before']:>8.0f}->{r['cycles_after']:<8.0f} "
              f"{r['cycle_reduction_pct']:>6.2f}%  "
              f"{','.join(r['rules']) or '-':<28} {r['bitexact']}")

    history = []
    if os.path.exists(OUT_JSON):
        with open(OUT_JSON) as f:
            history = json.load(f)
    history.append({"ts": time.time(), "sew": args.sew, "results": results})
    with open(OUT_JSON, "w") as f:
        json.dump(history, f, indent=1)
    print(f"results appended to {OUT_JSON}")

    failures = [f"{r['kernel']}/{r['engine']}: not bit-exact"
                for r in results if not r["bitexact"]]
    best = max(r["cycle_reduction_pct"] for r in results)
    if best < args.bound:
        failures.append(f"best cycle reduction {best:.2f}% "
                        f"< {args.bound}% bound")
    if args.enforce and failures:
        print("OPT BENCH GATE FAILED:\n  " + "\n  ".join(failures))
        return 1
    if failures:
        print("(informational) " + "; ".join(failures))
    print(f"gate: best win {best:.2f}% (bound {args.bound}%), "
          f"{len(results)} targets bit-exact")
    return 0


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
