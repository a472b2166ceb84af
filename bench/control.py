"""Readings that the correctness limits are set from: the program's and the
control's, over many seeds, in one process.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed the cell's driver runs a short window at the cell's own
load and sizes; then the number the check compares is read for the
program and for the control put in its place:

* serving cells: the widest reference gap (in standard deviations of the
  reference's logits) of the served tokens, and of the tokens that the
  4-bit reference puts first at the same positions of the same prompts;
* NMC cells: mismatched output elements of the program, and of the
  saturating reference, against the wrapping reference.

Prints one JSON line per seed.  The benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def serve_reading(cfg, traffic, seed, seconds):
    from bench import costs, harness as H
    from bench.drivers import serve_closed_loop as S

    params, engine = S.build(cfg, traffic, seed)
    h = H.Harness(seconds, time.perf_counter())
    loop = S.drive(h, engine, traffic, costs.dims(cfg).vocab, seed)
    e2e = S.end_to_end(loop, h)
    e2e["ttft_samples"] = len(loop.ttft)
    reqs = S.sample(loop.finished, traffic, seed)
    del engine, loop
    gc.collect()
    res = S.check(params, cfg, traffic, reqs, control=True)
    return {"program": res["max_gap"], "control": res["control_max_gap"],
            "tokens": res["tokens"],
            "median_top2_spacing": res["median_top2_spacing"],
            "end_to_end": e2e, "setup_s": h.setup_s}


def nmc_reading(cfg, traffic, seed, seconds, driver):
    from bench import harness as H
    from bench.drivers import nmc_waves
    from bench.ref import nmc as ref

    h = H.Harness(seconds, time.perf_counter())
    res = driver.run(h, cfg, traffic, seed)
    if traffic["driver"] == "nmc_waves":
        insts, _ = nmc_waves.build_library(cfg, traffic, seed)
    else:
        import numpy as np
        from bench.drivers import nmc_jit_calls, nmc_library as lib
        rng = np.random.default_rng(seed)
        insts = [lib.instance(k, s, sh, rng) for k, s, _, sh, _ in
                 nmc_jit_calls.kernels(traffic, None)]
    ctrl = sum(ref.mismatches(
        ref.reference(i.kind, i.args, i.sew, i.params, saturate=True),
        ref.reference(i.kind, i.args, i.sew, i.params)) for i in insts)
    return {"program": res.compared[0].value, "control": ctrl,
            "kernels": res.attempted, "end_to_end": res.end_to_end}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    from bench import harness as H

    bm = H.load_benchmark()
    cell, centry = H.find_cell(bm, args.workload)
    devs = H.accelerator(int(cell["chips"]))
    H.enable_compile_cache()
    cfg = H.load_config(centry)
    traffic = H.load_traffic(cell["traffic"])
    driver = H.load_driver(traffic["driver"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        if traffic["driver"] == "serve_closed_loop":
            r = serve_reading(cfg, traffic, seed, args.seconds)
        else:
            r = nmc_reading(cfg, traffic, seed, args.seconds, driver)
        r.update(workload=args.workload, seed=seed,
                 device=devs[0].device_kind,
                 seconds=time.perf_counter() - t)
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
