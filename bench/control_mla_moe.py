"""Readings that the correctness limit of a cell driven by
``serve_mla_moe`` is set from: the program's and the 4-bit control's over
many seeds, and the program's under planted faults, in one process.
Each reading is the widest and the mean reference gap.

    python bench/control_mla_moe.py --workload <cell> --seeds 1,2,3
    python bench/control_mla_moe.py --workload <cell> --seeds 1 --fault <f>

As ``bench/control.py`` does for ``serve_closed_loop``: for each seed the
driver runs a short window at the cell's own load and sizes, then the
widest reference gap (in standard deviations of the reference's logits)
of the served tokens is read, and of the tokens that the 4-bit reference
puts first at the same positions.  With ``--fault`` one of
:data:`FAULTS` is planted first: ``bench/control.py``'s, and
``experts_skipped``.  Prints one JSON line per seed.  The benchmark's own
runs never run this.
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

from bench import control  # noqa: E402


def experts_skipped(E):
    """The held experts' routed part set to zero: only the shared experts
    (and the leading dense layers) reach the residual stream."""
    import jax.numpy as jnp
    from repro.models import moe

    orig = moe.routed_part

    def routed_part(p, x, cfg):
        y, rows = orig(p, x, cfg)
        return jnp.zeros_like(y), rows
    return moe, "routed_part", routed_part


FAULTS = dict(control.FAULTS, experts_skipped=experts_skipped)


def serve_reading(cfg, traffic, seed, seconds, control=True):
    from bench import costs_mla_moe, harness as H
    from bench.drivers import serve_mla_moe as S

    params, engine = S.build(cfg, traffic, seed)
    h = H.Harness(seconds, time.perf_counter())
    loop = S.drive(h, engine, traffic, costs_mla_moe.dims(cfg).vocab, seed)
    e2e = S.end_to_end(loop, h)
    e2e["ttft_samples"] = len(loop.ttft)
    reqs = S.sample(loop.finished, traffic, seed)
    del engine, loop
    gc.collect()
    res = S.check(params, cfg, traffic, reqs, control=control)
    return {"program": res["max_gap"],
            "control": res.get("control_max_gap"),
            "program_mean": res["mean_gap"],
            "control_mean": res.get("control_mean_gap"),
            "tokens": res["tokens"],
            "median_top2_spacing": res["median_top2_spacing"],
            "end_to_end": e2e, "setup_s": h.setup_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--fault", choices=sorted(FAULTS),
                    help="plant this fault in the serving program")
    args = ap.parse_args(argv)

    from bench import harness as H

    bm = H.load_benchmark()
    cell, centry = H.find_cell(bm, args.workload)
    traffic = H.load_traffic(cell["traffic"])
    if traffic["driver"] != "serve_mla_moe":
        ap.error(f"{args.workload} is not driven by serve_mla_moe")
    devs = H.accelerator(int(cell["chips"]))
    H.enable_compile_cache()
    cfg = H.load_config(centry)
    if args.fault:
        from repro.serve import engine as E
        setattr(*FAULTS[args.fault](E))
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        r = serve_reading(cfg, traffic, seed, args.seconds,
                          control=not args.fault)
        r.update(workload=args.workload, seed=seed, fault=args.fault,
                 device=devs[0].device_kind,
                 seconds=time.perf_counter() - t)
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
