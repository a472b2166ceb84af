"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics
are found by name (see ``bench/harness.py``).  With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` the
first part of the window runs under the JAX profiler and the result
carries the per-layer metrics, the device's busy and window seconds and
a breakdown of device time and idle gaps.

The last lines on standard error, and the ``compared`` key that comes
last in the result line, give every number the correctness check
compared with its limit.  The run fails, and prints no result, where JAX
finds no accelerator or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def finite(x):
    """JSON-safe numbers: a reading that is not finite becomes 1e300."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return 1e300
    return x


class Context:
    """What a per-layer reader reads: the reduced trace, the driver's
    facts (counts, host-timed spans), the configuration and the peaks."""

    def __init__(self, trace, facts, cfg, traffic, peaks, harness):
        self.trace = trace
        self.facts = facts
        self.cfg = cfg
        self.traffic = traffic
        self.peaks = peaks
        self.harness = harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness as H

    bm = H.load_benchmark()
    cell, centry = H.find_cell(bm, args.workload)
    try:
        devices = H.accelerator(int(cell["chips"]))
    except RuntimeError as e:
        H.eprint(f"bench: {e}")
        return 2
    dev = devices[0]
    peaks = H.load_peaks(dev.device_kind)
    cache = H.enable_compile_cache()
    cfg = H.load_config(centry)
    traffic = H.load_traffic(cell["traffic"])
    driver = H.load_driver(traffic["driver"])

    trace_dir = H.make_trace_dir() if args.trace else None
    trace_s = min(args.seconds, float(traffic.get("trace_seconds",
                                                  args.seconds)))
    h = H.Harness(args.seconds, T_START,
                  trace_s=trace_s if args.trace else 0.0,
                  trace_dir=trace_dir, devices=devices)
    H.eprint(f"bench: {args.workload} seed {args.seed} on {dev.platform} "
             f"{dev.device_kind} x{len(devices)}; compile cache {cache}")
    res = driver.run(h, cfg, traffic, args.seed)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": h.memory_peak}
    out = {"correct": res.correct, "attempted": res.attempted,
           "failed": res.failed}
    if args.trace:
        from bench import trace_reduce
        summary = trace_reduce.reduce_dir(trace_dir, set(h.spans),
                                          n_devices=len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = Context(summary, res.facts, cfg, traffic, peaks, h)
        out["metrics"] = H.run_metric_readers(
            H.metrics_of(bm, args.workload, "per_layer"), ctx)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["device"] = device
        out["breakdown"] = summary.breakdown()
    else:
        values = dict(res.end_to_end, setup_s=h.setup_s)
        out["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in H.metrics_of(bm, args.workload, "end_to_end")}
        out["device"] = device
    out["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                       for c in res.compared}

    for line in res.notes:
        H.eprint(f"bench: {line}")
    H.eprint(f"bench: set-up {h.setup_s!r} s, window {h.window_s!r} s, "
             f"{h.window_compiles} compiles inside the window, "
             f"{h.compiles.n} compiles and {h.compiles.hits} cache hits "
             f"in all")
    for c in res.compared:
        H.eprint(f"compared: {c.name} {c.value!r} limit {c.limit!r} "
                 f"{'ok' if c.ok else 'FAIL'}")
    print(json.dumps(finite(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
