"""From a JAX profiler trace to busy time, kernel time and idle gaps.

    python bench/trace_reduce.py <file.xplane.pb>    # what a trace holds

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`reduce` reads it with ``jax.profiler.ProfileData`` and returns a
:class:`Summary` over the traced window, which is the host span
``bench.traced`` that the harness opens and closes around the traced
part of the measured window:

* ``busy_s``: the union of the device's operation intervals inside the
  window, averaged over the devices;
* ``ops``: device seconds by operation name (``XLA Ops`` line), and
  ``modules``: device seconds by program name (``XLA Modules`` line);
* ``gaps``: idle device seconds inside the window, by what the host was
  doing at the middle of each gap: the innermost harness span open then
  (``"no span"`` where none was), and after ``" > "`` the innermost
  runtime event inside it, where one was open.

Operation names on a TPU are HLO instructions
(``%nmc_matmul.50 = bf16[32,2048]{...} custom-call(...)``);
:func:`short_op` cuts them to name, result shape and opcode for the
breakdown.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import sys
from collections import Counter
from typing import Optional

WINDOW_SPAN = "bench.traced"
DEVICE_PREFIXES = ("/device:TPU:", "/device:GPU:")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "no span"
CONTAINERS = ("while", "conditional", "call")
_OP = re.compile(r"^%(?P<name>\S+) = (?P<shape>.+?) (?P<op>[\w-]+)\(")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    ops: dict            # op name -> device seconds (mean over devices)
    op_counts: dict      # op name -> events (all devices)
    modules: dict        # program name -> device seconds
    module_counts: dict  # program name -> executions
    gaps: dict           # host span -> idle device seconds
    n_devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, pred) -> float:
        """Device seconds of the operations whose name satisfies ``pred``."""
        return sum(s for n, s in self.ops.items() if pred(n))

    def module_seconds(self, pred) -> tuple[float, int]:
        """Device seconds and executions of the programs ``pred`` picks."""
        keys = [n for n in self.modules if pred(n)]
        return (sum(self.modules[n] for n in keys),
                sum(self.module_counts[n] for n in keys))

    def breakdown(self, top: int = 10) -> dict:
        """The operations that took most device time (loop containers
        left out: their time is that of the operations inside them) and
        the longest idle gaps by what the host was doing."""
        ops = Counter()
        for n, s in self.ops.items():
            label, opcode = short_op(n)
            if opcode not in CONTAINERS:
                ops[label] += s
        return {"device_ops": [[k, v] for k, v in ops.most_common(top)],
                "idle_gaps": [[k, v] for k, v in
                              Counter(self.gaps).most_common(top)]}


def short_op(name: str) -> tuple[str, str]:
    """``(label, opcode)`` of an HLO-instruction operation name."""
    m = _OP.match(name)
    if m is None:
        return name[:80], ""
    shape = m.group("shape")
    shape = "(tuple)" if shape.startswith("(") else shape.split("{")[0]
    return f"{m.group('name')} {shape} {m.group('op')}", m.group("op")


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps_in(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans, t: float) -> str:
    """Name of the latest-starting span that covers time ``t``."""
    best, best_start = NO_SPAN, None
    for name, a, b in spans:
        if a <= t <= b and (best_start is None or a > best_start):
            best, best_start = name, a
    return best


def _events(plane, line_name: Optional[str] = None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for ev in line.events:
                yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def reduce(pd, span_names=(), n_devices: Optional[int] = None) -> Summary:
    """Reduce a ``ProfileData`` over its ``bench.traced`` window.
    ``span_names`` are the harness's own host spans; every other host
    event counts as runtime work."""
    planes = list(pd.planes)
    host = [p for p in planes if p.name == "/host:CPU"]
    events = [e for p in host for e in _events(p)
              if e[2] > e[1] and not e[0].startswith("$")]
    windows = [(a, b) for n, a, b in events if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    lo, hi = windows[0]
    events = [e for e in events if e[0] != WINDOW_SPAN]
    spans = [e for e in events if e[0] in span_names]
    runtime = [e for e in events if e[0] not in span_names]
    devices = sorted((p for p in planes
                      if p.name.startswith(DEVICE_PREFIXES)
                      and any(ln.name == OPS_LINE for ln in p.lines)),
                     key=lambda p: p.name)
    if n_devices is not None:
        devices = devices[:n_devices]
    if not devices:
        raise ValueError("trace has no device plane with an "
                         f"{OPS_LINE!r} line")
    ops, op_counts = Counter(), Counter()
    modules, module_counts = Counter(), Counter()
    gaps = Counter()
    busy_total = 0.0
    for plane in devices:
        evs = [(n, a, b) for n, a, b in _events(plane, OPS_LINE)]
        inside = [(n, max(a, lo), min(b, hi)) for n, a, b in evs
                  if min(b, hi) > max(a, lo)]
        for n, a, b in inside:
            ops[n] += (b - a) * 1e-9
            op_counts[n] += 1
        for n, a, b in _events(plane, MODULES_LINE):
            a, b = max(a, lo), min(b, hi)
            if b > a:
                modules[n] += (b - a) * 1e-9
                module_counts[n] += 1
        busy = union((a, b) for _, a, b in inside)
        busy_total += sum(b - a for a, b in busy) * 1e-9
        for a, b in gaps_in(busy, lo, hi):
            mid = (a + b) / 2
            span, event = innermost(spans, mid), innermost(runtime, mid)
            label = span if event == NO_SPAN else f"{span} > {event}"
            gaps[label] += (b - a) * 1e-9
    k = len(devices)
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total / k,
        ops={n: s / k for n, s in ops.items()}, op_counts=dict(op_counts),
        modules={n: s / k for n, s in modules.items()},
        module_counts=dict(module_counts),
        gaps={n: s / k for n, s in gaps.items()}, n_devices=k)


def trace_file(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: str, span_names=(),
               n_devices: Optional[int] = None) -> Summary:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(trace_file(trace_dir)), span_names,
                  n_devices)


def dump(path: str, top: int = 25) -> None:
    """Print the planes and lines of a trace with their busiest events,
    and the statistics of the first event of each."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            names, first = Counter(), {}
            n = 0
            for ev in line.events:
                names[ev.name] += ev.duration_ns
                first.setdefault(ev.name, ev)
                n += 1
            print(f"  line {line.name!r}: {n} events")
            for name, ns in names.most_common(top):
                ev = first[name]
                stats = [(k, str(v)[:80]) for k, v in ev.stats]
                print(f"    {ns * 1e-6:12.3f} ms  {name}  "
                      f"start {ev.start_ns!r}  {stats}")


if __name__ == "__main__":
    dump(sys.argv[1])
