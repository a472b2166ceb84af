"""The benchmark's registry, clocks, spans and result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own and is found here by the name that
``BENCHMARK.json`` gives it:

* ``configs[].file``               — the configuration, as it is run;
* ``bench/traffic/<traffic>.json`` — the traffic mix: parameters plus the
  name of the driver (``bench/drivers/<driver>.py``) that runs it;
* ``bench/metrics/<metric>.py``    — one reader per per-layer metric.

A driver gets a :class:`Harness`: it builds and warms the system, calls
:meth:`Harness.open_window`, drives traffic until :meth:`Harness.done`,
closes the window, and returns a :class:`Result`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bm: dict, name: str) -> tuple[dict, dict]:
    """``(workload entry, config entry)`` of the cell called ``name``."""
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    return cell, configs[cell["config"]]


def load_config(entry: dict, root: Path = ROOT) -> dict:
    return json.loads((root / entry["file"]).read_text())


def traffic_path(name: str, bench: Path = BENCH) -> Path:
    return bench / "traffic" / f"{name}.json"


def load_traffic(name: str, bench: Path = BENCH) -> dict:
    return json.loads(traffic_path(name, bench).read_text())


def load_driver(name: str):
    return importlib.import_module(f"bench.drivers.{name}")


def metric_path(name: str, bench: Path = BENCH) -> Path:
    return bench / "metrics" / f"{name}.py"


def load_metric(name: str, bench: Path = BENCH):
    """The reader module of per-layer metric ``name`` (file names carry
    the metric's dots, so it is loaded by path, not by package)."""
    path = metric_path(name, bench)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bm: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries reported in ``cell``.

    A metric with a ``workloads`` key is reported in those cells.  A
    per-layer metric without one follows the end-to-end metric it moves;
    an end-to-end metric without one is reported everywhere."""
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    out = []
    for m in bm[kind]:
        if "workloads" in m:
            keep = cell in m["workloads"]
        elif kind == "per_layer":
            moved = e2e[m["moves"]]
            keep = "workloads" not in moved or cell in moved["workloads"]
        else:
            keep = True
        if keep:
            out.append(m)
    return out


def load_peaks(device_kind: str, bench: Path = BENCH) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = json.loads((bench / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# statistics
# ---------------------------------------------------------------------------

def percentile(values, q: float) -> Optional[float]:
    """``q``-th percentile (linear between order statistics), or None."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def mean(values) -> Optional[float]:
    values = list(values)
    return statistics.fmean(values) if values else None


# ---------------------------------------------------------------------------
# compile counter (``jax.monitoring``)
# ---------------------------------------------------------------------------

class Compiles:
    """Counts XLA backend compiles and persistent-cache hits."""

    def __init__(self):
        import jax
        self.n, self.secs, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Compared:
    """One number the correctness check compares, beside its limit: the
    run is correct when every ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Result:
    attempted: int
    failed: int
    end_to_end: dict                 # metric name -> value (host clock)
    compared: list                   # [Compared]
    facts: dict                      # what the per-layer readers read
    notes: list = dataclasses.field(default_factory=list)  # stderr lines

    @property
    def correct(self) -> bool:
        return bool(self.compared) and all(c.ok for c in self.compared) \
            and self.failed == 0


class Harness:
    """Clock, spans, compile counter and optional device trace of one run.

    ``t_start`` is the process start (taken before JAX is imported), so
    ``setup_s`` covers imports, weights, compiles or cache loads and
    warm-up.  With ``trace_s > 0`` the first ``trace_s`` seconds of the
    window run under the JAX profiler; host spans are written with
    ``jax.profiler.TraceAnnotation`` so the trace can attribute device
    idle gaps to them."""

    def __init__(self, seconds: float, t_start: float, trace_s: float = 0.0,
                 trace_dir: Optional[str] = None, devices=()):
        self.seconds = float(seconds)
        self.devices = list(devices)
        self.memory_peak = 0
        self.t_start = t_start
        self.trace_s = float(trace_s)
        self.trace_dir = trace_dir
        self.compiles = Compiles()
        self.spans: dict[str, list[float]] = {}
        self.setup_s: Optional[float] = None
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None
        self._tracing = False
        self._trace_end: Optional[float] = None
        self._win_ann = None
        self.traced: Optional[tuple[float, float]] = None
        self.window_compiles = 0
        self._c0 = 0

    # -- spans ---------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t)

    # -- window --------------------------------------------------------------
    def open_window(self) -> float:
        """End of set-up, start of the measured window; returns its start."""
        import jax
        now = time.perf_counter()
        self.setup_s = now - self.t_start
        self.spans.clear()
        self._c0 = self.compiles.n
        if self.trace_s > 0:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no per-call Python events
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._tracing = True
            self._win_ann = jax.profiler.TraceAnnotation("bench.traced")
            self._win_ann.__enter__()
            now = time.perf_counter()
            self._trace_end = now + self.trace_s
            self.traced = (now, None)
        self.t0 = now
        return now

    def tick(self) -> float:
        """Current time; stops the profiler once the traced part is over."""
        now = time.perf_counter()
        if self._tracing and now >= self._trace_end:
            self._stop_trace(now)
        return now

    def done(self) -> bool:
        return self.tick() - self.t0 >= self.seconds

    def _stop_trace(self, now: float) -> None:
        import jax
        self._win_ann.__exit__(None, None, None)
        self.traced = (self.traced[0], now)
        jax.profiler.stop_trace()
        self._tracing = False

    def close_window(self) -> float:
        """End of the window.  Reads the peak device memory here, before
        any reference runs."""
        now = time.perf_counter()
        self.t1 = now
        self.window_compiles = self.compiles.n - self._c0
        if self._tracing:
            self._stop_trace(now)
        self.memory_peak = memory_peak_bytes(self.devices)
        return now

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def accelerator(chips: int) -> list:
    """The devices a cell runs on.  Raises where JAX finds no accelerator
    or fewer chips than the cell asks for: the benchmark never falls back
    to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise RuntimeError(f"no accelerator attached (JAX found "
                           f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise RuntimeError(f"the cell asks for {chips} chips, JAX found "
                           f"{len(devs)}")
    return devs[:chips]


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout
    (``JAX_COMPILATION_CACHE_DIR`` wins where it is set); every program is
    cached, however short its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root /
                                                              ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def make_trace_dir() -> str:
    return tempfile.mkdtemp(prefix="bench_trace_")


def run_metric_readers(entries: list, ctx: Any,
                       loader: Callable = load_metric) -> dict:
    """Per-layer readings; a reader that finds nothing returns None and
    its metric is left out."""
    out = {}
    for m in entries:
        val = loader(m["name"]).read(ctx)
        if val is not None:
            out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out


def eprint(*a) -> None:
    print(*a, file=sys.stderr, flush=True)
