"""The NMC stack behind one import: ``from repro import nmc`` (DESIGN.md §5/§7).

Authoring — write numpy-style Python, get the whole stack::

    from repro import nmc

    @nmc.kernel                       # trace + engine auto-selection, SEW 8
    def fused(t, x, y):
        t.store((t.load(x) * 3 + t.load(y)).max(0))

    out = fused(xs, ys)               # sync: lower, schedule, run, extract
    fut = fused.call_async(xs, ys)    # async future — bit-exact vs sync

    wide = nmc.jit(fused.fn, tiles=4) # shard one kernel across 4 tiles
    assert (wide(xs, ys) == out).all()  # bit-exact vs single-tile

Layers (each usable directly for expert control):

* :mod:`repro.nmc.frontend` — the traced frontend: :func:`kernel` /
  :func:`jit` compile a Python function over :class:`NmcValue` tracers
  into a :class:`CompiledKernel`; engine auto-selection with
  :class:`UnsupportedOnEngine` diagnostics.
* :mod:`repro.nmc.registry` — the op registry and the shared
  :class:`NmcRuntime` (one bucketed jit cache for sync + async dispatch).
* :mod:`repro.nmc.program` — the engine-agnostic structured-array
  :class:`Program` IR covering NM-Caesar bus-op streams and NM-Carus
  xvnmc issue traces, plus the padding NOP and bucket rules.
* :mod:`repro.nmc.engine` — the :class:`Engine` protocol (lower / run /
  extract / cost), the two scan-backend tile adapters over the functional
  simulators, and the backend registry (``get_engine(name, backend)``).
* :mod:`repro.nmc.pallas_engine` — the fused-kernel fast path
  (DESIGN.md §10): a Pallas pool lowers whole bucketed waves to one
  ``pl.pallas_call`` (interpret-mode on CPU), bit-exact vs scan.
* :mod:`repro.nmc.pool` — the batched executors: the compile cache
  :class:`TilePool` / :class:`BucketedPool` (one XLA compile per
  ``(engine, sew, instr-bucket, tile-bucket)``; the one place the
  executor is chosen, ``backend="auto"`` = Pallas on a TPU, scan
  elsewhere) and the persistently-resident :class:`ResidentPool`, whose
  ``dispatch`` is the one launch path.
* :mod:`repro.nmc.runtime` — the async double-buffered
  :class:`DispatchQueue`: futures, shadow-buffer staging, batched launch
  waves (DESIGN.md §5.2).
* :mod:`repro.nmc.partition` — the tile-parallel partitioning planner
  (DESIGN.md §9): shards one traced kernel across the tile array
  (``nmc.jit(fn, tiles=N)``), reassembled by :class:`GatherFuture` —
  bit-exact vs the single-tile path by construction.
* :mod:`repro.nmc.schedule` — the cost-model-driven wave scheduler and
  plan autotuner (DESIGN.md §14): searches partition strategy × chunk
  skew × per-shard engine assignment × dispatch order against
  :func:`repro.core.timing.wave_cycles`
  (``nmc.jit(fn, tiles=N, schedule="auto")``), caching winning
  :class:`SchedulePlan` objects in a content-keyed blake2b-LRU registry.
"""

from repro.nmc.program import (PROG_DTYPE, Program, caesar_entry, carus_entry,
                               instr_bucket, nop_entry)
from repro.nmc.engine import (BACKENDS, CaesarTile, CarusTile, Engine,
                              get_engine, implementations, resolve_backend)
from repro.nmc.pool import BucketedPool, ResidentPool, TilePool, tile_bucket
from repro.nmc.runtime import DispatchQueue, GatherFuture, NMCFuture
from repro.nmc.registry import (NmcRuntime, default_runtime,
                                set_default_runtime)
from repro.nmc.frontend import (CompiledKernel, LoweredKernel, LoweringError,
                                NmcValue, ProgramBuilder, TileContext,
                                UnsupportedOnEngine, jit, kernel, mac,
                                select_engine)
from repro.nmc.partition import (PartitionError, PartitionPlan, slide_halo,
                                 plan as plan_partition)
from repro.nmc.schedule import (SCHEDULE_MODES, SchedulePlan, autotune,
                                clear_plan_cache, plan_wave, uniform_plan)
from repro.nmc.check import (CHECK_MODES, CheckReport, Diagnostic,
                             VerificationError, assert_submittable,
                             assert_wave, verify_chained_waves,
                             verify_lowered, verify_plan, verify_program,
                             verify_resident, verify_wave)
from repro.nmc.opt import (OPT_LEVELS, OptError, OptReport, RewriteRecord,
                           optimize)

__all__ = [
    # the one-call frontend (DESIGN.md §7)
    "jit", "kernel", "mac", "CompiledKernel", "LoweredKernel", "NmcValue",
    "ProgramBuilder", "TileContext", "UnsupportedOnEngine", "LoweringError",
    "select_engine",
    # static verification (DESIGN.md §11)
    "CHECK_MODES", "CheckReport", "Diagnostic", "VerificationError",
    "verify_program", "verify_lowered", "verify_plan", "verify_wave",
    "verify_resident", "verify_chained_waves",
    "assert_wave", "assert_submittable", "slide_halo",
    # analysis-driven IR optimizer (DESIGN.md §13)
    "OPT_LEVELS", "OptError", "OptReport", "RewriteRecord", "optimize",
    # tile-parallel partitioning planner (DESIGN.md §9)
    "plan_partition", "PartitionPlan", "PartitionError",
    # wave scheduler + plan autotuner (DESIGN.md §14)
    "SCHEDULE_MODES", "SchedulePlan", "autotune", "uniform_plan",
    "plan_wave", "clear_plan_cache",
    # shared execution runtime
    "NmcRuntime", "default_runtime", "set_default_runtime",
    # unified program IR
    "PROG_DTYPE", "Program", "caesar_entry", "carus_entry", "nop_entry",
    "instr_bucket",
    # engines / backends
    "CaesarTile", "CarusTile", "Engine", "get_engine", "BACKENDS",
    "implementations", "resolve_backend",
    # pools / scheduler
    "TilePool", "BucketedPool", "ResidentPool", "tile_bucket",
    # async dispatch runtime
    "DispatchQueue", "NMCFuture", "GatherFuture",
]
