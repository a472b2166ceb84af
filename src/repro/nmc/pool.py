"""Batched multi-tile NMC executors (DESIGN.md §5).

The paper's architectures are *scalable*: an edge node instantiates arrays of
identical NM-Caesar / NM-Carus tiles across its SRAM macros, each running its
own program against its own memory.  This module models that at three levels:

* :class:`TilePool` — the compile cache: one batched executor per
  ``(engine, sew, instr-bucket, tile-bucket)`` (``jax.vmap`` over the
  ``lax.scan`` engines, or one fused Pallas grid), and around it one
  jitted device call per bucket group.  The pool picks its executor once,
  at construction: ``backend="auto"`` (the default) is Pallas on a TPU
  and scan elsewhere.
* :class:`BucketedPool` — the same cache plus the padding counters of the
  shape-bucketed scheduler: instruction streams NOP-pad to power-of-two
  buckets (:func:`repro.nmc.program.instr_bucket`) and partial tile
  batches pad to power-of-two tile counts (:func:`tile_bucket`, extra
  lanes replicated and discarded), so a heterogeneous kernel sweep
  compiles once per bucket instead of once per exact shape/count pair.
* :class:`ResidentPool` — persistently-resident tile memories: per-tile
  state stays on device across dispatches (the paper's memory-mode /
  compute-mode duality), with explicit load/store accounting so benchmarks
  can assert that steady-state dispatch moves only instruction bytes.
  :meth:`ResidentPool.dispatch` is the one launch path: every program that
  runs on the tile array goes through it.

Compilation discipline: ``compiles`` counts actual trace-cache misses, and
``pad_waste`` quantifies the cost of the bucketing trade — benchmarks and
tests assert on both exactly where the property is claimed (one compile
per bucket over a grouped sweep).
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro.nmc.engine import get_engine, resolve_backend
from repro.nmc.program import (FIELD_MAPS, PROG_DTYPE, instr_bucket,
                               pack_programs)

WORD_BYTES = 4


def tile_bucket(n_tiles: int) -> int:
    """Power-of-two tile-count bucket rule: a partial batch pads up to the
    next power of two (replicated lanes, outputs discarded) so it reuses the
    padded-batch trace instead of re-tracing per tile count.  Same rule as
    :func:`repro.nmc.program.instr_bucket`, delegated so the two bucketing
    dimensions can never drift apart."""
    return instr_bucket(n_tiles)


class TilePool:
    """Compile cache of batched tile executors.

    ``backend`` (``"scan"``, ``"pallas"`` or ``"auto"``) is resolved once
    here; it is the only place the stack chooses its executor.  ``donate``
    is accepted and ignored: the executor is traced inside
    :meth:`_group_fn`, whose inputs are never donated.
    """

    def __init__(self, donate: bool = False, backend: str = "auto"):
        del donate
        self._cache: dict[tuple, object] = {}
        self._group_fns: dict[tuple, object] = {}
        self.backend = resolve_backend(backend)
        self.compiles = 0          # distinct traces (cache misses)

    def _batched_fn(self, shape_key: tuple, n_tiles: int):
        """``(batch_state[T, ...], fields) -> batch_state``: the unjitted
        executor of one bucket shape, traced by :meth:`_group_fn`."""
        key = (*shape_key, n_tiles)
        fn = self._cache.get(key)
        if fn is None:
            engine_name, sew, _ = shape_key
            engine = get_engine(engine_name, self.backend)
            make = getattr(engine, "batched_fn", None)
            # fused-kernel backends build the whole tile batch in one
            # call (Pallas grid) instead of vmapping the scan step; the
            # function under its jit is what the group call traces (a jit
            # nested in that one lowers up to 2.5x slower on the chip)
            fn = make(sew, n_tiles).__wrapped__ if make is not None \
                else jax.vmap(engine.scan_fn(sew))
            self._cache[key] = fn
            self.compiles += 1
        return fn

    def _group_fn(self, shape_key: tuple, n_tiles: int):
        """``(states: tuple of n_tiles arrays, packed[F, n_tiles, n]) ->
        tuple of n_tiles final states`` — one jitted device call per bucket
        group for :meth:`ResidentPool.dispatch`: the tile states stack, the
        :func:`pack_programs` stream splits into its fields, and the lanes
        split back, all inside the trace around :meth:`_batched_fn`'s
        executor.  Cached under the same key, so it adds no compile key;
        nothing is donated (the stack inside the trace is a fresh buffer,
        so the executor still updates it in place)."""
        key = (*shape_key, n_tiles)
        fn = self._group_fns.get(key)
        if fn is None:
            batched = self._batched_fn(shape_key, n_tiles)
            fields = [eng for eng, _ in FIELD_MAPS[shape_key[0]]]

            def run_group(states, packed):
                final = batched(jnp.stack(states),
                                dict(zip(fields, packed)))
                return tuple(final[t] for t in range(n_tiles))

            fn = self._group_fns[key] = jax.jit(run_group)
        return fn

    @property
    def shape_keys_compiled(self) -> set[tuple]:
        return {k[:3] for k in self._cache}


class BucketedPool(TilePool):
    """:class:`TilePool` with the shape-bucketed scheduler's counters
    (DESIGN.md §5), written by :meth:`ResidentPool.dispatch`:

    * ``pad_waste``     — instruction slots spent on padding: NOP tails of
      real programs plus the whole streams of replicated padding lanes.
    * ``useful_instrs`` — the real (non-NOP) instructions executed.
    """

    def __init__(self, donate: bool = False, backend: str = "auto"):
        super().__init__(donate=donate, backend=backend)
        self.pad_waste = 0
        self.useful_instrs = 0


class ResidentPool:
    """Persistently-resident tile array over a :class:`BucketedPool`.

    Models the paper's memory-mode / compute-mode duality: a tile's SRAM
    macro is *loaded* once (memory-mode write), then arbitrarily many
    programs execute against the resident state (compute mode) with only
    instruction streams crossing the host/device boundary, and results are
    *stored* back explicitly (memory-mode read).  Between dispatches the
    per-tile state lives on device, and each bucket group of a wave runs
    as one device call (:meth:`dispatch`).

    Accounting: ``bytes_moved`` counts only explicit host<->device traffic —
    ``load`` (full image), ``dispatch`` (instruction bytes), ``store``
    (result words) — so benchmarks can assert that steady-state dispatch
    cost is O(program), not O(tile memory).
    """

    def __init__(self, pool: BucketedPool | None = None,
                 backend: str = "auto"):
        self.pool = pool if pool is not None \
            else BucketedPool(backend=backend)
        self._engine: dict[object, str] = {}   # tile id -> engine name
        self._state: dict[object, object] = {}  # tile id -> resident state
        self._ids = itertools.count()
        self.loads = 0
        self.stores = 0
        self.dispatches = 0
        self.dispatch_calls = 0      # dispatch() invocations (launch waves)
        self.stream_uploads = 0      # host->device stream transfers: one
                                     # packed array per bucket group
        self.programs_run = 0
        self.bytes_moved = 0
        self.patches = 0             # partial memory-mode writes (word spans)
        self.patch_bytes = 0         # bytes moved by patches (also counted
                                     # in bytes_moved)

    @property
    def compiles(self) -> int:
        return self.pool.compiles

    @property
    def tiles(self) -> list:
        return list(self._state)

    def state(self, tile) -> jax.Array:
        """The tile's resident device buffer (memory-mode view)."""
        return self._state[tile]

    # -- memory mode ---------------------------------------------------------
    def load(self, tile, engine: str, image) -> None:
        """Memory-mode write: host image -> resident tile memory."""
        self.install(tile, engine, get_engine(engine).init_state(image))

    def install(self, tile, engine: str, state) -> None:
        """Adopt an already-staged device buffer as the tile's resident
        state (the buffer swap of the double-buffered dispatch runtime:
        :mod:`repro.nmc.runtime` stages images asynchronously and installs
        them at launch time).  Accounted exactly like ``load``."""
        self._engine[tile] = engine
        self._state[tile] = state
        self.loads += 1
        self.bytes_moved += int(state.size) * WORD_BYTES

    def patch(self, tile, updates: list[tuple[int, np.ndarray]]) -> None:
        """Memory-mode *partial* write: apply ``(word_start, words)`` spans
        onto the tile's resident state without re-uploading the full image.

        This is the steady-state serving path (DESIGN.md §12): weights stay
        resident across calls, only the per-call activation words cross the
        bus.  Accounted under dedicated counters (``patches`` /
        ``patch_bytes``, also rolled into ``bytes_moved``) and *not* under
        ``loads`` — so residency proofs can assert weights DMA'd onto the
        tile once while activations streamed per call."""
        assert tile in self._state, \
            f"patch of tile {tile!r} with no resident state — load first"
        state = self._state[tile]
        flat = state.reshape(-1)
        nw = 0
        for lo, words in updates:
            w = jnp.asarray(np.asarray(words, np.int32).reshape(-1))
            assert int(lo) >= 0 and int(lo) + w.size <= flat.size, \
                (tile, lo, int(w.size), int(flat.size))
            flat = flat.at[int(lo):int(lo) + w.size].set(w)
            nw += int(w.size)
        self._state[tile] = flat.reshape(state.shape)
        self.patches += 1
        self.patch_bytes += nw * WORD_BYTES
        self.bytes_moved += nw * WORD_BYTES

    def store(self, tile, out_slice: tuple[int, int], sew: int) -> np.ndarray:
        """Memory-mode read: resident output words -> host elements."""
        return self.extract(self._engine[tile], self._state[tile],
                            out_slice, sew)

    def extract(self, engine: str, state, out_slice: tuple[int, int],
                sew: int) -> np.ndarray:
        """Read ``out_slice`` of a tile state back to host elements and
        count it as a store — the one memory-mode read, shared by
        :meth:`store` and :meth:`repro.nmc.runtime.NMCFuture.result`."""
        elems = get_engine(engine).extract(state, out_slice, sew)
        self.stores += 1
        # word-granular accounting: ``out_slice`` is (word_start, n_words),
        # and the 32-bit system bus moves whole words — a sub-word element
        # tail at SEW 8/16 (common for gathered shard outputs) still costs
        # its full last word.  Locked by tests/test_runtime.py.
        self.bytes_moved += int(out_slice[1]) * WORD_BYTES
        return elems

    # -- compute mode --------------------------------------------------------
    def dispatch(self, assignments: list[tuple]) -> None:
        """Execute ``(tile, program)`` pairs against the resident states.

        Grouped by bucket key and batched through the wrapped pool's jit
        cache, on the pool's executor; final states replace the resident
        buffers without ever leaving the device.  Only the instruction
        streams are uploaded (counted in ``bytes_moved``).

        Each bucket group is one device call (:meth:`TilePool._group_fn`)
        fed with the tiles' resident states and the group's NOP-padded
        streams packed into one int32 array (:func:`pack_programs`): one
        stream upload per group, counted in ``stream_uploads`` (equal to
        ``dispatches``).  The per-tile inputs are not donated: a tile's
        previous future may still hold its state, and padding lanes alias
        lane 0.

        One dispatch is one parallel step across the tile array, so a tile
        may appear at most once per call — chained programs on one tile are
        sequential ``dispatch`` calls (each sees the previous final state).
        A mixed-engine wave (DESIGN.md §14) rides one call: its Caesar and
        Carus shards fall into separate bucket-key groups below (the
        bucket key carries the engine), each batched on its own
        interpreter, but they remain one parallel step — ``dispatch_calls``
        counts the steps, ``dispatches`` the per-group executions."""
        self.dispatch_calls += 1
        seen = set()
        by_key: dict[tuple, list[tuple]] = {}
        for tile, prog in assignments:
            assert tile not in seen, \
                f"tile {tile!r} assigned twice in one dispatch — chain " \
                f"programs via sequential dispatch() calls"
            seen.add(tile)
            assert self._engine[tile] == prog.engine, \
                (tile, self._engine[tile], prog.engine)
            by_key.setdefault(prog.bucket_key, []).append((tile, prog))
        from repro.nmc.check import assert_wave
        for key, group in by_key.items():
            tiles = [t for t, _ in group]
            bucket = key[2]
            progs = [p.pad_to(bucket) for _, p in group]
            # wave-level floor of the static checking contract (§11)
            assert_wave(progs)
            tb = tile_bucket(len(tiles))
            states = [self._state[t] for t in tiles]
            states += [states[0]] * (tb - len(tiles))
            progs += [progs[0]] * (tb - len(tiles))
            fn = self.pool._group_fn(progs[0].shape_key, tb)
            final = fn(tuple(states), pack_programs(progs))  # stays on device
            for tile, state in zip(tiles, final):
                self._state[tile] = state
            self.stream_uploads += 1
            self.dispatches += 1
            self.programs_run += len(tiles)
            self.bytes_moved += tb * bucket * PROG_DTYPE.itemsize
            # ragged-tail visibility: the wrapped pool's padding counters
            # (NOP tails of real programs + whole replicated padding lanes)
            real = sum(p.n_instr - p.n_nops for _, p in group)
            self.pool.pad_waste += bucket * tb - real
            self.pool.useful_instrs += real

    # -- convenience ---------------------------------------------------------
    def run_builds(self, builds: list) -> list[np.ndarray]:
        """Run a list of :class:`repro.core.programs.EngineBuild` instances
        (each tagged with engine/sew by its kernel builder) via
        load/dispatch/store and return each build's output *elements*, with
        its host-side ``post`` stage applied — bit-identical to the
        single-instance ``run_build`` path, with every tile memory left
        resident afterwards.
        :meth:`repro.nmc.runtime.DispatchQueue.run_builds` is the
        asynchronous flavour."""
        tiles = []
        for eb in builds:
            tile = ("build", next(self._ids))
            self.load(tile, eb.program.engine, eb.mem)
            tiles.append(tile)
        self.dispatch([(t, eb.program) for t, eb in zip(tiles, builds)])
        outs = []
        for t, eb in zip(tiles, builds):
            elems = self.store(t, eb.out_slice, eb.program.sew)
            outs.append(eb.post(elems) if eb.post else elems)
        return outs
