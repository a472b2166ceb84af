"""Async dispatch runtime + pool-accounting invariants (DESIGN.md §5.2).

Covers the ISSUE-3 acceptance criteria:

* hand-computed counter invariants for :class:`ResidentPool`
  (``compiles`` / ``pad_waste`` / load/dispatch/store byte accounting)
  over mixed-shape sweeps — exact equalities, not bounds;
* every layer of the stack defaults to the executor the platform picks;
* :class:`DispatchQueue` futures resolve **bit-exact equal** to synchronous
  ``ResidentPool`` dispatch on the full Table V sweep (all kernels x both
  engines x SEW in {8, 16, 32}), including round-robin tile reuse (double
  buffering) and chained per-tile programs;
* the overlapped-DMA timing mode reports <= the serial mode's cycles on
  every kernel sweep, strictly less on the matmul sweep, with the pipeline
  makespan hand-computed on synthetic stages.
"""

import functools

import numpy as np
import pytest

from repro.core import programs, timing
from repro.nmc import (BucketedPool, DispatchQueue, Program, ResidentPool,
                       caesar_entry, carus_entry, instr_bucket, tile_bucket)
from repro.nmc.engine import get_engine
from repro.nmc.program import PROG_DTYPE
from repro.core.isa import CaesarOp, VOp
from repro.core.timing import StageCost, dispatch_cycles

SMALL = {"caesar_bytes": 2048, "carus_bytes": 4096}
ALL_SEWS = (8, 16, 32)

# one bucketed jit cache for the whole module: sync pools and queues share
# traces (the compile-once property the scheduler tests already prove), so
# the full-sweep differential below costs execution time, not compile time
_SHARED = BucketedPool()


@functools.lru_cache(maxsize=None)
def _full_build(name: str, sew: int):
    return programs.build(name, sew)


def _small_builds(sew: int = 8):
    kbs = [programs.build(n, sew, **SMALL)
           for n in ("xor", "add", "mul", "relu")]
    return [getattr(kb, e) for kb in kbs for e in ("caesar", "carus")]


def _caesar_prog(n_instr: int, sew: int = 8) -> Program:
    return Program.from_entries(
        "caesar", sew, [caesar_entry(CaesarOp.ADD, 100 + i, i, 4096 + i)
                        for i in range(n_instr)])


def _carus_prog(n_instr: int, sew: int = 8) -> Program:
    return Program.from_entries(
        "carus", sew, [carus_entry(VOp.VADD, vd=3, vs1=1, vs2=2)
                       for _ in range(n_instr)])


# ---------------------------------------------------------------------------
# Pool-accounting invariants: exact hand-computed counter values
# ---------------------------------------------------------------------------

def test_bucketed_pool_counters_hand_computed():
    """Mixed-shape sweep: three caesar programs in the 8-bucket (3 tiles ->
    tile-bucket 4), one caesar and one carus program in the 4-bucket,
    loaded, dispatched and stored through a resident pool.  Every counter
    is checked against the by-hand arithmetic."""
    progs = [_caesar_prog(5), _caesar_prog(6), _caesar_prog(7),
             _caesar_prog(3), _carus_prog(3)]
    rp = ResidentPool(pool=BucketedPool())
    for t, p in enumerate(progs):
        rp.load(t, p.engine, np.zeros(_STATE_SHAPE[p.engine], np.int32))
    rp.dispatch(list(enumerate(progs)))
    for t in range(len(progs)):
        rp.store(t, (100, 3), 8)
    assert rp.compiles == 3              # (c,8,8)x4t, (c,8,4)x1t, (k,8,4)x1t
    assert rp.dispatches == 3 and rp.programs_run == 5
    # pad_waste: [4 tiles x bucket 8 - (5+6+7)] + [4 - 3] + [4 - 3]
    assert rp.pool.pad_waste == (4 * 8 - 18) + 1 + 1 == 16
    assert rp.pool.useful_instrs == 5 + 6 + 7 + 3 + 3
    e = PROG_DTYPE.itemsize              # 8 int32 fields = 32 B per entry
    assert e == 32
    state_b = 8192 * 4                   # every image is 8192 words
    expected = (5 * state_b                              # loads
                + 4 * 8 * e + 2 * (1 * 4 * e)            # streams
                + 5 * 3 * 4)                             # 3-word stores
    assert rp.bytes_moved == expected == 165180


def test_resident_pool_mixed_engine_accounting():
    """load = full image, dispatch = instruction bytes per bucket group,
    store = result words — exact values for a two-engine tile pair."""
    rp = ResidentPool()
    rp.load("c", "caesar", np.zeros(8192, np.int32))
    rp.load("k", "carus", np.zeros((32, 256), np.int32))
    assert rp.loads == 2 and rp.bytes_moved == 2 * 8192 * 4
    rp.dispatch([("c", _caesar_prog(5)), ("k", _carus_prog(3))])
    e = PROG_DTYPE.itemsize
    instr = 1 * instr_bucket(5) * e + 1 * instr_bucket(3) * e   # 256 + 128
    assert rp.dispatches == 2            # one group per engine bucket
    assert rp.bytes_moved == 2 * 8192 * 4 + instr
    rp.store("c", (100, 4), 8)
    rp.store("k", (0, 8), 8)
    assert rp.stores == 2
    assert rp.bytes_moved == 2 * 8192 * 4 + instr + (4 + 8) * 4


def test_tile_bucket_matches_instr_bucket_rule():
    assert [tile_bucket(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]


# ---------------------------------------------------------------------------
# One device call per bucket group in ResidentPool.dispatch
# ---------------------------------------------------------------------------

_STATE_SHAPE = {"caesar": (8192,), "carus": (32, 256)}


def _image(engine: str, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(-2**31, 2**31, _STATE_SHAPE[engine], dtype=np.int32)


def _prog(engine: str, n_instr: int, sew: int = 8) -> Program:
    return (_caesar_prog if engine == "caesar" else _carus_prog)(n_instr, sew)


@pytest.mark.parametrize("engine", ("caesar", "carus"))
def test_pack_programs_matches_stack_programs(engine):
    """The packed ``[F, T, n]`` stream holds exactly each program's lowered
    engine fields, in ``field_map()`` order."""
    from repro.nmc.program import pack_programs
    progs = [_prog(engine, 8).pad_to(8), _prog(engine, 5).pad_to(8)]
    packed = pack_programs(progs)
    assert packed.dtype == np.int32 and packed.flags.c_contiguous
    assert packed.shape == (len(progs[0].field_map()), 2, 8)
    for i, (field, _) in enumerate(progs[0].field_map()):
        for t, p in enumerate(progs):
            assert np.array_equal(packed[i, t], p.lower_np()[field])


@pytest.mark.parametrize("via", ("dispatch", "queue"))
def test_resident_dispatch_one_upload_per_bucket_group(via):
    """A mixed wave of three bucket groups — caesar 5/6/7 instructions
    (bucket 8, 3 tiles -> tile bucket 4), caesar 3 (bucket 4, 1 tile) and
    carus 3/4 (bucket 4, 2 tiles) — makes one stream upload per group and
    leaves every other counter at its hand-computed value."""
    rp = ResidentPool(pool=BucketedPool())
    wave = [(("c", i), _caesar_prog(n)) for i, n in enumerate((5, 6, 7, 3))]
    wave += [(("k", i), _carus_prog(n)) for i, n in enumerate((3, 4))]
    images = {t: np.zeros(_STATE_SHAPE[p.engine], np.int32) for t, p in wave}
    if via == "dispatch":
        for t, p in wave:
            rp.load(t, p.engine, images[t])
        rp.dispatch(wave)
    else:
        queue = DispatchQueue(pool=rp)
        for t, p in wave:
            queue.submit(t, p, image=images[t])
        queue.flush()
        assert queue.waves == 1
    assert rp.stream_uploads == rp.dispatches == 3
    assert rp.dispatch_calls == 1 and rp.programs_run == 6
    assert rp.compiles == 3 and rp.loads == 6 and rp.stores == 0
    e = PROG_DTYPE.itemsize
    state_b = 8192 * 4                   # both engines hold 32 KiB
    assert rp.bytes_moved == 6 * state_b + (4 * 8 + 1 * 4 + 2 * 4) * e
    # [4 tiles x 8 - (5+6+7)] + [1 x 4 - 3] + [2 x 4 - (3+4)]
    assert rp.pool.pad_waste == 14 + 1 + 1
    assert rp.pool.useful_instrs == 18 + 3 + 7


@pytest.mark.parametrize("backend", ("scan", "pallas"))
@pytest.mark.parametrize("engine", ("caesar", "carus"))
def test_resident_dispatch_bit_exact_with_padding_lanes(engine, backend):
    """Three real tiles in a tile bucket of four: each tile's final state
    equals its program run alone; the padding lane (a copy of lane 0)
    writes nothing back."""
    rp = ResidentPool(backend=backend)
    eng = get_engine(engine)
    progs = [_prog(engine, n) for n in (5, 6, 7)]
    for i in range(3):
        rp.load(i, engine, _image(engine, i))
    rp.load("idle", engine, _image(engine, 9))
    rp.dispatch(list(enumerate(progs)))
    assert rp.dispatches == rp.stream_uploads == 1
    for i, p in enumerate(progs):
        want = eng.run(eng.init_state(_image(engine, i)), p)
        assert np.array_equal(np.asarray(rp.state(i)), np.asarray(want)), i
    assert np.array_equal(np.asarray(rp.state("idle")), _image(engine, 9))


@pytest.mark.parametrize("n_tiles", (1, 3))
@pytest.mark.parametrize("engine", ("caesar", "carus"))
def test_chained_dispatch_leaves_earlier_future_intact(engine, n_tiles):
    """A chained program (no image) takes the previous wave's final state
    as input while that wave's future is unresolved; the input is not
    donated, so the earlier future still reads its own result — also when
    the tile is lane 0 of a padded group."""
    eng = get_engine(engine)
    queue = DispatchQueue(pool=ResidentPool(pool=_SHARED))
    first, second = [], []
    for t in range(n_tiles):
        img = _image(engine, 20 + t)
        first.append(queue.submit(t, _prog(engine, 5), image=img,
                                  out_slice=(0, 64)))
        second.append(queue.submit(t, _prog(engine, 3), out_slice=(0, 64)))
    queue.flush()
    assert queue.waves == 2 and not any(f.resolved for f in first)
    for t in range(n_tiles):
        assert not first[t]._final.is_deleted()
        s1 = eng.run(eng.init_state(_image(engine, 20 + t)),
                     _prog(engine, 5))
        s2 = eng.run(s1, _prog(engine, 3))
        assert np.array_equal(second[t].result(),
                              eng.extract(s2, (0, 64), 8))
        assert np.array_equal(first[t].result(),
                              eng.extract(s1, (0, 64), 8))


@pytest.mark.parametrize("n_tiles", (3, None))
def test_group_calls_compile_once_across_passes(n_tiles):
    """A second pass of a mixed library compiles nothing: the pool's
    compile count and every group call's trace cache stay where the first
    pass left them (states staged from images and states handed on from
    earlier calls share one trace)."""
    builds = _small_builds()
    pool = BucketedPool()
    queue = DispatchQueue(pool=ResidentPool(pool=pool))
    first = queue.run_builds(builds, n_tiles=n_tiles)
    compiles = pool.compiles
    traces = {k: fn._cache_size() for k, fn in pool._group_fns.items()}
    assert set(traces.values()) == {1} and len(traces) == compiles
    again = queue.run_builds(builds, n_tiles=n_tiles)
    assert pool.compiles == compiles
    assert {k: fn._cache_size() for k, fn in pool._group_fns.items()} \
        == traces
    assert queue.pool.stream_uploads == queue.pool.dispatches
    for a, b in zip(first, again):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# Async futures vs synchronous dispatch: bit-exact (acceptance, Table V)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sew", ALL_SEWS)
def test_async_queue_bit_exact_full_table_v(sew):
    """The full Table V sweep through the overlapped DispatchQueue (with a
    4-tile round-robin array, so shadow-buffer staging actually happens)
    must equal synchronous ResidentPool dispatch bit-exactly."""
    kbs = [_full_build(name, sew) for name in programs.ALL_KERNELS]
    builds = [getattr(kb, e) for kb in kbs for e in ("caesar", "carus")]
    sync = ResidentPool(pool=_SHARED)
    ref = sync.run_builds(builds)
    queue = DispatchQueue(pool=ResidentPool(pool=_SHARED))
    got = queue.run_builds(builds, n_tiles=4)
    for eb, a, b in zip(builds, ref, got):
        assert (np.asarray(a) == np.asarray(b)).all(), (eb.engine, sew)
        exp = np.asarray(eb.oracle).reshape(-1)
        assert (np.asarray(b).reshape(-1)[:exp.size] == exp).all()
    assert queue.submitted == queue.launched == queue.resolved == len(builds)
    assert queue.staged_while_busy == len(builds) - 4   # all but first wave
    assert queue.waves == -(-len(builds) // 4)          # ceil(items / tiles)


def test_inorder_and_overlapped_schedulers_agree():
    """The queue on a 2-tile array equals synchronous dispatch bit for
    bit, staging every image after the first wave while its tile is
    busy."""
    builds = _small_builds()
    ref = ResidentPool(pool=_SHARED).run_builds(builds)
    qo = DispatchQueue(pool=ResidentPool(pool=_SHARED))
    oo = qo.run_builds(builds, n_tiles=2)
    for a, b in zip(ref, oo):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert qo.staged_while_busy == len(builds) - 2
    assert qo.waves == len(builds) // 2


def test_chained_programs_single_tile_fifo():
    """Two chained submits on one tile (second without an image) equal the
    concatenated program, and land in consecutive waves."""
    mem = np.zeros(8192, np.int32)
    mem[0], mem[4096] = 5, 7
    pa = Program.from_entries(
        "caesar", 32, [caesar_entry(CaesarOp.ADD, 100, 0, 4096)])
    pb = Program.from_entries(
        "caesar", 32, [caesar_entry(CaesarOp.XOR, 101, 100, 4096)])
    queue = DispatchQueue(pool=ResidentPool(pool=_SHARED))
    f1 = queue.submit("t", pa, image=mem)
    f2 = queue.submit("t", pb, out_slice=(100, 2))
    assert not f1.launched and not f2.launched
    out = f2.result()                    # resolves lazily, flushing both
    assert queue.waves == 2
    assert f1.result() is None           # no out_slice: state stays resident
    eng = get_engine("caesar")
    both = Program.from_entries("caesar", 32,
                                list(pa.entries) + list(pb.entries))
    exp = eng.extract(eng.run(eng.init_state(mem), both), (100, 2), 32)
    assert (out == exp).all()


def test_drain_resolves_chained_futures():
    """drain() must resolve every outstanding future — including earlier
    chained submits on a tile, not just the per-tile FIFO tail."""
    mem = np.zeros(8192, np.int32)
    pa = _caesar_prog(1, sew=8)
    pb = _caesar_prog(2, sew=8)
    queue = DispatchQueue(pool=ResidentPool(pool=_SHARED))
    f1 = queue.submit("t", pa, image=mem, out_slice=(100, 1))
    f2 = queue.submit("t", pb, out_slice=(100, 2))
    queue.drain()
    assert f1.resolved and f2.resolved
    assert queue.resolved == 2
    assert queue.pool.stores == 2        # both results were extracted


def test_queue_tile_ids_never_collide_with_pool_run_builds():
    """Anonymous queue tiles draw from the pool's id counter, so mixing
    sync and async run_builds on one pool never clobbers resident state."""
    builds = _small_builds()[:2]
    rp = ResidentPool(pool=_SHARED)
    rp.run_builds(builds)
    n_sync = len(rp.tiles)
    DispatchQueue(pool=rp).run_builds(builds)
    assert len(rp.tiles) == n_sync + len(builds)   # all tiles distinct


@pytest.mark.parametrize("make", ("BucketedPool", "ResidentPool",
                                  "DispatchQueue", "NmcRuntime"))
def test_default_executor_follows_platform(monkeypatch, make):
    """Built with no arguments, every layer of the stack runs on the
    executor the platform picks: Pallas on a TPU (nothing runs here)."""
    import jax
    from repro import nmc
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    top = getattr(nmc, make)()
    pool = {"BucketedPool": lambda: top,
            "ResidentPool": lambda: top.pool,
            "DispatchQueue": lambda: top.pool.pool,
            "NmcRuntime": lambda: top.bucketed}[make]()
    assert pool.backend == "pallas"


# ---------------------------------------------------------------------------
# Overlapped-DMA timing mode (acceptance: <= serial everywhere, < on matmul)
# ---------------------------------------------------------------------------

def test_dispatch_cycles_hand_computed():
    s = StageCost("s", 10, 100, 10)
    assert dispatch_cycles([], "overlapped") == 0.0
    # single stage: nothing to overlap with — modes agree exactly
    assert dispatch_cycles([s], "overlapped") \
        == dispatch_cycles([s], "serial") == 120
    # two compute-bound stages: the second load (10) hides under compute 0,
    # store 0 (10) hides under compute 1 — only the last store is exposed:
    # 10 + 100 + 100 + 10 = 220 vs serial 240
    assert dispatch_cycles([s, s], "serial") == 240
    assert dispatch_cycles([s, s], "overlapped") == 220
    # DMA-bound: computes hide under the DMA stream instead
    d = StageCost("d", 100, 10, 10)
    assert dispatch_cycles([d, d], "serial") == 240
    assert dispatch_cycles([d, d], "overlapped") == 220


def test_wave_cycles_hand_computed():
    """Multi-tile wave model: one shared bus serializes DMA, per-tile
    compute overlaps (DESIGN.md §9)."""
    s = StageCost("s", 10, 100, 10)
    assert timing.wave_cycles([], 4) == 0.0
    # one tile, one stage: identical to the serial sum
    assert timing.wave_cycles([s], 1) == 120
    assert timing.wave_cycles([s, s], 2, "serial") == 240
    # two tiles, two compute-bound stages: loads serialize on the bus
    # (end 10, 20), computes overlap (end 110, 120), stores drain after
    # their compute (110+10=120, then max(120,120)+10=130)
    assert timing.wave_cycles([s, s], 2) == 130
    # same two stages on ONE tile: computes serialize (10+100+100), the
    # second load hides, stores drain -> 220 (the double-buffered shape)
    assert timing.wave_cycles([s, s], 1) == 220
    # DMA-bound stages: adding tiles cannot beat the serialized bus —
    # loads end at 200, the second compute at 210, its store at 220
    d = StageCost("d", 100, 10, 10)
    assert timing.wave_cycles([d, d], 2) == 220
    assert timing.wave_cycles([d] * 4, 4) >= 4 * 100


def test_wave_speedup_saturates_when_bus_binds():
    """Scaling shape: compute-bound shards speed up with the tile count;
    once the serialized DMA stream exceeds the overlapped compute, adding
    tiles stops helping (the paper's system-level saturation)."""
    single = StageCost("w", 64, 4096, 8)

    def shard(n):
        return StageCost("p", 64 / n, 4096 / n, 8 / n)

    speed = [timing.wave_speedup(single, [shard(n)] * n, n)
             for n in (1, 2, 4, 8, 16, 64)]
    assert abs(speed[0] - 1.0) < 1e-9
    assert all(a < b for a, b in zip(speed[:4], speed[1:5]))  # rising
    # with a 64-cycle image split across 64 tiles the bus stream alone is
    # 64 cycles against 64-cycle shard computes: speedup is bus-capped far
    # below the tile count
    assert speed[-1] < 64 / 1.9


def test_store_accounting_word_granular_for_subword_tails():
    """ResidentPool.store and a resolving future (both through
    ResidentPool.extract) count whole bus words: a sub-word element tail
    (gathered shards at SEW 8/16 make odd tails common) still moves its
    full last word.  Locks the audited behavior of the 32-bit-bus
    accounting model."""
    rp = ResidentPool(pool=_SHARED)
    rp.load(("acct", 0), "caesar", np.zeros(8192, np.int32))
    b0 = rp.bytes_moved
    elems = rp.store(("acct", 0), (0, 2), 8)     # 2 words @ SEW 8
    assert rp.bytes_moved - b0 == 8              # whole words, not 5 bytes
    assert elems.size == 8                       # 2 words x 4 lanes
    b1 = rp.bytes_moved
    rp.store(("acct", 0), (0, 3), 16)            # 3 words @ SEW 16
    assert rp.bytes_moved - b1 == 12
    # the async path accounts identically: a future resolving a 2-word
    # slice with a 5-element post trim still counts 8 bytes
    queue = DispatchQueue(pool=rp)
    fut = queue.submit(("acct", 1), _caesar_prog(4),
                       image=np.zeros(8192, np.int32), out_slice=(100, 2),
                       post=lambda e: e[:5])
    queue.flush()                 # launch: image install + instruction bytes
    b2 = rp.bytes_moved
    out = fut.result()            # resolution: only the store leg remains
    assert out.size == 5                          # trimmed elements
    assert rp.bytes_moved - b2 == 8               # word-granular bytes


@pytest.mark.parametrize("name", programs.ALL_KERNELS)
def test_overlapped_leq_serial_every_kernel(name):
    stages = [timing.stage_cost(getattr(_full_build(name, sew), e))
              for sew in ALL_SEWS for e in ("caesar", "carus")]
    ser = dispatch_cycles(stages, "serial")
    ovl = dispatch_cycles(stages, "overlapped")
    assert ovl <= ser, (name, ovl, ser)
    if name == "matmul":                 # acceptance: strictly less
        assert ovl < ser, (ovl, ser)


def test_overlapped_strictly_less_on_full_sweep():
    builds = [getattr(_full_build(name, sew), e)
              for name in programs.ALL_KERNELS for sew in ALL_SEWS
              for e in ("caesar", "carus")]
    ser = timing.sweep_dispatch_cycles(builds, "serial")
    ovl = timing.sweep_dispatch_cycles(builds, "overlapped")
    assert ovl < ser
    # steady-state floor: the pipeline can't beat its busiest resource
    total_dma = sum(timing.stage_cost(b).dma_in_cycles
                    + timing.stage_cost(b).dma_out_cycles for b in builds)
    total_comp = sum(timing.stage_cost(b).compute_cycles for b in builds)
    assert ovl >= max(total_dma, total_comp)
