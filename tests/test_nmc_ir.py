"""Unified NMC program IR + batched tile-pool executors (DESIGN.md §5).

Covers the IR and scheduler contracts:
* IR encode/decode round-trips losslessly for both engine formats,
* the batched multi-tile pool is bit-exact vs. the single-instance path for
  every kernel in programs.ALL_KERNELS x SEW in {8, 16, 32},
* NOP padding is bit-exact and zero-cost on both engines (the bucketed
  scheduler's filler),
* the bucketed pool compiles once per (engine, sew, instr-bucket,
  tile-bucket) over a full Table V sweep — O(#buckets), not O(#shapes), and
* the resident pool keeps tile state on device across dispatches with
  explicit load/store byte accounting.
"""

import numpy as np
import pytest

from repro.core import ecpu, energy, isa, programs
from repro.core import timing
from repro.core.isa import CaesarOp, VOp
from repro.nmc import (Program, ResidentPool, caesar_entry, carus_entry,
                       instr_bucket, nop_entry, tile_bucket)
from repro.nmc.engine import get_engine
from repro.nmc.program import PROG_DTYPE

RNG = np.random.default_rng(7)

# reduced sizes keep the scanned engines fast in CI (mirrors test_engines)
SMALL = {"caesar_bytes": 2048, "carus_bytes": 4096}


def _build(name, sew):
    kw = SMALL if name in ("xor", "add", "mul", "relu", "leaky_relu",
                           "maxpool") else {}
    return programs.build(name, sew, **kw)


# ---------------------------------------------------------------------------
# IR round-trips
# ---------------------------------------------------------------------------

def test_caesar_stream_roundtrip():
    ops = [o for o in CaesarOp if o != CaesarOp.CSRW]
    stream = [(ops[int(RNG.integers(len(ops)))], int(RNG.integers(8192)),
               int(RNG.integers(8192)), int(RNG.integers(8192)))
              for _ in range(64)]
    prog = Program.from_caesar_stream(stream, sew=16)
    assert prog.shape_key == ("caesar", 16, 64)
    assert prog.to_caesar_stream() == stream


def test_carus_trace_roundtrip():
    from repro.core.carus import trace_entry as legacy_entry
    vops = list(isa.VOP_COMPACT)
    trace = [legacy_entry(vops[int(RNG.integers(len(vops)))],
                          vd=int(RNG.integers(32)), vs1=int(RNG.integers(32)),
                          vs2=int(RNG.integers(32)),
                          sval1=int(RNG.integers(-2**31, 2**31)),
                          sval2=int(RNG.integers(-2**31, 2**31)),
                          imm=int(RNG.integers(-16, 16)),
                          mode=int(RNG.integers(16)))
             for _ in range(64)]
    prog = Program.from_carus_trace(trace, sew=8)
    assert prog.shape_key == ("carus", 8, 64)
    for back, orig in zip(prog.to_carus_trace(), trace):
        for f in isa.CARUS_TRACE_DTYPE.names:
            assert back[f] == orig[f], f


def test_ir_entry_helpers_match_legacy_formats():
    e = caesar_entry(CaesarOp.MAC_STORE, 7, 100, 4196)
    assert e.dtype == PROG_DTYPE
    assert (int(e["op"]), int(e["dest"]), int(e["src1"]), int(e["src2"])) \
        == (int(CaesarOp.MAC_STORE), 7, 100, 4196)
    v = carus_entry(VOp.VMACC, vd=3, vs1=1, vs2=2, sval1=-5,
                    mode=isa.MODE_VX)
    assert int(v["op"]) == isa.COMPACT_ID[VOp.VMACC]
    assert (int(v["dest"]), int(v["src1"]), int(v["src2"]),
            int(v["sval1"]), int(v["mode"])) == (3, 1, 2, -5, isa.MODE_VX)


def test_builder_emits_ir_and_legacy_timing_agrees():
    """Builders emit PROG_DTYPE entries; the unified cost path must agree
    with a Program reconstructed from the decoded legacy stream."""
    kb = _build("gemm", 16)
    assert kb.caesar.program.entries.dtype == PROG_DTYPE
    assert kb.carus.program.entries.dtype == PROG_DTYPE
    legacy = Program.from_caesar_stream(kb.caesar.program.to_caesar_stream(),
                                        16)
    a = timing.program_cycles(kb.caesar.program, kb.caesar.host_cycles)
    b = timing.program_cycles(legacy, kb.caesar.host_cycles)
    assert a == b
    legacy_k = Program.from_carus_trace(kb.carus.program.to_carus_trace(), 16)
    ak = timing.program_cycles(kb.carus.program.with_sew(16))
    bk = timing.program_cycles(legacy_k)
    assert ak == bk
    assert timing.program_vrf_accesses(kb.carus.program.with_sew(16)) \
        == timing.program_vrf_accesses(legacy_k)


def test_untagged_engine_build_costs_through_wrappers():
    """Hand-built EngineBuilds without engine/sew tags (as tests construct
    them) must cost identically whether their stream holds legacy tuples or
    raw IR entries — the wrappers carry the engine knowledge."""
    legacy = programs.EngineBuild([(CaesarOp.ADD, 10, 0, 4096)] * 4,
                                  np.zeros(8192, np.int32), (10, 1))
    ir = programs.EngineBuild([caesar_entry(CaesarOp.ADD, 10, 0, 4096)] * 4,
                              np.zeros(8192, np.int32), (10, 1))
    assert timing.caesar_cycles(legacy) == timing.caesar_cycles(ir)
    k_ir = programs.EngineBuild([programs.trace_entry(VOp.VSETVL, sval1=64)],
                                np.zeros((32, 256), np.int32), (0, 4))
    assert timing.carus_cycles(k_ir, 8).n_instrs == 1


def test_ecpu_issue_trace_is_ir_program():
    """The eCPU's issue trace round-trips through the IR and replays
    bit-exactly on the batched executor."""
    import jax.numpy as jnp
    from repro.core import alu, carus

    src = """
        li   t0, 1024
        vsetvli t1, t0, e8
        xvnmc.vadd.vv v20, v1, v2
        halt
    """
    vpu = carus.CarusVPU()
    a = RNG.integers(-128, 128, 1024).astype(np.int8)
    b = RNG.integers(-128, 128, 1024).astype(np.int8)
    vrf = np.zeros((32, 256), np.int32)
    vrf[1], vrf[2] = alu.pack_np(a), alu.pack_np(b)
    cpu = ecpu.ECpu(vpu, jnp.asarray(vrf))
    cpu.load_program(ecpu.assemble(src))
    cpu.run()
    prog = cpu.program()
    assert isinstance(prog, Program) and prog.engine == "carus"
    assert prog.n_instr == cpu.vector_retired == 2
    # replay the full trace through the pool; must equal the eager result
    pool = ResidentPool()
    pool.load("t", "carus", vrf)
    pool.dispatch([("t", prog)])
    assert (np.asarray(cpu.vrf) == np.asarray(pool.state("t"))).all()


# ---------------------------------------------------------------------------
# Batched multi-tile execution: bit-exact vs the single-instance path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sew", [8, 16, 32])
def test_pool_bit_exact_all_kernels(sew):
    kbs = [_build(name, sew) for name in programs.ALL_KERNELS]
    pool = ResidentPool()
    builds = [kb.caesar for kb in kbs] + [kb.carus for kb in kbs]
    batched = pool.run_builds(builds)
    for eb, got in zip(builds, batched):
        # full output identical to the single-instance path, not just the
        # oracle-covered prefix
        single = programs.run_build(eb)
        assert (np.asarray(single) == np.asarray(got)).all(), \
            (eb.engine, sew)
        exp = np.asarray(eb.oracle).reshape(-1)
        assert (np.asarray(got).reshape(-1)[:exp.size] == exp).all(), \
            (eb.engine, sew)
    # grouped dispatch: one compile per bucket, strictly fewer compiles
    # than kernel instances
    assert pool.compiles == len({eb.program.bucket_key for eb in builds})
    assert pool.compiles < len(builds)


def test_pool_groups_heterogeneous_batches():
    kbs = [_build("xor", 8), _build("relu", 8), _build("matmul", 8)]
    pool = ResidentPool()
    res = programs.verify_sweep(kbs, pool)
    assert all(all(v.values()) for v in res.values())
    buckets = {getattr(kb, e).program.bucket_key
               for kb in kbs for e in ("caesar", "carus")}
    assert pool.compiles == len(buckets)
    # programs sharing a bucket (xor and relu on NM-Caesar) batch together
    assert pool.programs_run == 6 and pool.dispatches == len(buckets)
    assert pool.dispatches < pool.programs_run


# ---------------------------------------------------------------------------
# NOP padding: bit-exact no-op semantics, zero cycle/energy cost
# ---------------------------------------------------------------------------

def test_instr_bucket_rule():
    assert [instr_bucket(n) for n in (0, 1, 2, 3, 4, 5, 129, 512, 513)] \
        == [1, 1, 2, 4, 4, 8, 256, 512, 1024]
    assert [tile_bucket(n) for n in (1, 2, 3, 4, 5, 8, 9)] \
        == [1, 2, 4, 4, 8, 8, 16]


@pytest.mark.parametrize("engine", ["caesar", "carus"])
@pytest.mark.parametrize("kernel", ["leaky_relu", "maxpool"])
def test_nop_padding_bit_exact_and_zero_cost(engine, kernel):
    """Padded program ≡ unpadded: identical final state on the scan engine,
    identical cycles, VRF accesses and energy (NOPs are free)."""
    eb = getattr(_build(kernel, 8), engine)
    prog = eb.program
    padded = prog.pad_to(instr_bucket(prog.n_instr + 1))
    assert padded.n_instr > prog.n_instr
    assert padded.n_nops == padded.n_instr - prog.n_instr
    assert padded.bucket_key[2] >= prog.bucket_key[2]
    eng = get_engine(engine)
    s1 = eng.run(eng.init_state(eb.mem), prog)
    s2 = eng.run(eng.init_state(eb.mem), padded)
    assert (np.asarray(s1) == np.asarray(s2)).all()
    assert timing.program_cycles(prog, eb.host_cycles) \
        == timing.program_cycles(padded, eb.host_cycles)
    assert energy.program_energy(prog, eb.host_cycles) \
        == energy.program_energy(padded, eb.host_cycles)
    if engine == "carus":
        assert timing.program_vrf_accesses(prog) \
            == timing.program_vrf_accesses(padded)


def test_nop_entry_roundtrips_through_legacy_formats():
    c = Program.from_entries("caesar", 8, [nop_entry("caesar")] * 3)
    assert c.n_nops == 3
    assert c.to_caesar_stream() == [(CaesarOp.NOP, 0, 0, 0)] * 3
    k = Program.from_entries("carus", 8, [nop_entry("carus")] * 2)
    assert k.n_nops == 2 and k.vops() == [VOp.VNOP, VOp.VNOP]
    back = Program.from_carus_trace(k.to_carus_trace(), 8)
    assert back.n_nops == 2


# ---------------------------------------------------------------------------
# Bucketed scheduler: one compile per (engine, sew, instr-bucket, tile-bucket)
# ---------------------------------------------------------------------------

def _caesar_prog(n_instr: int, sew: int = 8) -> Program:
    return Program.from_entries(
        "caesar", sew,
        [caesar_entry(CaesarOp.ADD, 100 + i, i, 4096 + i)
         for i in range(n_instr)])


def _dispatch_fresh(pool: ResidentPool, progs: list, state) -> list:
    """Load ``state`` onto one fresh tile per program, dispatch the wave,
    and return the tile ids."""
    tiles = [("t", next(pool._ids)) for _ in progs]
    for t, p in zip(tiles, progs):
        pool.load(t, p.engine, state)
    pool.dispatch(list(zip(tiles, progs)))
    return tiles


def test_bucketed_pool_merges_ragged_shapes():
    """Four distinct exact shapes in one instr bucket: one compile, one
    batched dispatch, bit-exact vs each program run alone on the scan
    engine."""
    progs = [_caesar_prog(n) for n in (5, 6, 7, 8)]
    state = np.arange(8192, dtype=np.int32)
    pool = ResidentPool()
    tiles = _dispatch_fresh(pool, progs, state)
    assert len({p.shape_key for p in progs}) == 4       # exact: 4 shapes
    assert pool.compiles == 1                           # bucketed: 1
    assert pool.dispatches == 1 and pool.programs_run == 4
    # pad_waste: 4 tiles x bucket 8 - (5+6+7+8) real instructions
    assert pool.pool.pad_waste == 4 * 8 - (5 + 6 + 7 + 8)
    # four image loads plus one packed 4 x 8-entry stream
    assert pool.bytes_moved == 4 * 8192 * 4 + 4 * 8 * PROG_DTYPE.itemsize
    eng = get_engine("caesar", "scan")
    for t, p in zip(tiles, progs):
        ref = np.asarray(eng.run(eng.init_state(state), p))
        assert (np.asarray(pool.state(t)) == ref).all()


def test_bucketed_pool_tile_count_buckets_reuse_traces():
    """Partial batches pad to power-of-two tile counts and reuse the
    padded-batch trace instead of re-tracing per count."""
    pool = ResidentPool()
    state = np.zeros(8192, np.int32)
    _dispatch_fresh(pool, [_caesar_prog(8)] * 3, state)  # 3 -> bucket 4
    assert pool.compiles == 1
    _dispatch_fresh(pool, [_caesar_prog(8)] * 4, state)  # 4 -> same bucket
    assert pool.compiles == 1
    _dispatch_fresh(pool, [_caesar_prog(6)] * 4, state)  # same buckets
    assert pool.compiles == 1
    _dispatch_fresh(pool, [_caesar_prog(8)] * 2, state)  # 2 -> new bucket
    assert pool.compiles == 2


@pytest.mark.parametrize("sew", [8, 16, 32])
def test_bucketed_pool_table_v_sweep(sew):
    """Acceptance (ISSUE 2): the full Table V kernel sweep through the
    bucketed pool is bit-exact vs the single-program path and compiles at
    most once per (engine, sew, bucket) — asserted on the pool counters."""
    kbs = [_build(name, sew) for name in programs.ALL_KERNELS]
    builds = [getattr(kb, e) for kb in kbs for e in ("caesar", "carus")]
    pool = ResidentPool()
    outs = pool.run_builds(builds)
    for eb, got in zip(builds, outs):
        # bit-exact vs the per-engine oracles (the single-program path is
        # checked against the same oracles in test_pool_bit_exact_all_kernels
        # and against padded programs in the NOP tests above)
        exp = np.asarray(eb.oracle).reshape(-1)
        assert (np.asarray(got).reshape(-1)[:exp.size] == exp).all(), \
            (eb.engine, sew)
    buckets = {eb.program.bucket_key for eb in builds}
    shapes = {eb.program.shape_key for eb in builds}
    # one grouped run: exactly one compile per occupied bucket, and
    # bucketing must not exceed the exact-shape compile count
    assert pool.compiles == len(buckets)
    assert pool.compiles <= len(shapes)
    assert pool.programs_run == len(builds)
    # spot-check full bit-exactness vs the single-program path
    for i in (0, 1):
        single = programs.run_build(builds[i])
        assert (np.asarray(single) == np.asarray(outs[i])).all()


# ---------------------------------------------------------------------------
# Resident tile array: memory-mode/compute-mode duality
# ---------------------------------------------------------------------------

def test_resident_pool_state_persists_across_dispatches():
    """Two compute-mode dispatches against one resident tile must equal the
    concatenated program run in one shot — and share one trace."""
    mem = np.zeros(8192, np.int32)
    mem[0], mem[4096] = 5, 7
    pa = Program.from_entries(
        "caesar", 32, [caesar_entry(CaesarOp.ADD, 100, 0, 4096)])
    pb = Program.from_entries(
        "caesar", 32, [caesar_entry(CaesarOp.XOR, 101, 100, 4096)])
    rp = ResidentPool()
    rp.load("t", "caesar", mem)
    rp.dispatch([("t", pa)])
    rp.dispatch([("t", pb)])
    eng = get_engine("caesar")
    both = Program.from_entries("caesar", 32,
                                list(pa.entries) + list(pb.entries))
    ref = np.asarray(eng.run(eng.init_state(mem), both))
    assert (np.asarray(rp.state("t")) == ref).all()
    assert rp.compiles == 1            # same (caesar, 32, 1, 1) bucket twice
    assert rp.dispatches == 2 and rp.loads == 1


def test_resident_pool_byte_accounting_and_outputs():
    """load moves the full image, dispatch only instruction bytes, store
    only the result words — and outputs stay bit-exact vs the oracle."""
    kb = _build("xor", 8)
    eb = kb.caesar
    rp = ResidentPool()
    rp.load("t0", "caesar", eb.mem)
    state_bytes = int(rp.state("t0").size) * 4
    assert rp.bytes_moved == state_bytes
    prog = eb.program
    rp.dispatch([("t0", prog)])
    instr_bytes = rp.bytes_moved - state_bytes
    assert instr_bytes == instr_bucket(prog.n_instr) * PROG_DTYPE.itemsize
    assert instr_bytes < state_bytes   # the residency win
    before_store = rp.bytes_moved
    out = rp.store("t0", eb.out_slice, kb.sew)
    assert rp.bytes_moved - before_store == eb.out_slice[1] * 4
    exp = np.asarray(eb.oracle).reshape(-1)
    assert (out.reshape(-1)[:exp.size] == exp).all()


def test_resident_run_builds_matches_pool_run_builds():
    """The batched resident path equals each build run alone on its
    engine (the single-instance ``run_build`` path)."""
    kbs = [_build(n, 8) for n in ("xor", "add", "relu")]
    builds = [getattr(kb, e) for kb in kbs for e in ("caesar", "carus")]
    rp = ResidentPool()
    got = rp.run_builds(builds)
    ref = [programs.run_build(eb) for eb in builds]
    for a, b in zip(got, ref):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert rp.loads == len(builds) and rp.stores == len(builds)
    # tile memories are still resident (memory mode) after the run
    assert len(rp.tiles) == len(builds)
