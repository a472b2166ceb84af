"""Property-based differential fuzzer: engine backends vs a numpy oracle.

Random unified-IR programs (hypothesis-generated, or the deterministic
vendored shim offline) execute on **every registered engine backend** —
the ``lax.scan`` interpreters and the fused Pallas kernels (interpret mode
on CPU, so this fuzz coverage needs no accelerator) — and are checked
**bit-exact** against independent numpy interpreters built on the
``repro.core.alu`` numpy mirrors (``lane_binop_np`` & co.) — an entirely
separate evaluation path: no JAX, no tracing, plain int64 arithmetic with
truncation at pack time.  Three properties, each across SEW in {8, 16, 32}
and backend in {scan, pallas}:

* random NM-Caesar bus-op programs (all binops + MAC/DOT accumulator chains
  + NOPs, random addresses) match the numpy memory-image interpreter;
* random NM-Carus xvnmc traces (arith vv/vx/vi, vmacc, vmv, vsetvl with
  dynamic VL, VL-masked tail-undisturbed writeback, NOPs) match the numpy
  VRF interpreter;
* one abstract elementwise op chain lowered to BOTH engines produces the
  same elements, equal to the shared numpy lane chain (the cross-engine
  differential: ops expressible on both ISAs must agree).

Programs NOP-pad to fixed instruction buckets so each engine traces once
per SEW for the whole fuzz run (the bucketed-scheduler property the suite
already proves).  Indirect addressing, slides and EMVV/EMVX are exercised
by tests/test_engines.py; they are out of the expressible-on-both subset
fuzzed here.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import alu, isa
from repro.core.carus import CarusConfig
from repro.core.isa import CaesarOp, VOp
from repro.nmc.engine import BACKENDS, get_engine
from repro.nmc.program import Program, caesar_entry, carus_entry

SEWS = (8, 16, 32)

CAESAR_MEM_WORDS = 8192
CAESAR_BUCKET = 16          # fuzzed streams pad here: one trace per SEW
CARUS_BUCKET = 16

# Independent op tables (deliberately restated, not imported from the
# engines, so a transcription bug in either side is caught).
CAESAR_BINOPS = {
    CaesarOp.AND: "and", CaesarOp.OR: "or", CaesarOp.XOR: "xor",
    CaesarOp.ADD: "add", CaesarOp.SUB: "sub", CaesarOp.MUL: "mul",
    CaesarOp.SLL: "sll", CaesarOp.SLR: "srl", CaesarOp.SRA: "sra",
    CaesarOp.MIN: "min", CaesarOp.MAX: "max",
}
CARUS_ARITH = {
    VOp.VADD: "add", VOp.VSUB: "sub", VOp.VMUL: "mul", VOp.VAND: "and",
    VOp.VOR: "or", VOp.VXOR: "xor", VOp.VMIN: "min", VOp.VMINU: "minu",
    VOp.VMAX: "max", VOp.VMAXU: "maxu", VOp.VSLL: "sll", VOp.VSRL: "srl",
    VOp.VSRA: "sra",
}
# ops expressible on both ISAs, as (caesar, carus, lane-op) triples
COMMON_OPS = [(c, {"add": VOp.VADD, "sub": VOp.VSUB, "mul": VOp.VMUL,
                   "and": VOp.VAND, "or": VOp.VOR, "xor": VOp.VXOR,
                   "min": VOp.VMIN, "max": VOp.VMAX, "sll": VOp.VSLL,
                   "srl": VOp.VSRL, "sra": VOp.VSRA}[name], name)
              for c, name in CAESAR_BINOPS.items()]


# ---------------------------------------------------------------------------
# numpy reference interpreters (the oracle side of the differential)
# ---------------------------------------------------------------------------

def caesar_oracle(mem: np.ndarray, prog: Program) -> np.ndarray:
    """Walk a Caesar IR program over a numpy memory image (word at a time),
    carrying the packed MAC and scalar DOT accumulators."""
    mem = np.array(mem, dtype=np.int32).copy()
    sew = prog.sew
    mac = np.int32(0)
    dot = 0
    for e in prog.entries:
        op = CaesarOp(int(e["op"]))
        d, s1, s2 = int(e["dest"]), int(e["src1"]), int(e["src2"])
        if op == CaesarOp.NOP:
            continue
        a, b = mem[s1], mem[s2]
        if op in CAESAR_BINOPS:
            mem[d] = alu.word_binop_np(CAESAR_BINOPS[op], a, b, sew)
        elif op == CaesarOp.MAC_INIT:
            mac = alu.word_macc_np(np.int32(0), a, b, sew)
        elif op == CaesarOp.MAC:
            mac = alu.word_macc_np(mac, a, b, sew)
        elif op == CaesarOp.MAC_STORE:
            mac = alu.word_macc_np(mac, a, b, sew)
            mem[d] = mac
        elif op == CaesarOp.DOT_INIT:
            dot = alu.word_dot_np(0, a, b, sew)
        elif op == CaesarOp.DOT:
            dot = alu.word_dot_np(dot, a, b, sew)
        elif op == CaesarOp.DOT_STORE:
            dot = alu.word_dot_np(dot, a, b, sew)
            mem[d] = dot
        else:
            raise AssertionError(op)
    return mem


def carus_oracle(vrf: np.ndarray, prog: Program) -> np.ndarray:
    """Walk a Carus IR trace over a numpy VRF with dynamic VL and the
    VL-masked (tail-undisturbed) writeback of the scanned VPU."""
    cfg = CarusConfig()
    vrf = np.array(vrf, dtype=np.int32).reshape(cfg.n_regs,
                                                cfg.reg_words).copy()
    sew = prog.sew
    L = 32 // sew
    n_elems = cfg.reg_words * L
    vlmax = cfg.vlmax(sew)
    vl = vlmax
    for e in prog.entries:
        vop = isa.VOP_COMPACT[int(e["op"])]
        if vop == VOp.VNOP:
            continue
        if vop == VOp.VSETVL:
            vl = min(int(e["sval1"]), vlmax)
            continue
        opmode = int(e["mode"]) & 0x3
        vd = int(e["dest"]) % cfg.n_regs
        vs2 = int(e["src2"]) % cfg.n_regs
        vs1 = int(e["src1"]) % cfg.n_regs
        dst = alu.unpack_lanes_np(vrf[vd], sew).reshape(-1)
        s2 = alu.unpack_lanes_np(vrf[vs2], sew).reshape(-1)
        if opmode == isa.MODE_VV:
            b = alu.unpack_lanes_np(vrf[vs1], sew).reshape(-1)
        else:
            scalar = (int(e["imm"]) if opmode == isa.MODE_VI
                      else int(e["sval1"]))
            b = np.full(n_elems, scalar, np.int64)
        if vop in CARUS_ARITH:
            r = alu.lane_binop_np(CARUS_ARITH[vop], s2, b, sew)
        elif vop == VOp.VMACC:
            r = dst + s2 * b
        elif vop == VOp.VMV:
            r = b
        else:
            raise AssertionError(vop)
        sel = np.where(np.arange(n_elems) < vl, r, dst)
        vrf[vd] = alu.pack_lanes_np(sel.reshape(cfg.reg_words, L), sew)
    return vrf


def _run_engine(prog: Program, state: np.ndarray,
                backend: str = "scan") -> np.ndarray:
    eng = get_engine(prog.engine, backend)
    return np.asarray(eng.run(eng.init_state(state), prog))


# ---------------------------------------------------------------------------
# numpy-mirror unit sanity: the mirrors match the JAX ALU on random words
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sew", SEWS)
def test_numpy_alu_mirrors_match_jax(sew):
    rng = np.random.default_rng(3)
    words_a = rng.integers(-2**31, 2**31, 64, dtype=np.int64).astype(np.int32)
    words_b = rng.integers(-2**31, 2**31, 64, dtype=np.int64).astype(np.int32)
    import jax.numpy as jnp
    ja, jb = jnp.asarray(words_a), jnp.asarray(words_b)
    for op in alu.BINOPS:
        got = alu.word_binop_np(op, words_a, words_b, sew)
        exp = np.asarray(alu.word_binop(op, ja, jb, sew))
        assert (got == exp).all(), op
    got = alu.word_macc_np(words_a, words_b, words_a, sew)
    exp = np.asarray(alu.word_macc(ja, jb, ja, sew))
    assert (got == exp).all()
    assert alu.word_dot_np(7, words_a, words_b, sew) \
        == int(alu.word_dot(jnp.int32(7), ja, jb, sew))


# ---------------------------------------------------------------------------
# engine-specific fuzzers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("sew", SEWS)
@given(n_instr=st.integers(1, CAESAR_BUCKET - 1), seed=st.integers(0, 2**16))
@settings(max_examples=8, deadline=None)
def test_caesar_random_programs_match_oracle(sew, backend, n_instr, seed):
    rng = np.random.default_rng(seed)
    ops = list(CAESAR_BINOPS) + [CaesarOp.MAC_INIT, CaesarOp.MAC,
                                 CaesarOp.MAC_STORE, CaesarOp.DOT_INIT,
                                 CaesarOp.DOT, CaesarOp.DOT_STORE,
                                 CaesarOp.NOP]
    entries = [caesar_entry(ops[rng.integers(len(ops))],
                            int(rng.integers(CAESAR_MEM_WORDS)),
                            int(rng.integers(CAESAR_MEM_WORDS)),
                            int(rng.integers(CAESAR_MEM_WORDS)))
               for _ in range(n_instr)]
    prog = Program.from_entries("caesar", sew, entries) \
        .pad_to(CAESAR_BUCKET)                 # one trace per SEW
    mem = rng.integers(-2**31, 2**31, CAESAR_MEM_WORDS,
                       dtype=np.int64).astype(np.int32)
    got = _run_engine(prog, mem, backend)
    exp = caesar_oracle(mem, prog)
    assert (got == exp).all(), \
        (sew, backend, seed, np.flatnonzero(got != exp)[:8])


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("sew", SEWS)
@given(n_instr=st.integers(1, CARUS_BUCKET - 1), seed=st.integers(0, 2**16))
@settings(max_examples=8, deadline=None)
def test_carus_random_traces_match_oracle(sew, backend, n_instr, seed):
    rng = np.random.default_rng(seed)
    cfg = CarusConfig()
    vlmax = cfg.vlmax(sew)
    arith = list(CARUS_ARITH)
    kinds = arith + [VOp.VMACC, VOp.VMV, VOp.VSETVL, VOp.VNOP]
    entries = []
    for _ in range(n_instr):
        vop = kinds[rng.integers(len(kinds))]
        mode = int(rng.integers(3))             # vv / vx / vi, direct only
        entries.append(carus_entry(
            vop, vd=int(rng.integers(cfg.n_regs)),
            vs1=int(rng.integers(cfg.n_regs)),
            vs2=int(rng.integers(cfg.n_regs)),
            sval1=int(rng.integers(0, vlmax + 17)) if vop == VOp.VSETVL
            else int(rng.integers(-2**31, 2**31)),
            imm=int(rng.integers(-16, 16)), mode=mode))
    prog = Program.from_entries("carus", sew, entries).pad_to(CARUS_BUCKET)
    vrf = rng.integers(-2**31, 2**31, (cfg.n_regs, cfg.reg_words),
                       dtype=np.int64).astype(np.int32)
    got = _run_engine(prog, vrf, backend)
    exp = carus_oracle(vrf, prog)
    assert (got == exp).all(), \
        (sew, backend, seed, np.argwhere(got != exp)[:8])


# ---------------------------------------------------------------------------
# cross-engine differential: one abstract chain, both engines, one oracle
# ---------------------------------------------------------------------------

N_ELEMS = 32          # differential vector length (nw words = N*sew/32)

@pytest.mark.parametrize("sew", SEWS)
@given(n_ops=st.integers(1, 4), seed=st.integers(0, 2**16))
@settings(max_examples=6, deadline=None)
def test_cross_engine_chain_agrees(sew, n_ops, seed):
    """c_0 = a op_0 b; c_k = c_{k-1} op_k b — lowered to both engines from
    one spec, both must equal the shared numpy lane chain bit-exactly."""
    rng = np.random.default_rng(seed)
    chain = [COMMON_OPS[rng.integers(len(COMMON_OPS))] for _ in range(n_ops)]
    dt = alu.NP_DTYPES[sew]
    info = np.iinfo(dt)
    a = rng.integers(info.min, info.max + 1, N_ELEMS, dtype=dt)
    b = rng.integers(info.min, info.max + 1, N_ELEMS, dtype=dt)
    nw = N_ELEMS * sew // 32

    # shared numpy expectation: lanes chain, truncated at SEW each step
    cur = np.asarray(a, np.int64)
    b_l = np.asarray(b, np.int64)
    for _, _, name in chain:
        cur = alu.trunc_lanes_np(alu.lane_binop_np(name, cur, b_l, sew), sew)

    # NM-Caesar: a @ word 0 (bank 0), b @ 4096 (bank 1), chain results at
    # 1024 + k*nw; each abstract op is nw word-ops
    mem = np.zeros(CAESAR_MEM_WORDS, np.int32)
    mem[:nw] = alu.pack_np(a)
    mem[4096:4096 + nw] = alu.pack_np(b)
    centries, src = [], 0
    for k, (cop, _, _) in enumerate(chain):
        dst = 1024 + k * nw
        centries += [caesar_entry(cop, dst + i, src + i, 4096 + i)
                     for i in range(nw)]
        src = dst
    cprog = Program.from_entries("caesar", sew, centries).pad_to(128)
    cfinal = _run_engine(cprog, mem)
    caesar_out = alu.unpack_np(cfinal[src:src + nw], dt)

    # NM-Carus: a -> v1, b -> v2, chain in v3, v4, ...; vl = N_ELEMS
    cfg = CarusConfig()
    vrf = np.zeros((cfg.n_regs, cfg.reg_words), np.int32)
    vrf[1, :nw] = alu.pack_np(a)
    vrf[2, :nw] = alu.pack_np(b)
    kentries = [carus_entry(VOp.VSETVL, sval1=N_ELEMS)]
    vsrc = 1
    for k, (_, vop, _) in enumerate(chain):
        vd = 3 + k
        kentries.append(carus_entry(vop, vd=vd, vs1=2, vs2=vsrc,
                                    mode=isa.MODE_VV))
        vsrc = vd
    kprog = Program.from_entries("carus", sew, kentries).pad_to(8)
    kfinal = _run_engine(kprog, vrf)
    carus_out = alu.unpack_np(kfinal[vsrc][:nw], dt)

    exp = cur.astype(dt)
    assert (caesar_out == exp).all(), (sew, seed, chain)
    assert (carus_out == exp).all(), (sew, seed, chain)
    assert (caesar_out == carus_out).all()


# ---------------------------------------------------------------------------
# dispatch-path differential: the Pallas backend through the pools and the
# async queue must match the numpy oracles exactly like direct engine runs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sew", SEWS)
def test_backend_parity_sync_and_async_dispatch(sew):
    """Random Caesar/Carus waves through ``ResidentPool.dispatch`` (sync)
    and ``DispatchQueue.submit`` (async, double-buffered) on backend
    "pallas", checked against the numpy oracles and the scan dispatch
    path — the whole scheduler stack, not just ``Engine.run``."""
    from repro.nmc.pool import ResidentPool
    from repro.nmc.runtime import DispatchQueue

    rng = np.random.default_rng(sew)
    ops = list(CAESAR_BINOPS) + [CaesarOp.MAC_INIT, CaesarOp.MAC,
                                 CaesarOp.MAC_STORE]
    cprogs, cmems = [], []
    for _ in range(3):
        entries = [caesar_entry(ops[rng.integers(len(ops))],
                                int(rng.integers(CAESAR_MEM_WORDS)),
                                int(rng.integers(CAESAR_MEM_WORDS)),
                                int(rng.integers(CAESAR_MEM_WORDS)))
                   for _ in range(CAESAR_BUCKET - 1)]
        cprogs.append(Program.from_entries("caesar", sew, entries)
                      .pad_to(CAESAR_BUCKET))
        cmems.append(rng.integers(-2**31, 2**31, CAESAR_MEM_WORDS,
                                  dtype=np.int64).astype(np.int32))
    cfg = CarusConfig()
    kentries = [carus_entry(VOp.VSETVL, sval1=int(cfg.vlmax(sew) // 2))] + [
        carus_entry(list(CARUS_ARITH)[rng.integers(len(CARUS_ARITH))],
                    vd=int(rng.integers(cfg.n_regs)),
                    vs1=int(rng.integers(cfg.n_regs)),
                    vs2=int(rng.integers(cfg.n_regs)),
                    sval1=int(rng.integers(-2**31, 2**31)),
                    imm=int(rng.integers(-16, 16)),
                    mode=int(rng.integers(3)))
        for _ in range(CARUS_BUCKET - 2)]
    kprog = Program.from_entries("carus", sew, kentries).pad_to(CARUS_BUCKET)
    kvrf = rng.integers(-2**31, 2**31, (cfg.n_regs, cfg.reg_words),
                        dtype=np.int64).astype(np.int32)

    oracles = [caesar_oracle(m, p) for p, m in zip(cprogs, cmems)] \
        + [carus_oracle(kvrf, kprog)]
    progs = cprogs + [kprog]
    images = cmems + [kvrf]

    for backend in BACKENDS:
        # sync: one resident wave across 4 tiles
        rp = ResidentPool(backend=backend)
        for t, (p, img) in enumerate(zip(progs, images)):
            rp.load(("t", t), p.engine, img)
        rp.dispatch([(("t", t), p) for t, p in enumerate(progs)])
        sync = [np.asarray(rp.state(("t", t)))
                for t in range(len(progs))]
        # async: same wave through the double-buffered queue
        q = DispatchQueue(pool=ResidentPool(backend=backend))
        futs = [q.submit(("t", t), p, image=img)
                for t, (p, img) in enumerate(zip(progs, images))]
        asyn = [np.asarray(f.state()) for f in futs]
        for got_s, got_a, exp, p in zip(sync, asyn, oracles, progs):
            exp = exp.reshape(got_s.shape)
            assert (got_s == exp).all(), (backend, "sync", p.engine, sew)
            assert (got_a == exp).all(), (backend, "async", p.engine, sew)
