"""NM-Caesar / NM-Carus functional engines: bit-exact kernel verification,
indirect register addressing, VL masking, eCPU programmability, and the
engine-protocol conformance matrix (every opcode through every registered
backend implementation)."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import alu, carus, caesar, ecpu, isa, programs
from repro.core.isa import CaesarOp, VOp
from repro.nmc import engine as nmc_engine
from repro.nmc.program import Program, caesar_entry, carus_entry


@pytest.mark.parametrize("name", programs.ALL_KERNELS)
@pytest.mark.parametrize("sew", [8, 16, 32])
def test_kernel_bit_exact_both_engines(name, sew):
    # reduced sizes keep the scanned engines fast in CI
    kw = {}
    if name in ("xor", "add", "mul", "relu", "leaky_relu", "maxpool"):
        kw = {"caesar_bytes": 2048, "carus_bytes": 4096}
    kb = programs.build(name, sew, **kw)
    res = programs.verify(kb)
    assert res["caesar"], f"{name}/{sew}: Caesar mismatch"
    assert res["carus"], f"{name}/{sew}: Carus mismatch"


def test_indirect_equals_direct():
    """The paper's indirect register addressing: same instruction template
    with indices in a GPR must produce identical results to direct encoding."""
    vpu = carus.CarusVPU()
    rng = np.random.default_rng(0)
    a = rng.integers(-128, 128, 1024, dtype=np.int8)
    b = rng.integers(-128, 128, 1024, dtype=np.int8)
    vrf = np.zeros((32, 256), np.int32)
    vrf[1], vrf[2] = alu.pack_np(a), alu.pack_np(b)
    direct = carus.trace_to_arrays([
        carus.trace_entry(VOp.VSETVL, sval1=1024),
        carus.trace_entry(VOp.VADD, vd=3, vs1=1, vs2=2, mode=isa.MODE_VV)])
    indirect = carus.trace_to_arrays([
        carus.trace_entry(VOp.VSETVL, sval1=1024),
        carus.trace_entry(VOp.VADD, sval2=isa.pack_indices(3, 2, 1),
                          mode=isa.MODE_VV | isa.MODE_INDIRECT)])
    out1, _, _ = vpu.run_trace(jnp.asarray(vrf), direct, 8)
    out2, _, _ = vpu.run_trace(jnp.asarray(vrf), indirect, 8)
    assert (np.asarray(out1) == np.asarray(out2)).all()


@given(vl=st.integers(1, 512), sew=st.sampled_from([8, 16, 32]))
@settings(max_examples=12, deadline=None)
def test_vl_tail_undisturbed(vl, sew):
    """Elements at index >= VL must keep their previous value."""
    vpu = carus.CarusVPU()
    vlmax = vpu.cfg.vlmax(sew)
    vl = min(vl, vlmax)
    rng = np.random.default_rng(vl)
    vrf = rng.integers(-2**31, 2**31, (32, 256)).astype(np.int32)
    before = vrf[5].copy()
    tr = carus.trace_to_arrays([
        carus.trace_entry(VOp.VSETVL, sval1=vl),
        carus.trace_entry(VOp.VXOR, vd=5, vs1=1, vs2=2, mode=isa.MODE_VV)])
    out, _, _ = vpu.run_trace(jnp.asarray(vrf), tr, sew)
    got = alu.unpack_np(np.asarray(out[5]), np.int8 if sew == 8 else
                        np.int16 if sew == 16 else np.int32)
    prev = alu.unpack_np(before, got.dtype)
    assert (got[vl:] == prev[vl:]).all()


def test_caesar_bus_encoding_roundtrip():
    data, addr = isa.caesar_encode(CaesarOp.ADD, dest=7, src1=100, src2=4196)
    op, dest, s1, s2 = isa.caesar_decode(data, addr)
    assert (op, dest, s1, s2) == (CaesarOp.ADD, 7, 100, 4196)


def test_xvnmc_encoding_roundtrip():
    i = isa.VInstr(VOp.VMACC, True, 5, 3, isa.F3.OPIVX, 7)
    d = isa.xvnmc_decode(isa.xvnmc_encode(i))
    assert (d.funct6, d.indirect, d.vs2_f, d.vs1_f, d.funct3, d.vd_f) == \
        (VOp.VMACC, True, 5, 3, isa.F3.OPIVX, 7)


def test_ecpu_runs_assembled_indirect_loop():
    """Full programmability: the Section III-B1 loop as real RV32E+xvnmc."""
    src = """
        li   a0, 4
        li   t0, 1024
        vsetvli t1, t0, e8
        li   t2, 0x00140A00
        li   a1, 0x00010101
        li   t1, 0
    loop:
        xvnmc.vaddr.vv t2
        add  t2, t2, a1
        addi t1, t1, 1
        blt  t1, a0, loop
        halt
    """
    words = ecpu.assemble(src)
    vpu = carus.CarusVPU()
    rng = np.random.default_rng(1)
    a = rng.integers(-128, 128, 4096, dtype=np.int8)
    b = rng.integers(-128, 128, 4096, dtype=np.int8)
    vrf = np.zeros((32, 256), np.int32)
    for i in range(4):
        vrf[i] = alu.pack_np(a[i * 1024:(i + 1) * 1024])
        vrf[10 + i] = alu.pack_np(b[i * 1024:(i + 1) * 1024])
    cpu = ecpu.ECpu(vpu, jnp.asarray(vrf))
    cpu.load_program(words)
    cpu.run()
    got = np.concatenate([alu.unpack_np(np.asarray(cpu.vrf[20 + i]), np.int8)
                          for i in range(4)])
    assert (got == a + b).all()
    assert cpu.vector_retired == 5   # vsetvli + 4 vadd


def test_caesar_same_bank_timing_penalty():
    from repro.core import timing
    from repro.core.programs import EngineBuild
    both_diff = EngineBuild([(CaesarOp.ADD, 10, 0, 4096)] * 10,
                            np.zeros(8192, np.int32), (10, 1))
    both_same = EngineBuild([(CaesarOp.ADD, 10, 0, 1)] * 10,
                            np.zeros(8192, np.int32), (10, 1))
    t1 = timing.caesar_cycles(both_diff)
    t2 = timing.caesar_cycles(both_same)
    assert t2.cycles - t1.cycles == 10  # +1 cycle per same-bank op

# ---------------------------------------------------------------------------
# Engine-protocol conformance matrix (DESIGN.md §10): one small golden
# program per opcode, executed by every registered (engine, backend)
# implementation; every implementation must produce the scan reference's
# memory image bit-exactly.  Future backends get these checks for free by
# registering in repro.nmc.engine.implementations().
# ---------------------------------------------------------------------------

CONF_BUCKET = 16    # all golden programs pad here: one compile per variant


def _caesar_golden_cases():
    """(label, entries) per NM-Caesar opcode; addresses span both banks."""
    cases = []
    for op in (CaesarOp.AND, CaesarOp.OR, CaesarOp.XOR, CaesarOp.ADD,
               CaesarOp.SUB, CaesarOp.MUL, CaesarOp.SLL, CaesarOp.SLR,
               CaesarOp.SRA, CaesarOp.MIN, CaesarOp.MAX):
        cases.append((op.name.lower(), [
            caesar_entry(op, 100 + i, 7 * i, 4096 + 11 * i)
            for i in range(4)]))
    cases.append(("mac_chain", [
        caesar_entry(CaesarOp.MAC_INIT, 0, 3, 4096),
        caesar_entry(CaesarOp.MAC, 0, 5, 4098),
        caesar_entry(CaesarOp.MAC_STORE, 200, 9, 4100)]))
    cases.append(("dot_chain", [
        caesar_entry(CaesarOp.DOT_INIT, 0, 4, 4097),
        caesar_entry(CaesarOp.DOT, 0, 6, 4099),
        caesar_entry(CaesarOp.DOT_STORE, 201, 8, 4101)]))
    # CSRW and NOP must leave memory untouched (an ADD proves the stream
    # still executed around them)
    cases.append(("csrw_nop", [
        caesar_entry(CaesarOp.CSRW, 0, 1, 0),
        caesar_entry(CaesarOp.NOP, 0, 0, 0),
        caesar_entry(CaesarOp.ADD, 300, 1, 4097)]))
    return [("caesar", label, entries) for label, entries in cases]


def _carus_golden_cases():
    """(label, entries) per NM-Carus vector opcode, VL-restricted so the
    tail-undisturbed writeback is part of every golden image."""
    pre = [carus_entry(VOp.VSETVL, sval1=777)]    # < vlmax at every SEW
    cases = []
    for vop in (VOp.VADD, VOp.VSUB, VOp.VMUL, VOp.VAND, VOp.VOR, VOp.VXOR,
                VOp.VMIN, VOp.VMINU, VOp.VMAX, VOp.VMAXU, VOp.VSLL,
                VOp.VSRL, VOp.VSRA, VOp.VMACC):
        cases.append((vop.name.lower(), pre + [
            carus_entry(vop, vd=4, vs1=1, vs2=2, mode=isa.MODE_VV),
            carus_entry(vop, vd=5, vs2=2, sval1=-3, mode=isa.MODE_VX),
            carus_entry(vop, vd=6, vs2=3, imm=7, mode=isa.MODE_VI)]))
    cases.append(("vmv", pre + [
        carus_entry(VOp.VMV, vd=7, vs1=1, mode=isa.MODE_VV),
        carus_entry(VOp.VMV, vd=8, sval1=-120, mode=isa.MODE_VX)]))
    cases.append(("vslideup", pre + [
        carus_entry(VOp.VSLIDEUP, vd=9, vs2=2, sval1=5, mode=isa.MODE_VX),
        carus_entry(VOp.VSLIDEUP, vd=10, vs2=2, sval1=42,
                    mode=isa.MODE_VX | isa.MODE_SLIDE1)]))
    cases.append(("vslidedown", pre + [
        carus_entry(VOp.VSLIDEDOWN, vd=11, vs2=2, sval1=3, mode=isa.MODE_VX),
        carus_entry(VOp.VSLIDEDOWN, vd=12, vs2=2, sval1=-9,
                    mode=isa.MODE_VX | isa.MODE_SLIDE1)]))
    # offsets past either end of the register: the scan engine clips the
    # gather index, so the far edge element repeats
    cases.append(("vslide_clip", pre + [
        carus_entry(VOp.VSLIDEUP, vd=16, vs2=2, sval1=-7, mode=isa.MODE_VX),
        carus_entry(VOp.VSLIDEDOWN, vd=17, vs2=3, sval1=-11,
                    mode=isa.MODE_VX),
        carus_entry(VOp.VSLIDEUP, vd=18, vs2=2, sval1=5000,
                    mode=isa.MODE_VX),
        carus_entry(VOp.VSLIDEDOWN, vd=19, vs2=3, imm=-3,
                    mode=isa.MODE_VI)]))
    cases.append(("emvv_emvx", pre + [
        carus_entry(VOp.EMVV, vd=13, sval1=99, sval2=17),
        carus_entry(VOp.EMVX, vd=0, vs2=2, sval1=5)]))
    cases.append(("indirect", pre + [
        carus_entry(VOp.VADD, sval2=isa.pack_indices(14, 2, 1),
                    mode=isa.MODE_VV | isa.MODE_INDIRECT)]))
    cases.append(("vsetvl_vnop", [
        carus_entry(VOp.VSETVL, sval1=3),
        carus_entry(VOp.VNOP),
        carus_entry(VOp.VXOR, vd=15, vs1=1, vs2=2, mode=isa.MODE_VV)]))
    return [("carus", label, entries) for label, entries in cases]


CONFORMANCE_CASES = _caesar_golden_cases() + _carus_golden_cases()


def _conformance_state(engine_name: str, sew: int) -> np.ndarray:
    rng = np.random.default_rng(sew)
    if engine_name == "caesar":
        return rng.integers(-2**31, 2**31, 8192,
                            dtype=np.int64).astype(np.int32)
    return rng.integers(-2**31, 2**31, (32, 256),
                        dtype=np.int64).astype(np.int32)


@pytest.mark.parametrize("engine_name,label,entries", CONFORMANCE_CASES,
                         ids=[f"{e}-{l}" for e, l, _ in CONFORMANCE_CASES])
@pytest.mark.parametrize("backend", nmc_engine.BACKENDS)
@pytest.mark.parametrize("sew", [8, 16, 32])
def test_engine_conformance(engine_name, label, entries, backend, sew):
    prog = Program.from_entries(engine_name, sew, entries) \
        .pad_to(CONF_BUCKET)
    state = _conformance_state(engine_name, sew)
    ref_eng = nmc_engine.get_engine(engine_name, "scan")
    ref = np.asarray(ref_eng.run(ref_eng.init_state(state), prog))
    eng = nmc_engine.get_engine(engine_name, backend)
    assert isinstance(eng, nmc_engine.Engine)
    got = np.asarray(eng.run(eng.init_state(state), prog))
    assert got.shape == ref.shape
    assert (got == ref).all(), \
        (engine_name, backend, label, sew,
         np.argwhere(got != ref)[:8].tolist())


def test_implementations_registry_is_complete():
    impls = nmc_engine.implementations()
    assert set(impls) == {(n, b) for n in ("caesar", "carus")
                          for b in nmc_engine.BACKENDS}
    for name, backend in impls:
        eng = nmc_engine.get_engine(name, backend)
        assert eng.name == name
        assert isinstance(eng, nmc_engine.Engine)
