"""IR optimizer tests (DESIGN.md §13): every rewrite rule on a kernel
that exhibits its slack, the translation-validation gate on an injected
miscompile, the nmc.jit(opt=...) wiring with per-call override, opt/check
memo behavior (including LRU eviction + re-verification), the residency
hazard pass, and the ``python -m repro.nmc.check`` CLI exit codes and
JSON report schema.
"""

import copy
import json

import numpy as np
import pytest

from repro import nmc
from repro.core import alu, programs, timing
from repro.core.isa import CaesarOp, VOp
from repro.nmc import check, opt
from repro.nmc.engine import get_engine
from repro.nmc.opt import rules
from repro.nmc.opt.rules import Work
from repro.nmc.opt.validate import OptError, reference_output, validate
from repro.nmc.program import (PROG_DTYPE, Program, caesar_entry,
                               carus_entry, nop_entry)

ALL_SEWS = (8, 16, 32)
RNG = np.random.default_rng(11)

_RT = nmc.NmcRuntime()
_RT_BY_BACKEND = {bk: nmc.NmcRuntime(backend=bk) for bk in nmc.BACKENDS}


def _rand(n, sew):
    info = np.iinfo(alu.NP_DTYPES[sew])
    return RNG.integers(info.min, info.max + 1, n,
                        dtype=alu.NP_DTYPES[sew])


def _run_direct(lk):
    eng = get_engine(lk.engine)
    final = eng.run(eng.init_state(lk.mem), lk.program)
    return lk.post(eng.extract(final, lk.out_slice, lk.sew))


def axpy(t, c0, w, x):
    # written naively: the multi-use accumulator and unhinted bank
    # placement carry exactly the slack the optimizer reclaims
    t.store(nmc.mac(t.load(c0), t.load(w), t.load(x)))


def _axpy_args(sew, n=256):
    return tuple(_rand(n, sew) for _ in range(3))


def _cycles(lk):
    return timing.program_cycles(lk.program).cycles


# ---------------------------------------------------------------------------
# End-to-end: opt="O1" beats opt="off" on kernels with slack, bit-exactly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sew", ALL_SEWS)
def test_axpy_carus_copy_coalesce(sew):
    args = _axpy_args(sew)
    k = nmc.jit(axpy, engine="carus", sew=sew, runtime=_RT)
    off = k.lower(*args, opt="off")
    o1 = k.lower(*args)                 # default level is O1
    assert o1.opt_report is not None and off.opt_report is None
    assert "copy-coalesce" in {r.rule for r in o1.opt_report.rewrites}
    assert o1.program.n_instr < off.program.n_instr
    assert _cycles(o1) < _cycles(off)
    assert np.array_equal(_run_direct(o1), off.oracle)


@pytest.mark.parametrize("sew", ALL_SEWS)
def test_axpy_caesar_rebank(sew):
    args = _axpy_args(sew)
    k = nmc.jit(axpy, engine="caesar", sew=sew, runtime=_RT)
    off = k.lower(*args, opt="off")
    o1 = k.lower(*args)
    rep = o1.opt_report
    assert rep is not None and rep.moved > 0
    assert _cycles(o1) < _cycles(off)
    # rebank kills the same-bank penalty entirely on this kernel
    assert timing.program_cycles(o1.program).detail["same_bank_ops"] == 0
    assert np.array_equal(_run_direct(o1), off.oracle)


@pytest.mark.parametrize("sew", ALL_SEWS)
@pytest.mark.parametrize("backend", ("scan", "pallas"))
@pytest.mark.parametrize("engine", ("caesar", "carus"))
def test_opt_bit_exact_both_backends(engine, backend, sew):
    """Optimized and unoptimized programs agree through the full dispatch
    stack on both engines x both executors x every SEW (the acceptance
    matrix)."""
    args = _axpy_args(sew)
    k = nmc.jit(axpy, engine=engine, sew=sew,
                runtime=_RT_BY_BACKEND[backend])
    got_off = np.asarray(k(*args, opt="off"))
    got_o1 = np.asarray(k(*args))
    assert np.array_equal(got_off, got_o1)
    assert np.array_equal(got_o1, k.oracle(*args))


def test_gemm_registry_rebank_five_percent():
    """The paper's GEMM (Table V) lowers with its splat epilogue constants
    in the accumulator bank: bank-aware placement wins >= 5% modeled
    cycles with bit-exact output."""
    eb = programs.build("gemm", 8).caesar
    lk = copy.deepcopy(eb.lowered)
    before = timing.program_cycles(lk.program).cycles
    rep = opt.optimize(lk)
    assert rep is not None and rep.validated >= 1
    after = timing.program_cycles(lk.program).cycles
    assert after <= 0.95 * before
    assert np.array_equal(_run_direct(lk), eb.oracle)


def test_wave_shards_optimize_before_bucket():
    args = _axpy_args(8)
    k = nmc.jit(axpy, engine="carus", sew=8, runtime=_RT, tiles=2)
    _, lks_off = k.lower_wave(*args, opt="off")
    _, lks_o1 = k.lower_wave(*args)
    assert all(lk.opt_report is not None for lk in lks_o1)
    assert max(lk.program.n_instr for lk in lks_o1) \
        <= max(lk.program.n_instr for lk in lks_off)
    got = np.asarray(k(*args, tiles=2))
    assert np.array_equal(got, k.oracle(*args))


def test_opt_kwarg_validates_eagerly():
    with pytest.raises(ValueError, match="opt level 'O9'"):
        nmc.jit(axpy, opt="O9")
    k = nmc.jit(axpy, runtime=_RT)
    with pytest.raises(ValueError, match="opt level 'O2'"):
        k.lower(*_axpy_args(8), opt="O2")


def test_optimized_lowering_metadata_consistent():
    args = _axpy_args(8)
    k = nmc.jit(axpy, engine="carus", sew=8, runtime=_RT)
    lk = k.lower(*args)
    assert lk.opt_report is not None
    assert lk.prov is None or len(lk.prov) == len(lk.stream)
    assert not check.verify_lowered(lk).errors
    assert lk.program.n_instr == len(lk.stream)


# ---------------------------------------------------------------------------
# Rule units on hand-built Work items
# ---------------------------------------------------------------------------

def _caesar_work(entries, out_slice=(16, 4), init_spans=(), mem_words=64,
                 used_words=64):
    mem = np.zeros(mem_words, np.int32)
    return Work(engine="caesar", sew=8,
                entries=np.array(entries, dtype=PROG_DTYPE), mem=mem,
                out_slice=out_slice, init_spans=list(init_spans),
                cpool_spans=(), used_words=used_words, prov=None)


def test_dead_write_elim_drops_unobserved_store():
    w = _caesar_work([
        caesar_entry(CaesarOp.XOR, dest=40, src1=0, src2=1),   # dead
        caesar_entry(CaesarOp.ADD, dest=16, src1=0, src2=1),   # out word
    ])
    stats = rules.dead_write_elim(w)
    assert stats == {"removed": 1}
    assert len(w.entries) == 1 and int(w.entries["op"][0]) == int(CaesarOp.ADD)


def test_dead_write_elim_overwritten_store_dies():
    w = _caesar_work([
        caesar_entry(CaesarOp.ADD, dest=16, src1=0, src2=1),   # overwritten
        caesar_entry(CaesarOp.XOR, dest=16, src1=2, src2=3),   # survives
    ])
    assert rules.dead_write_elim(w) == {"removed": 1}
    assert int(w.entries["op"][0]) == int(CaesarOp.XOR)


def test_dead_write_elim_trims_whole_mac_cone():
    """A MAC chain whose store nobody observes is removed as a unit — a
    partial trim would change the accumulator for surviving stores."""
    w = _caesar_work([
        caesar_entry(CaesarOp.MAC_INIT, dest=0, src1=0, src2=1),
        caesar_entry(CaesarOp.MAC, dest=0, src1=2, src2=3),
        caesar_entry(CaesarOp.MAC_STORE, dest=48, src1=4, src2=5),  # dead
        caesar_entry(CaesarOp.ADD, dest=16, src1=0, src2=1),
    ])
    assert rules.dead_write_elim(w) == {"removed": 3}
    assert len(w.entries) == 1


def test_dead_write_elim_keeps_live_mac_cone():
    w = _caesar_work([
        caesar_entry(CaesarOp.MAC_INIT, dest=0, src1=0, src2=1),
        caesar_entry(CaesarOp.MAC_STORE, dest=16, src1=2, src2=3),  # out
    ])
    assert rules.dead_write_elim(w) is None
    assert len(w.entries) == 2


def test_dead_write_elim_carus_dead_final():
    ents = [carus_entry(VOp.VADD, vd=5, vs2=1, vs1=2),       # dead final
            carus_entry(VOp.VADD, vd=0, vs2=1, vs1=2)]       # output reg
    w = Work(engine="carus", sew=8,
             entries=np.array(ents, dtype=PROG_DTYPE),
             mem=np.zeros(32 * 32, np.int32), out_slice=(0, 4),
             init_spans=[], cpool_spans=(), used_words=0, prov=None)
    assert rules.dead_write_elim(w) == {"removed": 1}
    assert int(w.entries["dest"][0]) == 0


def test_nop_compact_strips_neutral_rows():
    w = _caesar_work([
        nop_entry("caesar"),
        caesar_entry(CaesarOp.ADD, dest=16, src1=0, src2=1),
        nop_entry("caesar"),
    ])
    assert rules.nop_compact(w) == {"removed": 2}
    assert len(w.entries) == 1


def test_vsetvl_dedup():
    from repro.core import constants as C
    vlmax = C.CARUS_REG_WORDS * (32 // 8)
    ents = [carus_entry(VOp.VSETVL, sval1=vlmax),            # re-requests VLMAX
            carus_entry(VOp.VADD, vd=0, vs2=1, vs1=2),
            carus_entry(VOp.VSETVL, sval1=8),                # observed: kept
            carus_entry(VOp.VADD, vd=0, vs2=1, vs1=2),
            carus_entry(VOp.VSETVL, sval1=4)]                # unobserved
    w = Work(engine="carus", sew=8,
             entries=np.array(ents, dtype=PROG_DTYPE),
             mem=np.zeros(32 * C.CARUS_REG_WORDS, np.int32), out_slice=(0, 4),
             init_spans=[], cpool_spans=(), used_words=0, prov=None)
    assert rules.vsetvl_dedup(w) == {"removed": 2}
    assert len(w.entries) == 3
    kept = w.entries[w.entries["op"] == w.entries["op"][1]]
    assert int(kept["sval1"][0]) == 8


def test_rebank_respects_cpool_and_out_spans():
    """Patched (cpool) spans and the output window never move, even when
    moving would win cycles — residency depends on their addresses."""
    ents = [caesar_entry(CaesarOp.ADD, dest=16, src1=0, src2=1)] * 4
    w = _caesar_work(ents, out_slice=(16, 4), init_spans=[(0, 1), (1, 1)],
                     mem_words=8192, used_words=32)
    w.cpool_spans = ((0, 1), (1, 1))
    assert rules.rebank(w) is None
    assert w.init_spans == [(0, 1), (1, 1)]


# ---------------------------------------------------------------------------
# Translation-validation gate: an optimizer bug must fail loudly
# ---------------------------------------------------------------------------

def _live_caesar_work():
    ents = [caesar_entry(CaesarOp.ADD, dest=16 + i, src1=i, src2=8 + i)
            for i in range(4)]
    w = _caesar_work(ents, out_slice=(16, 4), init_spans=[(0, 4), (8, 4)])
    w.mem[0:4] = [3, 5, 7, 9]           # values with carries, so an
    w.mem[8:12] = [1, 3, 5, 7]          # ADD->XOR tamper changes outputs
    return w


def test_validate_catches_semantic_tamper():
    w = _live_caesar_work()
    ref = reference_output("caesar", w.mem, w.entries, 8, w.out_slice)
    w.entries["op"][0] = int(CaesarOp.XOR)      # ADD -> XOR: miscompile
    with pytest.raises(OptError, match="miscompiled"):
        validate(w, ref, "tampered", "evil-rule")


def test_validate_catches_structurally_broken_rewrite():
    w = _live_caesar_work()
    ref = reference_output("caesar", w.mem, w.entries, 8, w.out_slice)
    w.entries["op"][0] = 63                     # not an opcode at all
    with pytest.raises(OptError, match="static verification"):
        validate(w, ref, "tampered", "evil-rule")


def test_injected_buggy_rule_raises_through_optimize(monkeypatch):
    """A rule that silently changes semantics is caught by the gate inside
    optimize() — the optimized artifact can never escape."""
    def evil(w):
        w.entries["src2"][0] += 1               # reads the wrong word
        return {"removed": 0}

    monkeypatch.setitem(rules.PIPELINE, "caesar",
                        (("evil-rule", evil),))
    opt.clear_memo()
    args = _axpy_args(8)
    k = nmc.jit(axpy, engine="caesar", sew=8, runtime=_RT)
    with pytest.raises(OptError, match="evil-rule"):
        k.lower(*args)
    opt.clear_memo()


# ---------------------------------------------------------------------------
# Memo behavior: optimizer LRU + check-memo eviction (satellite)
# ---------------------------------------------------------------------------

def test_optimize_memo_reuses_artifact():
    opt.clear_memo()
    args = _axpy_args(8)
    k = nmc.jit(axpy, engine="carus", sew=8, runtime=_RT)
    a = k.lower(*args)
    b = k.lower(*args)                  # memo hit: same content key
    assert a.opt_report == b.opt_report
    assert np.array_equal(np.array(a.stream, dtype=PROG_DTYPE),
                          np.array(b.stream, dtype=PROG_DTYPE))
    assert np.array_equal(np.asarray(a.mem), np.asarray(b.mem))


def test_check_memo_lru_eviction_and_reverify(monkeypatch):
    """verify_lowered's blake2b memo is LRU-bounded: filling past the cap
    evicts the oldest entry, and re-verifying an evicted lowering
    recomputes a correct (equal) report rather than serving stale or
    missing results."""
    monkeypatch.setattr(check, "_MEMO_CAP", 2)
    check.clear_memo()
    lks = [nmc.jit(axpy, engine="caesar", sew=8, runtime=_RT)
           .lower(*_axpy_args(8, n=n), opt="off", check="off")
           for n in (64, 128, 192)]   # distinct streams: distinct memo keys
    first = check.verify_lowered(lks[0])
    check.verify_lowered(lks[1])
    check.verify_lowered(lks[2])        # evicts lks[0]'s entry
    assert len(check._report_memo) == 2
    assert check._lowered_key(lks[0], lks[0].kernel or "k", None) \
        not in check._report_memo
    again = check.verify_lowered(lks[0])    # recomputed, not cached
    assert again is not first
    assert [d.rule for d in again.diagnostics] \
        == [d.rule for d in first.diagnostics]
    assert not again.errors
    check.clear_memo()


# ---------------------------------------------------------------------------
# Residency hazard pass
# ---------------------------------------------------------------------------

class _FakeLowered:
    def __init__(self, engine, entries, cpool_spans=(), init_spans=(),
                 sew=8):
        self.program = Program.from_entries(engine, sew, entries)
        self.cpool_spans = cpool_spans
        self.init_spans = init_spans
        self.kernel = "fake"
        self.prov = None


def test_verify_resident_rejects_carus():
    lk = _FakeLowered("carus", [carus_entry(VOp.VADD, vd=0, vs2=1, vs1=2)])
    rep = check.verify_resident(lk)
    assert rep.by_rule("engine-not-resident")


def test_verify_resident_patch_alias():
    lk = _FakeLowered(
        "caesar", [caesar_entry(CaesarOp.ADD, dest=64, src1=0, src2=8)],
        cpool_spans=((0, 8),), init_spans=((0, 8), (4, 8)))
    rep = check.verify_resident(lk)
    assert rep.by_rule("patch-aliases-weights")


def test_verify_resident_write_hazard():
    lk = _FakeLowered(
        "caesar", [caesar_entry(CaesarOp.ADD, dest=10, src1=0, src2=20)],
        init_spans=((8, 8),))
    rep = check.verify_resident(lk)
    d = rep.by_rule("resident-write-hazard")
    assert d and d[0].instr == 0


def test_verify_resident_clean():
    lk = _FakeLowered(
        "caesar", [caesar_entry(CaesarOp.ADD, dest=64, src1=0, src2=8)],
        cpool_spans=((0, 4),), init_spans=((0, 4), (8, 8)))
    assert not check.verify_resident(lk).diagnostics


def test_verify_chained_waves():
    ok = check.verify_chained_waves([[("r", 0, 0), ("r", 1, 0)],
                                     [("r", 2, 0)]])
    assert not ok.errors
    dup = check.verify_chained_waves([[7, 7]])
    assert dup.by_rule("war-hazard")
    shared = check.verify_chained_waves([[1, 2], [2, 3]])
    assert shared.by_rule("war-hazard")


def test_resident_projection_carries_hazard_reports():
    from repro.serve.block import ResidentProjection
    from repro.nmc.runtime import DispatchQueue
    from repro.nmc.pool import ResidentPool
    w8 = RNG.integers(-100, 100, (8, 16), dtype=np.int8)
    proj = ResidentProjection("t", w8, DispatchQueue(ResidentPool()),
                              rows=2, tiles=1)
    assert proj.hazard_reports and all(not r.errors
                                       for r in proj.hazard_reports)


# ---------------------------------------------------------------------------
# CLI: exit codes + JSON report schema (satellite)
# ---------------------------------------------------------------------------

def _run_cli(args):
    return check.main(args)


def test_cli_clean_sweep_exit_zero(tmp_path, capsys):
    out = tmp_path / "rep.json"
    rc = _run_cli(["--kernel", "xor", "--sew", "8", "--no-waves",
                   "--report", str(out)])
    assert rc == 0
    text = capsys.readouterr().out
    assert "0 error(s)" in text
    doc = json.loads(out.read_text())
    assert doc["schema"] == check.REPORT_SCHEMA == 1
    assert set(doc) == {"schema", "strict", "targets", "summary"}
    assert doc["summary"]["status"] == "ok"
    for t in doc["targets"]:
        assert set(t) == {"kernel", "sew", "engine", "n_instr", "errors",
                          "warnings", "status", "diagnostics"}
        assert t["status"] == "ok" and t["errors"] == 0


def test_cli_injected_error_exit_one(tmp_path, monkeypatch):
    """A corrupted registry build must flip the exit code to 1 and mark
    the target (and summary) as failed in the JSON report."""
    real_build = programs.build

    def corrupt(name, sew, **kw):
        kb = real_build(name, sew, **kw)
        kb.caesar.lowered.program.entries["op"][0] = 63   # bad opcode
        return kb

    monkeypatch.setattr(programs, "build", corrupt)
    check.clear_memo()
    out = tmp_path / "rep.json"
    rc = _run_cli(["--kernel", "xor", "--sew", "8", "--no-waves",
                   "--report", str(out)])
    assert rc == 1
    doc = json.loads(out.read_text())
    assert doc["summary"]["status"] == "fail"
    bad = [t for t in doc["targets"] if t["status"] == "fail"]
    assert bad and bad[0]["errors"] >= 1
    diags = bad[0]["diagnostics"]
    assert diags and set(diags[0]) == {"severity", "pass", "rule",
                                       "message", "kernel", "instr",
                                       "op_index"}
    assert any(d["rule"] == "bad-opcode" for d in diags)
    check.clear_memo()
