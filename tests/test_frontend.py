"""Traced kernel frontend (DESIGN.md §7): engine auto-selection,
UnsupportedOnEngine diagnostics, and bit-exactness of traced kernels
against the pure-numpy oracle mirrors (``alu.*_np``) on both engines at
SEW 8/16/32, via both sync and async (DispatchQueue) call styles.

The kernel-library acceptance (all five legacy builders re-expressed
through the frontend, bit-exact on the full Table V sweep, both engines,
sync + async) is carried by tests/test_engines.py, tests/test_nmc_ir.py
and tests/test_runtime.py, which all consume the traced builders; this
file covers the frontend's own contract.
"""

import numpy as np
import pytest

from repro import nmc
from repro.core import alu, programs
from repro.nmc.engine import get_engine
from repro.nmc.frontend import LoweringError

ALL_SEWS = (8, 16, 32)
RNG = np.random.default_rng(42)

# one shared runtime for the module: sync + async share a jit cache
_RT = nmc.NmcRuntime()
# one runtime per executor, for the comparisons between them
_RT_BY_BACKEND = {bk: nmc.NmcRuntime(backend=bk) for bk in nmc.BACKENDS}


def _rand(n, sew, shape=None):
    info = np.iinfo(alu.NP_DTYPES[sew])
    return RNG.integers(info.min, info.max + 1, shape or n,
                        dtype=alu.NP_DTYPES[sew])


def _run_direct(lk):
    """Run a LoweredKernel straight on its functional engine (no pool)."""
    eng = get_engine(lk.engine)
    final = eng.run(eng.init_state(lk.mem), lk.program)
    return lk.post(eng.extract(final, lk.out_slice, lk.sew))


# ---------------------------------------------------------------------------
# Bit-exactness vs the alu.*_np oracle mirrors, both engines, all SEWs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sew", ALL_SEWS)
@pytest.mark.parametrize("engine", ["caesar", "carus"])
def test_fused_kernel_bit_exact_vs_numpy_oracle(engine, sew):
    """A fused body exercising add/sub/mul/mac/shift/min/max/relu and
    scalar broadcast: the engine output must equal the alu.*_np evaluation
    the tracer performs (NmcValue.value / CompiledKernel.oracle)."""
    n = 256
    x, y = _rand(n, sew), _rand(n, sew)

    @nmc.jit(sew=sew, runtime=_RT)
    def fused(t, x, y):
        a, b = t.load(x, bank=0), t.load(y)
        s = (a * 3 + b).max(0)             # scalar mul, add, relu
        d = (a - b).min(s)                 # sub, vector min
        m = nmc.mac(d, 2, s)               # elementwise mac: d + 2*s
        t.store(m >> 1)                    # arithmetic shift epilogue

    # independent numpy mirror of the body (int64 lanes, wrap at SEW)
    def w(v):
        return alu.trunc_lanes_np(v, sew)
    xa, ya = x.astype(np.int64), y.astype(np.int64)
    s = np.maximum(w(w(xa * 3) + ya), 0)
    d = np.minimum(w(xa - ya), s)
    exp = w(d + 2 * s) >> 1

    lk = fused.lower(x, y, engine=engine)
    got = _run_direct(lk)
    assert (got.astype(np.int64) == exp).all(), (engine, sew)
    assert (np.asarray(fused.oracle(x, y)).astype(np.int64) == exp).all()


@pytest.mark.parametrize("sew", ALL_SEWS)
def test_sync_and_async_call_styles_bit_exact(sew):
    """CompiledKernel() vs call_async().result(): same engine path, same
    bucketed jit cache, bit-exact equal — on both engines."""
    x, y = _rand(128, sew), _rand(128, sew)

    @nmc.jit(sew=sew, runtime=_RT)
    def k(t, x, y):
        t.store((t.load(x, bank=0) ^ t.load(y)).max(1))

    for engine in ("caesar", "carus"):
        sync = k(x, y, engine=engine)
        fut = k.call_async(x, y, engine=engine)
        got = fut.result()
        assert (np.asarray(got) == np.asarray(sync)).all(), engine
        assert (np.asarray(sync) == k.oracle(x, y)).all(), engine


def test_unsigned_ops_and_slides_run_on_carus():
    x, y = _rand(64, 8), _rand(64, 8)

    @nmc.jit(runtime=_RT)
    def k(t, x, y):
        u = t.load(x).maxu(t.load(y))      # unsigned: Carus-only
        t.store(u.minu(100).slide_down(2))

    got = k(x, y)
    mask = (1 << 8) - 1
    xa, ya = x.astype(np.int64), y.astype(np.int64)
    u = np.where((xa & mask) >= (ya & mask), xa, ya)
    u = np.where((u & mask) <= 100, u, 100)
    exp = np.concatenate([u[2:], [0, 0]]).astype(np.int8)
    assert (np.asarray(got) == exp).all()


# ---------------------------------------------------------------------------
# Engine auto-selection + diagnostics
# ---------------------------------------------------------------------------

def test_auto_selects_caesar_for_bus_expressible_bodies():
    x = _rand(64, 8)

    @nmc.jit(runtime=_RT)
    def busk(t, x):
        v = t.load(x)
        t.store(((v + 1) * 2).max(0).min(100) >> 1)

    assert busk.select_engine(x) == "caesar"
    assert busk.lower(x).engine == "caesar"


def test_auto_falls_back_to_carus_for_unsigned_and_computed_slides():
    x = _rand(64, 8)

    @nmc.jit(runtime=_RT)
    def unsigned(t, x):
        t.store(t.load(x).maxu(0))

    @nmc.jit(runtime=_RT)
    def computed_slide(t, x):
        t.store((t.load(x) + 1).slide_down(1))

    assert unsigned.select_engine(x) == "carus"
    assert computed_slide.select_engine(x) == "carus"
    # slides of *loaded* values are bus-expressible (shifted data replicas)
    @nmc.jit(runtime=_RT)
    def loaded_slide(t, x):
        t.store(t.load(x).slide_down(1) + 0)

    assert loaded_slide.select_engine(x) == "caesar"


def test_unsupported_on_engine_names_the_offending_op():
    x = _rand(64, 8)

    @nmc.jit(runtime=_RT)
    def unsigned(t, x):
        t.store(t.load(x).minu(5))

    with pytest.raises(nmc.UnsupportedOnEngine) as ei:
        unsigned.lower(x, engine="caesar")
    assert ei.value.op == "minu" and ei.value.engine == "caesar"
    assert "minu" in str(ei.value)

    @nmc.jit(runtime=_RT)
    def computed_slide(t, x):
        t.store((t.load(x) * 2).slide_down(3))

    with pytest.raises(nmc.UnsupportedOnEngine) as ei:
        computed_slide.lower(x, engine="caesar")
    assert ei.value.op == "slide_down"


def test_carus_register_spanning_slide_is_diagnosed():
    x = _rand(4096, 8)                      # 1024 words > 256-word register

    @nmc.jit(runtime=_RT)
    def k(t, x):
        t.store((t.load(x) + 1).slide_down(1))

    with pytest.raises(nmc.UnsupportedOnEngine) as ei:
        k.lower(x, engine="carus")
    assert ei.value.op == "slide_down" and ei.value.engine == "carus"


def test_lowering_errors_are_informative():
    x = _rand(64, 8)

    @nmc.jit(runtime=_RT)
    def store_load(t, x):
        t.store(t.load(x))

    with pytest.raises(LoweringError, match="loaded value"):
        store_load.lower(x)

    @nmc.jit(runtime=_RT)
    def no_store(t, x):
        t.load(x)

    with pytest.raises(LoweringError, match="stored no"):
        no_store.lower(x)


# ---------------------------------------------------------------------------
# Lowering structure: the traced kernel library keeps the paper's shape
# ---------------------------------------------------------------------------

def test_traced_matmul_has_conflict_free_mac_chains():
    """The Table V matmul: every Caesar MAC reads the splatted tap from
    bank 0 and the B row from bank 1 — zero same-bank penalties."""
    kb = programs.build("matmul", 8)
    from repro.core import timing
    rep = timing.program_cycles(kb.caesar.program, 0.0)
    assert rep.detail["same_bank_ops"] == 0
    # Carus: one VSETVL + per-tap EMVX + VMUL/VMACC
    ops = kb.carus.program.vops()
    from repro.core.isa import VOp
    assert ops[0] == VOp.VSETVL
    assert ops.count(VOp.EMVX) == 64 and ops.count(VOp.VMACC) == 56


def test_store_trim_bounds_emission_and_output():
    """t.store(v, n=...) trims the logical output (conv2d's 'valid' region)
    and, on Caesar, the emitted word count."""
    x = _rand(64, 32)

    @nmc.jit(sew=32, runtime=_RT)
    def k(t, x):
        t.store(t.load(x) + 1, n=61)

    lk = k.lower(x, engine="caesar")
    assert lk.program.n_instr == 61         # demand-trimmed word loop
    assert lk.oracle.shape == (61,)
    got = _run_direct(lk)
    assert (got == (x[:61] + 1)).all()


def test_stored_slide_replica_lands_in_caesar_output_window():
    """A stored slide_down lowers on Caesar to a data replica placed
    directly in the output window (regression: the replica used to be
    re-allocated in bank 1, leaving the extracted window all-zero)."""
    x = _rand(64, 8)

    @nmc.jit(runtime=_RT)
    def k(t, x):
        t.store(t.load(x).slide_down(1))

    exp = np.concatenate([x[1:], [0]]).astype(np.int8)
    for engine in ("caesar", "carus"):
        got = np.asarray(k(x, engine=engine))
        assert (got == exp).all(), engine
    assert (k.oracle(x) == exp).all()


def test_mac_with_loaded_accumulator_copies_on_carus():
    """nmc.mac with a loaded (non-chain) accumulator is valid on both
    engines (regression: Carus used to raise 'accumulator and output
    block diverged' instead of emitting the VMV copy)."""
    c, a, b = _rand(64, 8), _rand(64, 8), _rand(64, 8)

    @nmc.jit(runtime=_RT)
    def axpy(t, c, a, b):
        t.store(nmc.mac(t.load(c, bank=0), t.load(a), t.load(b)))

    exp = (c.astype(np.int64) + a.astype(np.int64) * b.astype(np.int64)
           ).astype(np.int8)
    for engine in ("caesar", "carus"):
        got = np.asarray(axpy(c, a, b, engine=engine))
        assert (got == exp).all(), engine


def test_repeated_calls_keep_resident_state_bounded():
    """Kernel calls share the runtime's jit tile: N calls must not grow
    the resident pool by N tile memories (regression: every call used to
    leak one full tile buffer)."""
    rt = nmc.NmcRuntime()
    x = _rand(64, 8)

    @nmc.jit(runtime=rt)
    def k(t, x):
        t.store(t.load(x) + 1)

    before = len(rt.resident.tiles)
    outs = [np.asarray(k(x)) for _ in range(6)]
    futs = [k.call_async(x) for _ in range(3)]
    outs += [np.asarray(f.result()) for f in futs]
    assert len(rt.resident.tiles) == before + 1     # the shared jit tile
    exp = (x.astype(np.int64) + 1).astype(np.int8)
    assert all((o == exp).all() for o in outs)


def test_lowering_error_on_public_surface():
    assert nmc.LoweringError is LoweringError
    assert "LoweringError" in nmc.__all__


def test_jit_kwargs_validate_eagerly():
    """A typo'd engine string, an unsupported sew, an impossible tile
    count or an unknown partition strategy must raise a named ValueError
    at decoration time — not a deep-stack assertion at first call."""
    def body(t, x):
        t.store(t.load(x) + 1)

    with pytest.raises(ValueError, match="engine 'ceasar'"):
        nmc.jit(body, engine="ceasar")
    with pytest.raises(ValueError, match="sew 12"):
        nmc.jit(body, sew=12)
    with pytest.raises(ValueError, match="sew"):
        nmc.jit(body, sew="8x")
    with pytest.raises(ValueError, match="tiles"):
        nmc.jit(body, tiles=0)
    with pytest.raises(ValueError, match="tiles"):
        nmc.jit(body, tiles="many")
    with pytest.raises(ValueError, match="partition"):
        nmc.jit(body, partition="diagonal")
    # per-call overrides validate identically (no deep-stack KeyError /
    # bare int() failure)
    k = nmc.jit(body, runtime=_RT)
    with pytest.raises(ValueError, match="tiles"):
        k.call_async(np.zeros(8, np.int8), tiles=-2)
    with pytest.raises(ValueError, match="tiles must be an int"):
        k.call_async(np.zeros(8, np.int8), tiles="many")
    with pytest.raises(ValueError, match="engine 'ceasar'"):
        k(np.zeros(8, np.int8), engine="ceasar")
    with pytest.raises(ValueError, match="engine 'ceasar'"):
        k(np.zeros(8, np.int8), engine="ceasar", tiles=2)
    # valid kwargs still construct
    assert nmc.jit(body, engine="carus", sew=16, tiles=4,
                   partition="axis").tiles == 4


def test_backend_kwarg_validates_eagerly():
    """An unknown backend must raise a ValueError naming the valid set
    where the executor is chosen: when the runtime or pool is built."""
    with pytest.raises(ValueError, match="backend 'bogus'.*scan.*pallas"):
        nmc.NmcRuntime(backend="bogus")
    with pytest.raises(ValueError, match="backend 'bogus'"):
        nmc.ResidentPool(backend="bogus")
    with pytest.raises(ValueError, match="backend"):
        nmc.NmcRuntime(backend=8)
    # valid spellings construct; 'auto' resolves to a registered backend
    assert nmc.NmcRuntime(backend="pallas").backend == "pallas"
    assert _RT.backend in nmc.BACKENDS


@pytest.mark.parametrize("sew", [8, 16, 32])
def test_backend_pallas_bit_exact_vs_scan(sew):
    """The same traced kernel on a Pallas runtime must equal the scan
    runtime bit-for-bit — sync call and async future."""
    x, y = _rand(128, sew), _rand(128, sew)

    def body(t, a, b):
        t.store((t.load(a, bank=0) + t.load(b)) * t.load(a, bank=0))

    ks = {bk: nmc.jit(body, sew=sew, runtime=rt)
          for bk, rt in _RT_BY_BACKEND.items()}
    ref = np.asarray(ks["scan"](x, y))
    assert (np.asarray(ks["pallas"](x, y)) == ref).all()
    assert (np.asarray(ks["pallas"].call_async(x, y).result()) == ref).all()


def test_mac_rejects_scalar_accumulator():
    """Regression: a non-traced accumulator used to be silently dropped
    (mac(5, a, b) computed a*b); it must raise instead."""
    x = _rand(16, 8)

    @nmc.jit(runtime=_RT)
    def k(t, x):
        v = t.load(x)
        t.store(nmc.mac(5, v, v))

    with pytest.raises(TypeError, match="accumulator"):
        k.lower(x)


def test_consts_indexing_normalizes_and_bounds_checks():
    """Regression: negative consts indices used to read outside the pool
    on the engines while the oracle indexed pythonically."""
    x = _rand(16, 8)

    @nmc.jit(runtime=_RT)
    def k(t, x):
        c = t.consts(np.array([2, 3], np.int8))
        t.store(t.load(x) * c[-1])

    exp = (x.astype(np.int64) * 3).astype(np.int8)
    for engine in ("caesar", "carus"):
        assert (np.asarray(k(x, engine=engine)) == exp).all(), engine

    @nmc.jit(runtime=_RT)
    def oob(t, x):
        c = t.consts(np.array([2, 3], np.int8))
        t.store(t.load(x) * c[2])

    with pytest.raises(IndexError):
        oob.lower(x)


def test_compiled_kernel_repr_and_value_introspection():
    x = _rand(8, 8)

    @nmc.kernel
    def k(t, x):
        v = t.load(x) + 0
        assert v.ne == 8
        assert (v.value == x).all()         # eager oracle evaluation
        t.store(v)

    assert "k" in repr(k)
    out = nmc.jit(k.fn, runtime=_RT)(x)
    assert (np.asarray(out) == x).all()
