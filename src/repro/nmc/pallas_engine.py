"""Pallas engine backend (DESIGN.md §10).

The scan engines (:mod:`repro.core.caesar` / :mod:`repro.core.carus`)
interpret one instruction per ``lax.scan`` step with a ``lax.switch`` over
the opcode.  This module is the second implementation of the
:class:`repro.nmc.engine.Engine` protocol: it runs a bucketed
:class:`repro.nmc.program.Program` wave as **one** ``pl.pallas_call`` that
keeps each tile's state in on-chip memory for the whole instruction stream
— the paper's near-memory thesis applied to the simulator itself.

Kernel layout (one shape for both engines):

* **grid = (tiles, instruction chunks).**  Tiles are independent (the
  pool's vmap contract) and run on the ``"parallel"`` axis.  The stream is
  NOP-padded to whole chunks of :data:`CHUNK` instructions and cut on an
  ``"arbitrary"`` axis;
  the tile's state block keeps the same block index along it, so the state
  stays resident across chunks, and the accumulators (Caesar MAC/DOT,
  Carus VL) carry across chunks in SMEM scratch.
* **instruction stream → SMEM.**  Every stream field is an int32 SMEM block
  of one chunk (the tile axis squeezed).  The scalar core reads the fields
  of instruction ``i`` as scalars; at the largest bucket the registry and
  the resident block produce (16384) the stream never sits in SMEM whole.
* **NM-Caesar state → SMEM words.**  The 8192-word memory image is an SMEM
  block too (32 KiB of the 1 MiB): each bus micro-op reads two words and
  writes one, all scalar.  SIMD lanes are unpacked from the words with
  shifts, computed, and packed back — per-step truncation at SEW, exactly
  like the scan engine's pack/unpack.
* **NM-Carus state → VMEM element rows.**  The VRF is unpacked outside the
  kernel to sign-extended int32 elements, one register per ``(8, 128)``
  tile (1024 elements: the register at SEW 8; padded at SEW 16/32), so
  every vector op is lane-dense.  Results truncate to SEW at writeback.
  Slides are a dynamic flat roll (``pltpu.roll`` on lanes, then sublanes)
  plus masks in place of a gather.
* **opcode → select chain.**  Instructions stay *runtime data* (the
  bucketed compile cache keys on shape, never on contents), so every step
  computes each opcode's candidate and keeps one with a chain of selects
  on ``op`` — no branches, one state write per instruction.
* **SEW specialization.**  ``sew`` is static: the
  :class:`repro.nmc.pool.BucketedPool` cache key ``(engine, sew,
  instr-bucket, tile-bucket)`` of a Pallas pool maps one-to-one onto
  kernels.
  Each kernel is named ``caesar_sew<SEW>`` or ``carus_sew<SEW>``: the
  name the kernel's custom call carries in the compiled HLO and in a
  device trace.

The same kernels run in Pallas interpret mode when no TPU is attached — the
CPU test path only (:func:`interpret_mode`); with a TPU attached they always
compile natively.  Semantics are bit-exact vs the scan engines and the
``alu.*_np`` numpy oracles at SEW 8/16/32: property-fuzzed in
``tests/test_differential.py``, conformance-tested per opcode in
``tests/test_engines.py``, and compiled for a v5e in
``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import alu, isa
from repro.core.isa import CaesarOp, VOp
from repro.nmc.engine import CaesarTile, CarusTile
from repro.nmc.program import NOP_OP_ID

#: Instructions per grid step: one SMEM block per stream field (Carus: 8
#: fields x 4 KiB, double-buffered — 64 KiB of a v5e's 1 MiB SMEM).  XLA
#: lays a 1-D int32 array of 1024 words or more out in 1024-word tiles, and
#: Mosaic refuses a block that is smaller than its operand's tile, so no
#: stream block is shorter than this.
CHUNK = 1024
#: One Carus register as an int32 element tile (1024 = VLMAX at SEW 8).
ROWS, LANES = 8, 128
INT_MIN = -(1 << 31)

CAESAR_FIELDS = ("op", "dest", "src1", "src2")
CARUS_FIELDS = ("op", "vd", "vs1", "vs2", "sval1", "sval2", "imm", "mode")
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def interpret_mode() -> bool:
    """Pallas interpret mode exactly when no TPU is attached (CPU tests);
    a TPU always runs the compiled kernels."""
    return jax.default_backend() != "tpu"


def _trunc(x, sew: int):
    """Sign-extend the low SEW bits of int32 values (= pack then unpack)."""
    if sew == 32:
        return x
    s = 32 - sew
    return (x << s) >> s


def _sew_mask(sew: int) -> int:
    return -1 if sew == 32 else (1 << sew) - 1


def _flat_stream(arrays: dict, fields: tuple, nop: int
                 ) -> tuple[list, int]:
    """``[T, n]`` stream fields -> flat ``[T * n_pad]`` int32 columns and
    ``n_pad``, the stream NOP-padded to whole chunks."""
    cols = [jnp.asarray(arrays[f], jnp.int32) for f in fields]
    n = cols[0].shape[-1]
    n_pad = -(-n // CHUNK) * CHUNK
    if n_pad != n:
        cols = [jnp.pad(c, ((0, 0), (0, n_pad - n)),
                        constant_values=nop if f == "op" else 0)
                for f, c in zip(fields, cols)]
    return [c.reshape(-1) for c in cols], n_pad


def _stream_spec(n_instr: int) -> pl.BlockSpec:
    """Tile ``t``'s chunk ``c`` of one flat stream field, in SMEM."""
    per_tile = n_instr // CHUNK
    return pl.BlockSpec((CHUNK,), lambda t, c: (t * per_tile + c,),
                        memory_space=pltpu.SMEM)


# ---------------------------------------------------------------------------
# NM-Caesar: the flat word memory in SMEM, scalar micro-ops
# ---------------------------------------------------------------------------

_CAESAR_WRITES = tuple(sorted(int(o) for o in isa.CAESAR_STORE_OPS))


def _caesar_kernel(op_ref, dest_ref, s1_ref, s2_ref, mem_in_ref,
                   mem_ref, acc_ref, *, sew: int):
    n_lanes = 32 // sew
    mask = _sew_mask(sew)
    top = mem_ref.shape[0] - 1

    @pl.when(pl.program_id(1) == 0)
    def _load():
        def copy(w, carry):
            mem_ref[w] = mem_in_ref[w]
            return carry
        jax.lax.fori_loop(0, top + 1, copy, 0)
        acc_ref[0] = 0
        acc_ref[1] = 0

    def lanes(w):
        return [_trunc(w >> (sew * k), sew) for k in range(n_lanes)]

    def pack(ls):
        word = ls[0] & mask
        for k in range(1, n_lanes):
            word = word | ((ls[k] & mask) << (sew * k))
        return word

    def step(i, carry):
        mac, dot = carry
        op = op_ref[i]
        dest = dest_ref[i]
        a = mem_ref[jnp.clip(s1_ref[i], 0, top)]
        b = mem_ref[jnp.clip(s2_ref[i], 0, top)]
        is_init = op == int(CaesarOp.MAC_INIT)
        vals, macs, dsum = [], [], 0
        for ak, bk, mk in zip(lanes(a), lanes(b), lanes(mac)):
            prod = ak * bk
            sh = bk & (sew - 1)          # RVV: shift amount mod SEW
            m_new = jnp.where(is_init, 0, mk) + prod
            v = m_new                    # MAC_STORE stores the new MAC
            for o, r in ((CaesarOp.AND, ak & bk), (CaesarOp.OR, ak | bk),
                         (CaesarOp.XOR, ak ^ bk), (CaesarOp.ADD, ak + bk),
                         (CaesarOp.SUB, ak - bk), (CaesarOp.MUL, prod),
                         (CaesarOp.SLL, ak << sh),
                         (CaesarOp.SLR,
                          jax.lax.shift_right_logical(ak & mask, sh)),
                         (CaesarOp.SRA, ak >> sh),
                         (CaesarOp.MIN, jnp.where(ak < bk, ak, bk)),
                         (CaesarOp.MAX, jnp.where(ak > bk, ak, bk))):
                v = jnp.where(op == int(o), r, v)
            vals.append(v)
            macs.append(m_new)
            dsum = dsum + prod
        is_mac = (op >= int(CaesarOp.MAC_INIT)) & \
            (op <= int(CaesarOp.MAC_STORE))
        mac = jnp.where(is_mac, pack(macs), mac)
        is_dot = (op >= int(CaesarOp.DOT_INIT)) & \
            (op <= int(CaesarOp.DOT_STORE))
        dot_new = jnp.where(op == int(CaesarOp.DOT_INIT), 0, dot) + dsum
        dot = jnp.where(is_dot, dot_new, dot)
        val = jnp.where(op == int(CaesarOp.DOT_STORE), dot, pack(vals))
        writes = (dest >= 0) & (dest <= top) & functools.reduce(
            jnp.logical_or, [op == o for o in _CAESAR_WRITES])
        d = jnp.clip(dest, 0, top)
        mem_ref[d] = jnp.where(writes, val, mem_ref[d])
        return mac, dot

    acc_ref[0], acc_ref[1] = jax.lax.fori_loop(
        0, CHUNK, step, (acc_ref[0], acc_ref[1]))


@functools.lru_cache(maxsize=None)
def _caesar_call(sew: int, n_tiles: int, n_instr: int, mem_words: int,
                 interpret: bool):
    mspec = pl.BlockSpec((mem_words,), lambda t, c: (t,),
                         memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_caesar_kernel, sew=sew),
        grid=(n_tiles, n_instr // CHUNK),
        in_specs=[_stream_spec(n_instr)] * len(CAESAR_FIELDS) + [mspec],
        out_specs=mspec,
        out_shape=jax.ShapeDtypeStruct((n_tiles * mem_words,), jnp.int32),
        scratch_shapes=[pltpu.SMEM((2,), jnp.int32)],
        input_output_aliases={len(CAESAR_FIELDS): 0},
        compiler_params=_PARAMS,
        name=f"caesar_sew{sew}",
        interpret=interpret)


def caesar_wave(batch_state, arrays: dict, *, sew: int, interpret: bool):
    """``[T, mem_words]`` memory images + ``[T, n]`` stream fields -> final
    images: one ``pallas_call`` for the whole wave."""
    cols, n_instr = _flat_stream(arrays, CAESAR_FIELDS, NOP_OP_ID["caesar"])
    n_tiles, mem_words = batch_state.shape
    call = _caesar_call(sew, n_tiles, n_instr, mem_words, interpret)
    out = call(*cols, batch_state.astype(jnp.int32).reshape(-1))
    return out.reshape(n_tiles, mem_words)


# ---------------------------------------------------------------------------
# NM-Carus: the VRF as int32 element tiles in VMEM, vector micro-ops
# ---------------------------------------------------------------------------

def _flat_roll(x, sh, lane):
    """``out.flat[i] = x.flat[(i - sh) mod x.size]`` for an (R, 128)
    tile and a dynamic ``sh`` in ``[0, x.size)``: a lane roll, then the
    two sublane rolls its wrapped and unwrapped lanes need."""
    rows = x.shape[0]
    q, r = sh // LANES, sh % LANES
    y = pltpu.roll(x, r, 1)
    return jnp.where(lane >= r, pltpu.roll(y, q, 0),
                     pltpu.roll(y, (q + 1) % rows, 0))


def _splat_at(x, ids, idx: int):
    """Broadcast element ``x.flat[idx]`` over the tile."""
    m = jnp.where(ids == idx, x, INT_MIN)
    m = jnp.max(jnp.max(m, axis=1, keepdims=True), axis=0, keepdims=True)
    return jnp.broadcast_to(m, x.shape)


def _carus_kernel(op_ref, vd_ref, vs1_ref, vs2_ref, sval1_ref, sval2_ref,
                  imm_ref, mode_ref, vrf_in_ref, vrf_ref, vl_ref, *,
                  sew: int, n_elems: int):
    n_regs = vrf_ref.shape[0]
    mask = _sew_mask(sew)
    cid = isa.COMPACT_ID
    size = ROWS * LANES

    @pl.when(pl.program_id(1) == 0)
    def _load():
        vrf_ref[...] = vrf_in_ref[...]
        vl_ref[0] = n_elems               # VL starts at VLMAX

    shape = (ROWS, LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    ids = jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES + lane

    def step(i, vl):
        op, mode = op_ref[i], mode_ref[i]
        sval1, sval2 = sval1_ref[i], sval2_ref[i]
        indirect = (mode & isa.MODE_INDIRECT) != 0
        slide1 = (mode & isa.MODE_SLIDE1) != 0
        opmode = mode & 0x3
        # indirect register addressing: indices from sval2's LSBytes
        vd = jnp.where(indirect, (sval2 >> 16) & 0xFF, vd_ref[i]) % n_regs
        vs2 = jnp.where(indirect, (sval2 >> 8) & 0xFF, vs2_ref[i]) % n_regs
        vs1 = jnp.where(indirect, sval2 & 0xFF, vs1_ref[i]) % n_regs
        dst, s2, s1 = vrf_ref[vd], vrf_ref[vs2], vrf_ref[vs1]
        scalar_b = jnp.where(opmode == isa.MODE_VI, imm_ref[i], sval1)
        b = jnp.where(opmode == isa.MODE_VV, s1, scalar_b)
        # unsigned order of the SEW-bit zero-extensions
        au, bu = (s2 & mask) ^ INT_MIN, (b & mask) ^ INT_MIN
        sh = b & (sew - 1)
        # slides read vs2 at ids -/+ off, the index clipped to the register
        # (scan-engine gather semantics); MODE_SLIDE1 inserts the scalar at
        # the exposed edge element
        off = jnp.where(slide1, 1, scalar_b)
        idx = ids - off
        up = jnp.where(idx > n_elems - 1, _splat_at(s2, ids, n_elems - 1),
                       _flat_roll(s2, off % size, lane))
        up = jnp.where(idx >= 0, up, dst)
        up = jnp.where(slide1 & (ids == 0), sval1, up)
        idx = ids + off
        dn = jnp.where(idx < 0, _splat_at(s2, ids, 0),
                       _flat_roll(s2, -off % size, lane))
        dn = jnp.where(idx < vl, dn, 0)
        dn = jnp.where(slide1 & (ids == vl - 1), sval1, dn)
        val = dst
        for vop, r in ((VOp.VADD, s2 + b), (VOp.VSUB, s2 - b),
                       (VOp.VMUL, s2 * b), (VOp.VMACC, dst + s2 * b),
                       (VOp.VAND, s2 & b), (VOp.VOR, s2 | b),
                       (VOp.VXOR, s2 ^ b),
                       (VOp.VMIN, jnp.where(s2 < b, s2, b)),
                       (VOp.VMINU, jnp.where(au <= bu, s2, b)),
                       (VOp.VMAX, jnp.where(s2 > b, s2, b)),
                       (VOp.VMAXU, jnp.where(au >= bu, s2, b)),
                       (VOp.VSLL, s2 << sh),
                       (VOp.VSRL, jax.lax.shift_right_logical(s2 & mask, sh)),
                       (VOp.VSRA, s2 >> sh), (VOp.VMV, b),
                       (VOp.VSLIDEUP, up), (VOp.VSLIDEDOWN, dn),
                       # EMVV writes one element, full-length (ignores VL)
                       (VOp.EMVV, jnp.where(ids == sval2 % n_elems, sval1,
                                            dst))):
            val = jnp.where(op == cid[vop], r, val)
        # EMVX (scan-output only), VSETVL and VNOP leave the VRF untouched
        writes = op <= cid[VOp.EMVV]
        vl_eff = jnp.where(op == cid[VOp.EMVV], n_elems, vl)
        vrf_ref[vd] = jnp.where(writes & (ids < vl_eff), _trunc(val, sew),
                                dst)              # tail-undisturbed
        return jnp.where(op == cid[VOp.VSETVL],
                         jnp.minimum(sval1, n_elems), vl)

    vl_ref[0] = jax.lax.fori_loop(0, CHUNK, step, vl_ref[0])


@functools.lru_cache(maxsize=None)
def _carus_call(sew: int, n_tiles: int, n_instr: int, n_regs: int,
                n_elems: int, interpret: bool):
    vspec = pl.BlockSpec((None, n_regs, ROWS, LANES),
                         lambda t, c: (t, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_carus_kernel, sew=sew, n_elems=n_elems),
        grid=(n_tiles, n_instr // CHUNK),
        in_specs=[_stream_spec(n_instr)] * len(CARUS_FIELDS) + [vspec],
        out_specs=vspec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, n_regs, ROWS, LANES),
                                       jnp.int32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        input_output_aliases={len(CARUS_FIELDS): 0},
        compiler_params=_PARAMS,
        name=f"carus_sew{sew}",
        interpret=interpret)


def carus_wave(batch_state, arrays: dict, *, sew: int, interpret: bool):
    """``[T, n_regs, reg_words]`` VRFs + ``[T, n]`` stream fields -> final
    VRFs: unpack to element tiles, one ``pallas_call``, pack back."""
    cols, n_instr = _flat_stream(arrays, CARUS_FIELDS, NOP_OP_ID["carus"])
    n_tiles, n_regs, reg_words = batch_state.shape
    n_lanes = 32 // sew
    n_elems = reg_words * n_lanes
    assert n_elems <= ROWS * LANES and n_regs & (n_regs - 1) == 0, \
        (n_regs, reg_words, sew)
    elems = alu.unpack(batch_state, sew).reshape(n_tiles, n_regs, n_elems)
    elems = jnp.pad(elems, ((0, 0), (0, 0), (0, ROWS * LANES - n_elems)))
    call = _carus_call(sew, n_tiles, n_instr, n_regs, n_elems, interpret)
    out = call(*cols, elems.reshape(n_tiles, n_regs, ROWS, LANES))
    out = out.reshape(n_tiles, n_regs, ROWS * LANES)[..., :n_elems]
    return alu.pack(out.reshape(n_tiles, n_regs, reg_words, n_lanes), sew)


# ---------------------------------------------------------------------------
# Engine-protocol adapters
# ---------------------------------------------------------------------------

class _PallasMixin:
    """Shared plumbing: per-(sew, tiles) jit cache, scan_fn/run adapters."""

    backend = "pallas"
    _wave = None

    def _init_backend(self):
        self.interpret = interpret_mode()
        self._fns: dict = {}

    def batched_fn(self, sew: int, n_tiles: int):
        """``(batch_state[T, ...], batch_arrays[T, n]) -> batch_state`` —
        the pool-facing executor (one fused pallas_call per wave), jitted;
        the pool traces the function under the jit (``__wrapped__``) in
        place of ``vmap(scan_fn(sew))``."""
        key = (sew, n_tiles)
        fn = self._fns.get(key)
        if fn is None:
            fn = jax.jit(functools.partial(type(self)._wave, sew=sew,
                                           interpret=self.interpret))
            self._fns[key] = fn
        return fn

    def scan_fn(self, sew: int):
        """Single-tile flavor of :meth:`batched_fn` (grid of one).  The
        pool never vmaps this — ``TilePool._batched_fn`` prefers
        ``batched_fn`` — but it keeps the Engine protocol complete."""
        fn = self.batched_fn(sew, 1)

        def run_one(state, arrays):
            batch = {k: jnp.asarray(v)[None] for k, v in arrays.items()}
            return fn(jnp.asarray(state)[None], batch)[0]

        return run_one

    def run(self, state, program):
        assert program.engine == self.name, (program.engine, self.name)
        return self.scan_fn(program.sew)(state, program.lower())


class PallasCaesarEngine(_PallasMixin, CaesarTile):
    """NM-Caesar tile on the Pallas path: the 8192-word memory image
    resident in SMEM for the whole instruction stream."""

    _wave = staticmethod(caesar_wave)

    def __init__(self, config=None):
        super().__init__(config)
        self._init_backend()


class PallasCarusEngine(_PallasMixin, CarusTile):
    """NM-Carus tile on the Pallas path: the VRF resident in VMEM as one
    int32 element tile per register, VL carried through the stream."""

    _wave = staticmethod(carus_wave)

    def __init__(self, config=None):
        super().__init__(config)
        self._init_backend()
