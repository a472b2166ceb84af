"""Pallas kernels vs pure-jnp oracles (interpret=True shape/dtype sweeps)."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.nmc_matmul import nmc_expert_matmul, nmc_matmul
from repro.kernels.vrf_alu import make_prog, vrf_alu

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# nmc_matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 256, 128, 64, 64, 128),
    (256, 512, 256, 128, 256, 256),
    (64, 128, 512, 64, 128, 64),
])
@pytest.mark.parametrize("act", ["none", "relu", "silu"])
def test_nmc_matmul_shapes(m, k, n, bm, bn, bk, act):
    x = jnp.asarray(RNG.integers(-127, 128, (m, k), dtype=np.int8))
    w = jnp.asarray(RNG.integers(-127, 128, (k, n), dtype=np.int8))
    s = jnp.asarray(RNG.uniform(1e-3, 1e-2, n).astype(np.float32))
    b = jnp.asarray(RNG.normal(size=n).astype(np.float32))
    got = nmc_matmul(x, w, s, b, act=act, bm=bm, bn=bn, bk=bk,
                     interpret=True)
    exp = ref.nmc_matmul(x, w, s, b, act=act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m,k,n", [
    (4, 352, 200),      # K, N not multiples of 128: both zero-pad
    (5, 384, 1408),     # N = 11 * 128: bn falls to 128, odd M pads to 32
    (33, 2816, 256),    # qwen down-projection K = 22 * 128: bk = 256
    (200, 256, 384),    # a prefill M that is no multiple of the M tile
    (300, 128, 128),    # M past one default tile: pads to two
])
def test_nmc_matmul_any_shape_exact(m, k, n):
    """Shapes off the default tiles pad instead of asserting; int32
    accumulation stays exact (unit scale, no bias), and the served
    epilogue matches the reference."""
    x = jnp.asarray(RNG.integers(-127, 128, (m, k), dtype=np.int8))
    w = jnp.asarray(RNG.integers(-127, 128, (k, n), dtype=np.int8))
    one = jnp.ones((n,), jnp.float32)
    got = nmc_matmul(x, w, one, None, interpret=True)
    assert got.shape == (m, n)
    assert (np.asarray(got) == np.asarray(ref.nmc_matmul(x, w, one))).all()
    s = jnp.asarray(RNG.uniform(1e-3, 1e-2, n).astype(np.float32))
    b = jnp.asarray(RNG.normal(size=n).astype(np.float32))
    got = nmc_matmul(x, w, s, b, act="silu", interpret=True)
    exp = ref.nmc_matmul(x, w, s, b, act="silu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("e,m,k,n,bn,bk", [
    (3, 40, 256, 384, 128, 128),    # a grid step per expert, row, N, K tile
    (2, 9, 2048, 1408, 1536, 2048),  # DeepSeek-V2-Lite's up bank, decode
    (2, 5, 1408, 2048, 1536, 2048),  # its down bank: bn falls to 1024
])
@pytest.mark.parametrize("act", ["none", "silu"])
def test_nmc_expert_matmul_matches_reference(e, m, k, n, bn, bk, act):
    """Each expert's own weights, scales and activation scale; a bank
    that no row was routed to (all-zero input) gives zeros."""
    x = RNG.integers(-127, 128, (e, m, k), dtype=np.int8)
    x[1] = 0
    w = jnp.asarray(RNG.integers(-127, 128, (e, k, n), dtype=np.int8))
    s = jnp.asarray(RNG.uniform(1e-5, 1e-4, (e, n)).astype(np.float32))
    got = nmc_expert_matmul(jnp.asarray(x), w, s, act=act, bn=bn, bk=bk,
                            interpret=True)
    exp = ref.nmc_expert_matmul(jnp.asarray(x), w, s, act=act)
    assert got.shape == (e, m, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[1]).any()


def test_nmc_expert_matmul_is_nmc_matmul_per_expert():
    """Unit scales: the int32 sums are exact and equal ``nmc_matmul``'s on
    each expert's slice."""
    x = jnp.asarray(RNG.integers(-127, 128, (2, 33, 384), dtype=np.int8))
    w = jnp.asarray(RNG.integers(-127, 128, (2, 384, 256), dtype=np.int8))
    one = jnp.ones((2, 256), jnp.float32)
    got = np.asarray(nmc_expert_matmul(x, w, one, interpret=True))
    for i in range(2):
        want = np.asarray(ref.nmc_matmul(x[i], w[i], one[i]))
        assert (got[i] == want).all()


def test_nmc_matmul_int32_accumulation_exact():
    """Accumulation must be exact int32 (the paper's 32-bit MAC rule):
    worst-case +-127*127*K must not saturate or lose precision."""
    k = 1024
    x = jnp.full((128, k), 127, jnp.int8)
    w = jnp.full((k, 128), 127, jnp.int8)
    s = jnp.ones((128,), jnp.float32)
    got = nmc_matmul(x, w, s, None, bm=128, bn=128, bk=256, interpret=True)
    assert float(got[0, 0]) == 127 * 127 * k


def test_nmc_matmul_extreme_int8_epilogue_parity():
    """Worst-case int8 operands through the full epilogue (scale + bias +
    silu): accumulation stays exact int32 and the fused epilogue matches
    the reference within float tolerance."""
    k = 512
    x = jnp.asarray(RNG.choice(np.array([-128, -1, 127], np.int8), (64, k)))
    w = jnp.asarray(RNG.choice(np.array([-128, -1, 127], np.int8), (k, 128)))
    s = jnp.asarray(RNG.uniform(1e-4, 1e-3, 128).astype(np.float32))
    b = jnp.asarray(RNG.normal(size=128).astype(np.float32))
    got = nmc_matmul(x, w, s, b, act="silu", bm=64, bn=128, bk=128,
                     interpret=True)
    exp = ref.nmc_matmul(x, w, s, b, act="silu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-5, atol=1e-4)


def test_nmc_matmul_quantized_linear_accuracy():
    """End-to-end W8A8 path keeps ~1% relative error on typical weights."""
    rng = np.random.default_rng(42)
    d_in, d_out = 256, 512
    w = jnp.asarray(rng.normal(size=(d_in, d_out)).astype(np.float32)) * 0.05
    x = jnp.asarray(rng.normal(size=(64, d_in)).astype(np.float32))
    wq, sw = ref.quantize_rowwise(w)
    xq, sx = ref.quantize_dynamic(x)
    y = nmc_matmul(xq, wq, sw * sx, None, interpret=True, bm=64, bn=128,
                   bk=256)
    exact = x @ w
    rel = float(jnp.linalg.norm(y - exact) / jnp.linalg.norm(exact))
    assert rel < 0.015, rel


# ---------------------------------------------------------------------------
# vrf_alu
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.int8, np.int16, np.int32])
@pytest.mark.parametrize("block_vl", [128, 512])
def test_vrf_alu_program(dtype, block_vl):
    vrf = jnp.asarray(RNG.integers(-100, 100, (16, 512)).astype(dtype))
    prog = make_prog([
        ("add", 3, 1, 2, 0, ref.VRF_MODE_VV),
        ("mul", 4, 3, 3, 0, ref.VRF_MODE_VV),
        ("max", 5, 0, 4, 0, ref.VRF_MODE_VX),
        ("sra", 6, 0, 5, 3, ref.VRF_MODE_VX),
        ("xor", 7, 6, 5, 0, ref.VRF_MODE_VV),
        ("sub", 8, 7, 3, 0, ref.VRF_MODE_VV),
        ("mv", 9, 0, 0, -5, ref.VRF_MODE_VX),
        ("min", 10, 9, 8, 0, ref.VRF_MODE_VV),
    ])
    got = vrf_alu(vrf, prog, block_vl=block_vl, interpret=True)
    pd = {k: np.asarray(prog[:, i]) for i, k in
          enumerate(("op", "vd", "vs1", "vs2", "scalar", "mode"))}
    exp = ref.vrf_alu(vrf, pd)
    assert (np.asarray(got) == np.asarray(exp)).all()


@pytest.mark.parametrize("dtype", [np.int8, np.int16, np.int32])
def test_vrf_alu_wraparound_extremes(dtype):
    """Interpret-mode parity at the integer extremes: mul/add/sub/shift on
    saturating-looking inputs must wrap two's-complement, bit-exact with
    the reference (the NMC 'standard data types' contract)."""
    info = np.iinfo(dtype)
    vrf = np.zeros((16, 256), dtype)
    vrf[1, :] = info.min
    vrf[2, :] = info.max
    vrf[3, :] = np.tile(np.array([info.min, info.max, -1, 1], dtype), 64)
    prog = make_prog([
        ("mul", 4, 1, 2, 0, ref.VRF_MODE_VV),    # min*max wraps
        ("add", 5, 2, 4, 0, ref.VRF_MODE_VV),
        ("sub", 6, 1, 5, 0, ref.VRF_MODE_VV),
        ("mul", 7, 3, 3, 0, ref.VRF_MODE_VV),    # min^2 wraps to 0 at int8
        ("sll", 8, 0, 7, info.bits - 1, ref.VRF_MODE_VX),
        ("srl", 9, 0, 1, 1, ref.VRF_MODE_VX),
        ("sra", 10, 0, 1, 1, ref.VRF_MODE_VX),
        ("add", 11, 0, 2, 1, ref.VRF_MODE_VX),   # max+1 wraps to min
    ])
    got = vrf_alu(jnp.asarray(vrf), prog, block_vl=128, interpret=True)
    pd = {k: np.asarray(prog[:, i]) for i, k in
          enumerate(("op", "vd", "vs1", "vs2", "scalar", "mode"))}
    exp = ref.vrf_alu(jnp.asarray(vrf), pd)
    assert (np.asarray(got) == np.asarray(exp)).all()
    assert np.asarray(got)[11].flat[0] == info.min   # really wrapped


@given(n_instr=st.integers(1, 12), seed=st.integers(0, 2**16))
@settings(max_examples=10, deadline=None)
def test_vrf_alu_random_programs(n_instr, seed):
    """Property: arbitrary programs (program = data) match the oracle."""
    r = np.random.default_rng(seed)
    ops = list(ref.VRF_OPS)
    entries = [(ops[r.integers(len(ops))], int(r.integers(16)),
                int(r.integers(16)), int(r.integers(16)),
                int(r.integers(-100, 100)), int(r.integers(2)))
               for _ in range(n_instr)]
    vrf = jnp.asarray(r.integers(-100, 100, (16, 256)).astype(np.int16))
    prog = make_prog(entries)
    got = vrf_alu(vrf, prog, block_vl=128, interpret=True)
    pd = {k: np.asarray(prog[:, i]) for i, k in
          enumerate(("op", "vd", "vs1", "vs2", "scalar", "mode"))}
    exp = ref.vrf_alu(vrf, pd)
    assert (np.asarray(got) == np.asarray(exp)).all()


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,sq,skv,d,causal,win", [
    (2, 4, 2, 256, 256, 64, True, None),
    (1, 8, 2, 128, 512, 64, True, 128),
    (1, 4, 4, 128, 256, 32, False, None),
    (2, 2, 1, 64, 384, 128, True, None),
])
def test_flash_attention_configs(b, hq, hkv, sq, skv, d, causal, win):
    q = jnp.asarray(RNG.normal(size=(b, hq, sq, d)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(b, hkv, skv, d)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, hkv, skv, d)).astype(np.float32))
    got = flash_attention(q, k, v, causal=causal, window=win, bq=64, bk=128,
                          interpret=True)
    exp = ref.attention(q, k, v, causal=causal, window=win)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=2e-5)


@pytest.mark.parametrize("sq,skv,causal", [
    (7, 7, True),        # a short prompt: tiles shrink to 16 x 128
    (200, 200, True),    # past one q tile: q and kv both pad
    (130, 130, True),
    (7, 200, False),     # padded keys must stay masked without causality
])
def test_flash_attention_any_length(sq, skv, causal):
    """Prompt lengths off the default tiles pad instead of asserting."""
    q = jnp.asarray(RNG.normal(size=(1, 4, sq, 64)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(1, 2, skv, 64)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(1, 2, skv, 64)).astype(np.float32))
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    assert got.shape == q.shape
    exp = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=2e-5)


def test_flash_attention_gqa_window_combined():
    """GQA + sliding window + causal in one config (the serving attention
    shape), interpret-mode vs the plain-softmax reference."""
    q = jnp.asarray(RNG.normal(size=(2, 8, 192, 64)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(2, 2, 384, 64)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(2, 2, 384, 64)).astype(np.float32))
    got = flash_attention(q, k, v, causal=True, window=96, bq=64, bk=64,
                          interpret=True)
    exp = ref.attention(q, k, v, causal=True, window=96)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=2e-5)


def test_flash_attention_mla_dv_neq_dq():
    q = jnp.asarray(RNG.normal(size=(1, 4, 128, 192)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(1, 4, 128, 192)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(1, 4, 128, 128)).astype(np.float32))
    got = flash_attention(q, k, v, causal=True, bq=64, bk=64, interpret=True)
    exp = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=2e-5)


def test_chunked_fallback_matches_flash():
    from repro.kernels import ops
    q = jnp.asarray(RNG.normal(size=(2, 8, 256, 64)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(2, 2, 256, 64)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(2, 2, 256, 64)).astype(np.float32))
    a = ops.chunked_attention(q, k, v, causal=True, kv_chunk=64)
    b2 = flash_attention(q, k, v, causal=True, bq=64, bk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b2), atol=2e-5)
