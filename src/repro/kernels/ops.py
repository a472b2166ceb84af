"""Dispatching wrappers: Pallas on TPU, same-math XLA fallback elsewhere.

Models call these entry points.  On TPU hardware the Pallas kernels run; on
CPU (tests, this container) and in the multi-pod dry-run the mathematically
identical XLA path is used — deliberately, because (a) ``pallas_call`` has no
CPU lowering for compile-only, and (b) the roofline analysis reads FLOP/byte
attribution from XLA's cost model, which custom calls would hide.  Kernel
correctness is established separately in ``tests/test_kernels.py`` via
``interpret=True`` against :mod:`repro.kernels.ref`.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import nmc_matmul as _mm
from repro.kernels import ref

_BACKEND_IS_TPU = None


def backend_is_tpu() -> bool:
    global _BACKEND_IS_TPU
    if _BACKEND_IS_TPU is None:
        _BACKEND_IS_TPU = jax.default_backend() == "tpu"
    return _BACKEND_IS_TPU


def nmc_matmul(x_q, w_q, scale, bias=None, *, act: str = "none",
               out_dtype=jnp.bfloat16):
    """W8A8 matmul with fused epilogue (2-D operands)."""
    if backend_is_tpu():
        return _mm.nmc_matmul(x_q, w_q, scale, bias, act=act,
                              out_dtype=out_dtype)
    return ref.nmc_matmul(x_q, w_q, scale, bias, act=act, out_dtype=out_dtype)


def nmc_expert_matmul(x_q, w_q, scale, *, act: str = "none",
                      out_dtype=jnp.bfloat16):
    """A bank of W8A8 expert GEMMs (leading expert axis), one call."""
    if backend_is_tpu():
        return _mm.nmc_expert_matmul(x_q, w_q, scale, act=act,
                                     out_dtype=out_dtype)
    return ref.nmc_expert_matmul(x_q, w_q, scale, act=act,
                                 out_dtype=out_dtype)


def attention(q, k, v, *, causal=True, window=None, q_offset=0, scale=None):
    """Memory-safe attention: flash kernel on TPU, chunked lax fallback.
    ``scale`` multiplies the logits (``1/sqrt(head dim)`` where None)."""
    if backend_is_tpu():
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, scale=scale)
    return chunked_attention(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, scale=scale)


def chunked_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                      scale=None, kv_chunk: int = 1024):
    """Online-softmax attention as a lax.scan over KV chunks — the same math
    as the Pallas kernel, expressed in XLA ops.  Never materializes Sq x Skv;
    peak temp is Sq x kv_chunk per head.  Supports dv != dq (MLA)."""
    b, hq, sq, d = q.shape
    dv = v.shape[-1]
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    kv_chunk = min(kv_chunk, skv)
    if skv % kv_chunk:
        pad = kv_chunk - skv % kv_chunk
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = k.shape[2] // kv_chunk
    scale = 1.0 / jnp.sqrt(jnp.float32(d)) if scale is None \
        else jnp.float32(scale)

    qf = (q.astype(jnp.float32) * scale).reshape(b, hkv, group * sq, d)
    kc = k.reshape(b, hkv, nk, kv_chunk, d).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, hkv, nk, kv_chunk, dv).transpose(2, 0, 1, 3, 4)
    qpos = (jnp.arange(sq) + q_offset)
    qpos = jnp.tile(qpos, (group,))                       # (group*sq,)

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        kb, vb, j = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb.astype(jnp.float32))
        kpos = j * kv_chunk + jnp.arange(kv_chunk)
        mask = kpos[None, :] < skv
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > (qpos[:, None] - window)
        s = jnp.where(mask[None, None], s, -1e30)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p,
                                       vb.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hkv, group * sq, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hkv, group * sq, 1), jnp.float32)
    a0 = jnp.zeros((b, hkv, group * sq, dv), jnp.float32)
    with jax.named_scope("flashattn_fallback"):
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                      (kc, vc, jnp.arange(nk)))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l).reshape(b, hq, sq, dv)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, window=None):
    """Single-token decode attention against a (possibly padded) KV cache.

    q: (B, Hq, 1, D); caches: (B, Hkv, S, D); cache_len: (B,) valid lengths
    (the new token is at index cache_len - 1)."""
    b, hq, _, d = q.shape
    _, hkv, s, _ = k_cache.shape
    group = hq // hkv
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    qf = q.astype(jnp.float32).reshape(b, hkv, group, d) * scale
    logits = jnp.einsum("bhgd,bhkd->bhgk", qf, k_cache.astype(jnp.float32))
    kpos = jnp.arange(s)[None, :]
    mask = kpos < cache_len[:, None]
    if window is not None:
        mask &= kpos > (cache_len[:, None] - 1 - window)
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, hq, 1, v_cache.shape[-1]).astype(q.dtype)
