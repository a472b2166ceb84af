"""Pallas TPU kernel: blocked online-softmax (flash) attention.

Near-memory pattern at the attention level: K/V tiles stream HBM->VMEM once;
the softmax statistics (running max / sum) and the output accumulator stay
resident in VMEM scratch across the whole KV reduction — logits (Sq x Skv)
are never materialized.  Required by the 32k/500k context shapes.

Supports GQA (q-head -> kv-head via index_map), causal masking, sliding
windows, and a q-position offset for chunked prefill.  Block shapes default
to (128, head_dim) q-tiles x (512, head_dim) kv-tiles; VMEM per step ~
bq*d + 2*bk*d + bq*bk floats << VMEM budget.  Any Sq / Skv runs: a short
sequence shrinks its tile (q to a multiple of 16, kv to a multiple of 128),
and the rest zero-pads to whole tiles, padded keys masked out and padded
query rows dropped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            nk: int, bq: int, bk: int, scale: float, causal: bool,
            window: int | None, q_offset: int, skv: int):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (bq, bk)

    qpos = (pl.program_id(1) * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0) + q_offset)
    kpos = kv_i * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < skv                                 # padded keys
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_cur = jnp.max(s, axis=1)[:, None]               # (bq, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                            # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)
    l_new = alpha * l_prev + jnp.sum(p, axis=1)[:, None]
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())))
    m_ref[...], l_ref[...] = m_new, l_new

    @pl.when(kv_i == nk - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)               # fully-masked rows -> 0
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "scale", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    q_offset: int = 0, scale: float | None = None,
                    bq: int = 128, bk: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Sq, D); k: (B, Hkv, Skv, D); v: (B, Hkv, Skv, Dv).
    Dv may differ from D (MLA).  ``scale`` multiplies the logits
    (``1/sqrt(D)`` where None)."""
    b, hq, sq, d = q.shape
    dv = v.shape[-1]
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    bq, bk = min(bq, _round_up(sq, 16)), min(bk, _round_up(skv, 128))
    sqp, skvp = _round_up(sq, bq), _round_up(skv, bk)
    grid = (b * hq, sqp // bq, skvp // bk)
    scale = float(1.0 / np.sqrt(d)) if scale is None else float(scale)

    pad = ((0, 0), (0, sqp - sq), (0, 0))
    qs = jnp.pad(q.reshape(b * hq, sq, d), pad)
    pad = ((0, 0), (0, skvp - skv), (0, 0))
    ks = jnp.pad(k.reshape(b * hkv, skv, d), pad)
    vs = jnp.pad(v.reshape(b * hkv, skv, dv), pad)

    def kv_map(h, i, j):
        return (h // group, j, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, nk=skvp // bk, bq=bq, bk=bk, scale=scale,
                          causal=causal, window=window, q_offset=q_offset,
                          skv=skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sqp, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qs, ks, vs)
    return out[:, :sq].reshape(b, hq, sq, dv)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m
