"""Attention layers: GQA (RoPE, optional bias/sliding-window) and MLA.

Three execution modes, shared across all transformer families:
  * train/prefill: blocked flash attention (Pallas on TPU, chunked-lax
    fallback — never materializes S x S);
  * decode: one-token attention against a donated KV cache;
  * MLA keeps the *compressed* (kv_lora + rope) cache and uses the absorbed
    formulation for decode — the cache stays (S, kv_lora+rope_dim) per token
    instead of (S, H * (nope+v)), DeepSeek-V2's core memory win.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.models import layers as L
from repro.models.config import ModelConfig

# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def gqa_init(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.head_dim
    return {
        "wq": L.linear_init(ks[0], d, cfg.n_heads * hd, bias=cfg.qkv_bias),
        "wk": L.linear_init(ks[1], d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias),
        "wv": L.linear_init(ks[2], d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias),
        "wo": L.linear_init(ks[3], cfg.n_heads * hd, d),
    }


def gqa_qkv(p, x, cfg: ModelConfig, positions):
    """x: (B, S, D) -> q (B,H,S,hd), k/v (B,KV,S,hd), rope applied."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = L.linear(p["wq"], x, nmc_mode=cfg.nmc_mode).reshape(
        b, s, cfg.n_heads, hd)
    k = L.linear(p["wk"], x, nmc_mode=cfg.nmc_mode).reshape(
        b, s, cfg.n_kv_heads, hd)
    v = L.linear(p["wv"], x, nmc_mode=cfg.nmc_mode).reshape(
        b, s, cfg.n_kv_heads, hd)
    if not cfg.learned_pos:
        cos, sin = L.rope_table(positions, hd, cfg.rope_theta,
                                  cfg.rope_scaling)
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
    return (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3))


def gqa_apply(p, x, cfg: ModelConfig, *, causal=True, q_offset=0,
              kv=None) -> jax.Array:
    """Train/prefill path.  `kv` overrides K/V (cross-attention)."""
    b, s, _ = x.shape
    positions = jnp.arange(s) + q_offset
    q, k, v = gqa_qkv(p, x, cfg, positions)
    if kv is not None:
        k, v = kv
    o = kops.attention(q, k, v, causal=causal, window=cfg.window,
                       q_offset=q_offset)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return L.linear(p["wo"], o, nmc_mode=cfg.nmc_mode)


def gqa_decode(p, x, cfg: ModelConfig, cache: dict, cache_len,
               layer=None) -> tuple:
    """One-token decode.  cache: {"k","v"} (and "k_s","v_s" for the int8
    cache): (B, KV, S_cache, hd) per leaf, or the whole stack (L, B, KV,
    S_cache, hd) with ``layer`` the index of this layer in it; cache_len
    (B,) absolute lengths.  The new token's row of each slot is written at
    (len-1) mod S_cache into the given buffers by :func:`_write_rows`, so a
    donated stack stays one buffer from the decode program's input to its
    output; attention then reads this layer's slice.  Sliding-window archs
    use a RING cache with S_cache == window: slots hold the last `window`
    tokens (softmax is permutation-invariant so slot order is irrelevant,
    and RoPE is applied with absolute positions before insert).
    Returns (out (B,1,D), the cache with the rows written)."""
    b = x.shape[0]
    positions = cache_len[:, None] - 1 + jnp.zeros((b, 1), jnp.int32)
    q = L.linear(p["wq"], x, nmc_mode=cfg.nmc_mode).reshape(
        b, 1, cfg.n_heads, cfg.head_dim)
    k = L.linear(p["wk"], x, nmc_mode=cfg.nmc_mode).reshape(
        b, 1, cfg.n_kv_heads, cfg.head_dim)
    v = L.linear(p["wv"], x, nmc_mode=cfg.nmc_mode).reshape(
        b, 1, cfg.n_kv_heads, cfg.head_dim)
    if not cfg.learned_pos:
        cos, sin = L.rope_table(positions, cfg.head_dim, cfg.rope_theta,
                                  cfg.rope_scaling)
        q = L.apply_rope(q, cos[:, :, None, :], sin[:, :, None, :])
        k = L.apply_rope(k, cos[:, :, None, :], sin[:, :, None, :])
    q = q.transpose(0, 2, 1, 3)
    s_cache = cache["k"].shape[-2]
    ring = cfg.window is not None and s_cache <= cfg.window
    idx = (cache_len - 1) % s_cache                         # (B,)
    if "k_s" in cache:                 # int8 quantized cache
        kq, ks = _quant_kv(k[:, 0])
        vq, vs = _quant_kv(v[:, 0])
        rows = {"k": kq, "v": vq, "k_s": ks, "v_s": vs}
    else:
        rows = {"k": k[:, 0], "v": v[:, 0]}
    # slot b's (KV, d) rows land at [layer, b, :, idx_b, :]
    cache = {n: _write_rows(c, rows[n], idx, layer, pos_axis=2)
             for n, c in cache.items()}
    here = {n: _layer_of(c, layer) for n, c in cache.items()}
    if "k_s" in cache:
        kc = _dequant_kv(here["k"], here["k_s"], x.dtype)
        vc = _dequant_kv(here["v"], here["v_s"], x.dtype)
    else:
        kc, vc = here["k"], here["v"]
    if ring:
        # every resident slot is within the window; mask only warmup slots
        o = kops.decode_attention(q, kc, vc,
                                  jnp.minimum(cache_len, s_cache),
                                  window=None)
    else:
        o = kops.decode_attention(q, kc, vc, cache_len, window=cfg.window)
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, -1)
    out = L.linear(p["wo"], o, nmc_mode=cfg.nmc_mode)
    return out, cache


def _write_rows(cache, rows, idx, layer=None, *, pos_axis: int):
    """Write one row per slot into ``cache`` in place: slot ``b``'s row
    goes to position ``idx[b]`` on the per-layer ``pos_axis`` (the batch
    axis is 0 there), of layer ``layer`` where ``cache`` is a stack of
    layers (``layer`` None: one layer's leaf).  ``rows`` holds every
    per-layer axis but the position one, (B, ..., row).

    A scatter that indexes every axis up to the position one and writes
    the last axis whole, so its operand needs no transpose and keeps the
    layout it came in: a donated cache is updated where it lies, and
    nothing of its size is rewritten or copied."""
    lead = jnp.indices(rows.shape[:pos_axis], sparse=True)   # b (, h)
    pos = idx.reshape(idx.shape + (1,) * (pos_axis - 1))
    at = (*lead, pos) if layer is None else (layer, *lead, pos)
    return cache.at[at].set(rows.astype(cache.dtype),
                            mode="promise_in_bounds", unique_indices=True)


def _layer_of(cache, layer=None):
    """Layer ``layer`` of a stacked cache leaf (the leaf itself for None)."""
    if layer is None:
        return cache
    return jax.lax.dynamic_index_in_dim(cache, layer, keepdims=False)


def gqa_cache_init(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> dict:
    shape = (batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    if cfg.kv_cache_dtype == "int8":
        # beyond-paper NMC extension: quantized decode state.  Per-token
        # per-head scales; cache bytes halve vs bf16 (scales are hd x smaller)
        sshape = shape[:-1] + (1,)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_s": jnp.zeros(sshape, jnp.bfloat16),
                "v_s": jnp.zeros(sshape, jnp.bfloat16)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _quant_kv(x):
    """(..., hd) -> int8 values + (..., 1) scale (symmetric per token/head)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    s = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127)
    return q.astype(jnp.int8), s.astype(jnp.bfloat16)


def _dequant_kv(q, s, dtype=jnp.bfloat16):
    return (q.astype(jnp.float32) * s.astype(jnp.float32)).astype(dtype)


def gqa_prefill(p, x, cfg: ModelConfig, max_len: int) -> tuple:
    """Prefill: full attention over the prompt AND build the cache."""
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q, k, v = gqa_qkv(p, x, cfg, positions)
    o = kops.attention(q, k, v, causal=True, window=cfg.window)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
    out = L.linear(p["wo"], o, nmc_mode=cfg.nmc_mode)
    pad = max_len - s
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _quant_kv(kp)
        vq, vs = _quant_kv(vp)
        return out, {"k": kq, "v": vq, "k_s": ks, "v_s": vs}
    cache = {"k": kp.astype(x.dtype), "v": vp.astype(x.dtype)}
    return out, cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 6)
    d, h = cfg.d_model, cfg.n_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    return {
        "wq": L.linear_init(ks[0], d, h * (dn + dr)),
        "w_dkv": L.linear_init(ks[1], d, r),            # compress
        "w_krope": L.linear_init(ks[2], d, dr),         # shared rope key
        "w_uk": L.linear_init(ks[3], r, h * dn),        # decompress K
        "w_uv": L.linear_init(ks[4], r, h * dv),        # decompress V
        "wo": L.linear_init(ks[5], h * dv, d),
        "norm_ckv": L.rmsnorm_init(r),
    }


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``1/sqrt(qk_nope + qk_rope)``, times YaRN's ``mscale^2`` where the
    model scales its rope (DeepSeek-V2's ``softmax_scale``)."""
    s = 1.0 / np.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    return s if cfg.rope_scaling is None else \
        s * cfg.rope_scaling.softmax_scale


def mla_apply(p, x, cfg: ModelConfig, *, q_offset=0) -> jax.Array:
    """Train/prefill: expanded (flash-compatible) formulation."""
    b, s, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    positions = jnp.arange(s) + q_offset
    cos, sin = L.rope_table(positions, dr, cfg.rope_theta, cfg.rope_scaling)

    q = L.linear(p["wq"], x, nmc_mode=cfg.nmc_mode).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(q_rope, cos, sin)

    ckv = L.rmsnorm(p["norm_ckv"],
                    L.linear(p["w_dkv"], x, nmc_mode=cfg.nmc_mode),
                    cfg.norm_eps)
    k_nope = L.linear(p["w_uk"], ckv, nmc_mode=cfg.nmc_mode).reshape(
        b, s, h, dn)
    v = L.linear(p["w_uv"], ckv, nmc_mode=cfg.nmc_mode).reshape(b, s, h, dv)
    k_rope = L.apply_rope(
        L.linear(p["w_krope"], x, nmc_mode=cfg.nmc_mode)[:, :, None, :],
        cos, sin)
    k_rope = jnp.broadcast_to(k_rope, (b, s, h, dr))

    q_full = jnp.concatenate([q_nope, q_rope], -1).transpose(0, 2, 1, 3)
    k_full = jnp.concatenate([k_nope, k_rope], -1).transpose(0, 2, 1, 3)
    v_t = v.transpose(0, 2, 1, 3)
    o = kops.attention(q_full, k_full, v_t, causal=True, q_offset=q_offset,
                       scale=mla_softmax_scale(cfg))
    o = o.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
    return L.linear(p["wo"], o, nmc_mode=cfg.nmc_mode)


def mla_cache_init(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> dict:
    return {"ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype)}


def mla_prefill(p, x, cfg: ModelConfig, max_len: int) -> tuple:
    b, s, _ = x.shape
    out = mla_apply(p, x, cfg)
    positions = jnp.arange(s)
    cos, sin = L.rope_table(positions, cfg.qk_rope_dim, cfg.rope_theta,
                            cfg.rope_scaling)
    ckv = L.rmsnorm(p["norm_ckv"],
                    L.linear(p["w_dkv"], x, nmc_mode=cfg.nmc_mode),
                    cfg.norm_eps)
    krope = L.apply_rope(
        L.linear(p["w_krope"], x, nmc_mode=cfg.nmc_mode)[:, :, None, :],
        cos, sin)[:, :, 0, :]
    pad = max_len - s
    cache = {
        "ckv": jnp.pad(ckv, ((0, 0), (0, pad), (0, 0))).astype(x.dtype),
        "krope": jnp.pad(krope, ((0, 0), (0, pad), (0, 0))).astype(x.dtype),
    }
    return out, cache


def mla_decode(p, x, cfg: ModelConfig, cache: dict, cache_len,
               layer=None) -> tuple:
    """Absorbed decode: attention runs in the compressed latent space —
    per-token cache cost is kv_lora_rank + rope_dim, not H*(nope+v).
    cache: {"ckv": (B, S, r), "krope": (B, S, rope)}, or the whole stack
    (L, B, S, ...) with ``layer`` this layer's index in it.  The new
    token's latent and rope key of each slot are written at len-1 into the
    given buffers (:func:`_write_rows`), so a donated stack stays one
    buffer; attention reads this layer's slice after the write.  Returns
    (out (B,1,D), the cache with the rows written)."""
    b = x.shape[0]
    h = cfg.n_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    positions = (cache_len - 1)[:, None]
    cos, sin = L.rope_table(positions, dr, cfg.rope_theta, cfg.rope_scaling)

    q = L.linear(p["wq"], x, nmc_mode=cfg.nmc_mode).reshape(b, 1, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(q_rope, cos[:, :, None, :], sin[:, :, None, :])

    ckv_new = L.rmsnorm(p["norm_ckv"],
                        L.linear(p["w_dkv"], x, nmc_mode=cfg.nmc_mode),
                        cfg.norm_eps)
    krope_new = L.apply_rope(
        L.linear(p["w_krope"], x, nmc_mode=cfg.nmc_mode)[:, :, None, :],
        cos[:, :, None, :], sin[:, :, None, :])[:, :, 0, :]

    # slot b's latent and rope key land at [layer, b, len_b - 1, :]
    idx = cache_len - 1
    cache = {"ckv": _write_rows(cache["ckv"], ckv_new[:, 0], idx, layer,
                               pos_axis=1),
             "krope": _write_rows(cache["krope"], krope_new[:, 0], idx,
                                 layer, pos_axis=1)}
    ckv_c = _layer_of(cache["ckv"], layer)
    krope_c = _layer_of(cache["krope"], layer)

    # absorb W_uk into q: q_lat (B,H,r) = q_nope @ W_uk(per head)
    w_uk = p["w_uk"]["w"].reshape(r, h, dn) if "w" in p["w_uk"] else (
        p["w_uk"]["w_q"].astype(jnp.float32)
        * p["w_uk"]["scale"][None, :]).reshape(r, h, dn)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scale = mla_softmax_scale(cfg)
    logits = (jnp.einsum("bhr,bsr->bhs", q_lat,
                         ckv_c.astype(jnp.float32))
              + jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32),
                           krope_c.astype(jnp.float32))) * scale
    mask = jnp.arange(ckv_c.shape[1])[None, :] < cache_len[:, None]
    logits = jnp.where(mask[:, None, :], logits, -1e30)
    pr = jax.nn.softmax(logits, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", pr, ckv_c.astype(jnp.float32))
    w_uv = p["w_uv"]["w"].reshape(r, h, dv) if "w" in p["w_uv"] else (
        p["w_uv"]["w_q"].astype(jnp.float32)
        * p["w_uv"]["scale"][None, :]).reshape(r, h, dv)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv.astype(jnp.float32))
    o = o.reshape(b, 1, h * dv).astype(x.dtype)
    out = L.linear(p["wo"], o, nmc_mode=cfg.nmc_mode)
    return out, cache
