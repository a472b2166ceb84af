"""Plain reference of a Qwen2-style dense decoder (Qwen1.5), in float32.

The published architecture, written straight: RMSNorm, rotary position
embeddings (half-split rotation), causal multi-head attention with
grouped key/value heads and q/k/v biases, a SwiGLU MLP, a final norm and
the LM head.  No kernels, no cache, no batching, every matrix product at
``Precision.HIGHEST``.  The weights are the served int8 weights times
their scales, exactly; activations stay float32.  Nothing here imports
the program.

``bits=4`` is the control: every GEMM's weights (per output channel) and
inputs (per row) rounded to symmetric 4-bit integers first, the step
below the int8 the configuration states.
"""

from __future__ import annotations

import functools

import numpy as np

from bench import costs


def _fake_quant(x, bits: int, axis: int):
    import jax.numpy as jnp
    top = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-12) / top
    return jnp.clip(jnp.round(x / s), -top, top) * s


def _forward(params, tokens, pos, *, d: costs.Dims, eps: float,
             theta: float, bits):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32

    def weight(p):
        w = p["w_q"].astype(f32) * p["scale"].astype(f32)[None, :]
        return w if bits is None else _fake_quant(w, bits, axis=0)

    def gemm(x, p):
        if bits is not None:
            x = _fake_quant(x, bits, axis=-1)
        y = jnp.matmul(x, weight(p), precision=hi)
        return y + p["b"] if "b" in p else y

    def rms(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g

    s = tokens.shape[0]
    hd = d.head_dim
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=f32) / hd))
    ang = jnp.arange(s, dtype=f32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(x):                                    # (S, heads, hd)
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    causal = jnp.tril(jnp.ones((s, s), bool))
    group = d.heads // d.kv_heads

    def layer(x, lp):
        h = rms(x, lp["ln1"]["g"])
        a = lp["attn"]
        q = rope(gemm(h, a["wq"]).reshape(s, d.heads, hd))
        k = rope(gemm(h, a["wk"]).reshape(s, d.kv_heads, hd))
        v = gemm(h, a["wv"]).reshape(s, d.kv_heads, hd)
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=hi) / np.sqrt(hd)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                       precision=hi).reshape(s, d.heads * hd)
        x = x + gemm(o, a["wo"])
        h = rms(x, lp["ln2"]["g"])
        m = lp["mlp"]
        up, gate = gemm(h, m["wi"]), gemm(h, m["wg"])
        return x + gemm(up * jax.nn.silu(gate), m["wo"]), None

    x = params["embed"]["table"][tokens].astype(f32)
    x, _ = jax.lax.scan(layer, x, params["layers"])
    h = rms(x[pos], params["final_norm"]["g"])
    return gemm(h, params["head"])


@functools.lru_cache(maxsize=None)
def _compiled(d: costs.Dims, eps: float, theta: float, bits):
    import jax
    return jax.jit(functools.partial(_forward, d=d, eps=eps, theta=theta,
                                     bits=bits))


def logits(params, cfg: dict, tokens, positions, pad_to: int,
           pos_pad: int, bits=None) -> np.ndarray:
    """Reference logits ``(len(positions), vocab)`` of the sequence
    ``tokens`` at ``positions``.  The sequence is padded to ``pad_to``
    (causality leaves the real positions untouched) and the positions to
    ``pos_pad``, so one compiled program serves every request."""
    tokens = np.asarray(tokens, np.int32)
    positions = np.asarray(positions, np.int32)
    tok = np.zeros(pad_to, np.int32)
    tok[:tokens.size] = tokens
    pos = np.zeros(pos_pad, np.int32)
    pos[:positions.size] = positions
    fn = _compiled(costs.dims(cfg), float(cfg["rms_norm_eps"]),
                   float(cfg["rope_theta"]), bits)
    return np.asarray(fn(params, tok, pos))[:positions.size]


def served_gaps(ref_logits: np.ndarray, served) -> np.ndarray:
    """How far below the reference's best each served token's logit lies."""
    served = np.asarray(served)
    rows = np.arange(served.size)
    return ref_logits.max(-1) - ref_logits[rows, served]
