"""Plain reference of a DeepSeek-V2 decoder as one chip of an
expert-parallel deployment holds it, in float32.

The published architecture, written straight: RMSNorm; multi-head latent
attention in its expanded form (the latent, normalized, expanded into
every head's key and value; a rope key shared by the heads; YaRN rope
frequencies and the softmax scale ``mscale^2 / sqrt(qk_nope + qk_rope)``);
the leading dense layers' SwiGLU MLP; in the others a float32 softmax
router over every published expert, greedy top-k with the weights
renormalized only where ``norm_topk_prob``, the routed part of the
experts held here, and the shared experts; a final norm and the LM head.
What the absent experts would add is left out, as in the program.  No
kernels, no cache, no batching, every matrix product at
``Precision.HIGHEST``.  The weights are the served int8 weights times
their scales, dequantized one layer at a time; activations stay float32.
Nothing here imports the program.

``bits=4`` is the control: every GEMM's weights (per output channel) and
inputs (per row) rounded to symmetric 4-bit integers first, the step
below the int8 the configuration states (the routers stay float32, as
served).  ``act_int8=True`` instead rounds every GEMM input to int8 with
one scale per input tensor (per expert input for the experts: the rows
routed to it), the arithmetic the configuration states; it reproduces
the program's activation rounding only over the same rows, so it is
compared with a prefill of the unpadded sequence.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from bench import costs_mla_moe
from bench.ref.qwen import _fake_quant, served_gaps  # noqa: F401


def yarn_inv_freq(dim: int, theta: float, rs: dict) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies for the rope dimension."""
    def corr(n):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (n * 2 * math.pi)) / (2 * math.log(theta))
    lo = max(math.floor(corr(rs["beta_fast"])), 0)
    hi = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    base = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return base / rs["factor"] * ramp + base * (1 - ramp)


def yarn_mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def _forward(params, tokens, pos, *, d: costs_mla_moe.Dims, eps: float,
             theta: float, rs: tuple, norm_topk: bool, bits, act_int8: bool):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    rs = dict(rs)

    def dequant(w_q, s):                 # (..., k, n) x (..., n)
        w = w_q.astype(f32) * s.astype(f32)[..., None, :]
        return w if bits is None else _fake_quant(w, bits, axis=-2)

    def act(x, over=None):
        """A GEMM's input as the arithmetic under test rounds it; ``over``
        is the tensor whose largest magnitude sets an int8 scale."""
        if bits is not None:
            return _fake_quant(x, bits, axis=-1)
        if act_int8:
            over = x if over is None else over
            s = jnp.maximum(jnp.max(jnp.abs(over)), 1e-8) / 127.0
            return jnp.clip(jnp.round(x / s), -127, 127) * s
        return x

    def gemm(x, p, over=None):
        return jnp.matmul(act(x, over), dequant(p["w_q"], p["scale"]),
                          precision=hi)

    def rms(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g

    s = tokens.shape[0]
    h, dn, dr, dv = d.heads, d.qk_nope, d.qk_rope, d.v_head
    ang = jnp.arange(s, dtype=f32)[:, None] * \
        jnp.asarray(yarn_inv_freq(dr, theta, rs), f32)[None, :]
    m = yarn_mscale(rs["factor"], rs["mscale"]) / \
        yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    cos, sin = (jnp.cos(ang) * m)[:, None, :], (jnp.sin(ang) * m)[:, None, :]
    scale = yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2 / \
        np.sqrt(dn + dr)

    def rope(x):                                    # (S, heads, dr)
        x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    causal = jnp.tril(jnp.ones((s, s), bool))

    def attention(x, a):
        q = gemm(x, a["wq"]).reshape(s, h, dn + dr)
        q_nope, q_rope = q[..., :dn], rope(q[..., dn:])
        ckv = rms(gemm(x, a["w_dkv"]), a["norm_ckv"]["g"])
        k_nope = gemm(ckv, a["w_uk"]).reshape(s, h, dn)
        v = gemm(ckv, a["w_uv"]).reshape(s, h, dv)
        k_rope = rope(gemm(x, a["w_krope"])[:, None, :])
        sc = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope, precision=hi)
              + jnp.einsum("qhd,kxd->hqk", q_rope, k_rope, precision=hi)
              ) * scale
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                       precision=hi).reshape(s, h * dv)
        return gemm(o, a["wo"])

    def mlp(x, p):
        return gemm(jax.nn.silu(gemm(x, p["wg"])) * gemm(x, p["wi"]),
                    p["wo"])

    def experts(x, p):
        """The held experts' routed part: each expert's SwiGLU over the
        rows routed to it, weighted by the router."""
        probs = jax.nn.softmax(jnp.matmul(x, p["router"]["w"].astype(f32),
                                          precision=hi), -1)
        top_p, top_e = jax.lax.top_k(probs, d.top_k)
        if norm_topk:
            top_p = top_p / top_p.sum(-1, keepdims=True)
        hit = top_e[:, :, None] == d.first + jnp.arange(d.held)
        gate = jnp.where(hit, top_p[..., None], 0.0).sum(1)      # (S, E_h)
        routed = hit.any(1).T[:, :, None]                         # (E_h, S, 1)
        xe = jax.vmap(act)(jnp.where(routed, x[None], 0.0))
        wi = dequant(p["wi_q"], p["wi_s"])
        wg = dequant(p["wg_q"], p["wg_s"])
        wo = dequant(p["wo_q"], p["wo_s"])
        u = jnp.einsum("etd,edf->etf", xe, wi, precision=hi) * jax.nn.silu(
            jnp.einsum("etd,edf->etf", xe, wg, precision=hi))
        out = jnp.einsum("etf,efd->etd", jax.vmap(act)(u), wo, precision=hi)
        return jnp.einsum("etd,te->td", out, gate, precision=hi)

    def dense_layer(x, lp):
        x = x + attention(rms(x, lp["ln1"]["g"]), lp["attn"])
        return x + mlp(rms(x, lp["ln2"]["g"]), lp["mlp"]), None

    def moe_layer(x, lp):
        x = x + attention(rms(x, lp["ln1"]["g"]), lp["attn"])
        y = rms(x, lp["ln2"]["g"])
        return x + experts(y, lp["moe"]) + mlp(y, lp["moe"]["shared"]), None

    x = params["embed"]["table"][tokens].astype(f32)
    x, _ = jax.lax.scan(dense_layer, x, params["dense_layers"])
    x, _ = jax.lax.scan(moe_layer, x, params["layers"])
    y = rms(x, params["final_norm"]["g"])
    return gemm(y[pos], params["head"], over=y)


@functools.lru_cache(maxsize=None)
def _compiled(d: costs_mla_moe.Dims, eps: float, theta: float, rs: tuple,
              norm_topk: bool, bits, act_int8: bool):
    import jax
    return jax.jit(functools.partial(
        _forward, d=d, eps=eps, theta=theta, rs=rs, norm_topk=norm_topk,
        bits=bits, act_int8=act_int8))


def logits(params, cfg: dict, tokens, positions, pad_to: int,
           pos_pad: int, bits=None, act_int8: bool = False) -> np.ndarray:
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
    rs = tuple(sorted((k, v) for k, v in cfg["rope_scaling"].items()
                      if k != "type"))
    fn = _compiled(costs_mla_moe.dims(cfg), float(cfg["rms_norm_eps"]),
                   float(cfg["rope_theta"]), rs, bool(cfg["norm_topk_prob"]),
                   bits, act_int8)
    return np.asarray(fn(params, tok, pos))[:positions.size]
