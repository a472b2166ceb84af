"""Core layers: norms, embeddings, RoPE, (NMC-quantizable) linears, MLPs.

Functional style: every layer is an ``init(key, ...) -> params`` plus an
``apply(params, x, ...) -> y`` pair over plain dict pytrees — no framework
dependency, fully inspectable, shard-mappable.

The paper's technique surfaces here as :func:`linear`'s NMC modes:
  * ``none``  — bf16 dense (baseline)
  * ``w8``    — int8 weights dequantized on the fly (halves weight HBM bytes)
  * ``w8a8``  — int8 x int8 -> int32 MXU path with fused dequant epilogue
                (the NM-Carus vmacc loop; Pallas kernel on TPU)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.kernels import ref as kref

Params = dict


def shard_hidden(x: jax.Array, axis: int = -1) -> jax.Array:
    """Constrain the trailing (feature) axis of an activation to the `model`
    mesh axis when a mesh is active — used to steer GSPMD toward head/ffn
    tensor parallelism.  No-op without a mesh."""
    from repro.distributed import context
    spec = context.hidden_spec(x.ndim, axis, x.shape)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def shard_batch_only(x: jax.Array) -> jax.Array:
    """Constrain an activation to batch-only (pod/data) sharding — used at
    residual-stream junction points to keep the feature axis replicated."""
    from repro.distributed import context
    spec = context.batch_spec(x.ndim)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def shard_seq(x: jax.Array) -> jax.Array:
    """Sequence-parallel residual stream (Megatron-SP): shard dim 1 (seq)
    over `model`.  GSPMD then emits reduce-scatter after row-sharded
    projections and all-gather before column-sharded ones — same link bytes
    as the all-reduce it replaces, but every norm/residual/elementwise op in
    between touches 1/TP of the bytes."""
    from repro.distributed import context
    mesh = context.get_mesh()
    if mesh is None or not context.has_model_axis() or x.ndim < 3 \
            or x.shape[1] % mesh.shape[context.MODEL_AXIS]:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    ax = context.data_axes()
    spec = NamedSharding(mesh, P(ax if ax else None, context.MODEL_AXIS,
                                 *([None] * (x.ndim - 2))))
    return jax.lax.with_sharding_constraint(x, spec)


def _init_dense(key, shape, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(shape[0])
    return (jax.random.normal(key, shape, jnp.float32) * scale)


# ---------------------------------------------------------------------------
# Linear (+ NMC quantized execution)
# ---------------------------------------------------------------------------

def linear_init(key, d_in: int, d_out: int, bias: bool = False) -> Params:
    p = {"w": _init_dense(key, (d_in, d_out))}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def linear_quantize(p: Params) -> Params:
    """Convert a trained linear to its NMC (int8) serving form.  Handles
    stacked (scan-over-layers) weights of shape (L, d_in, d_out)."""
    w = p["w"]
    if w.ndim == 3:
        wq, s = jax.vmap(lambda wl: kref.quantize_rowwise(wl, axis=0))(w)
    else:
        wq, s = kref.quantize_rowwise(w, axis=0)
    q = {"w_q": wq, "scale": s}
    if "b" in p:
        q["b"] = p["b"]
    return q


def linear(p: Params, x: jax.Array, *, nmc_mode: str = "none",
           act: str = "none", dtype=None) -> jax.Array:
    """y = act(x @ W + b), honouring the NMC execution mode.

    Accepts arbitrary leading batch dims; contraction over the last."""
    dtype = dtype or x.dtype
    lead = x.shape[:-1]
    d_in = x.shape[-1]
    x2 = x.reshape(-1, d_in)
    if "w_q" in p:                               # quantized serving params
        if nmc_mode == "w8a8":
            xq, sx = kref.quantize_dynamic(x2)
            y = kops.nmc_matmul(xq, p["w_q"], p["scale"] * sx,
                                p.get("b"), act=act, out_dtype=dtype)
            return y.reshape(*lead, -1)
        # w8: dequantize weights, bf16 matmul (weight bytes halved in HBM)
        w = (p["w_q"].astype(dtype) * p["scale"].astype(dtype)[None, :])
    else:
        w = p["w"].astype(dtype)
    y = x2.astype(dtype) @ w
    if "b" in p:
        y = y + p["b"].astype(dtype)
    y = kref.apply_act(y, act).astype(dtype)
    return y.reshape(*lead, -1)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int) -> Params:
    return {"g": jnp.ones((d,), jnp.float32)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * p["g"]).astype(dt)


def layernorm_init(d: int) -> Params:
    return {"g": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def yarn_range(dim: int, theta: float, scaling) -> tuple[int, int]:
    """The frequency indices between which YaRN's ramp runs: those of the
    dimensions that turn ``beta_fast`` and ``beta_slow`` times over the
    original context, clamped to ``[0, dim - 1]``."""
    def corr(n):
        return dim * math.log(scaling.original_max_position_embeddings
                              / (n * 2 * math.pi)) / (2 * math.log(theta))
    return (max(math.floor(corr(scaling.beta_fast)), 0),
            min(math.ceil(corr(scaling.beta_slow)), dim - 1))


def yarn_inv_freq(dim: int, theta: float, scaling) -> np.ndarray:
    """YaRN's RoPE inverse frequencies ``(dim/2,)``
    (:class:`repro.models.config.YaRN`): those past the ramp divided by
    its factor, linearly in between (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``)."""
    base = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    lo, hi = yarn_range(dim, theta, scaling)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    inv = base / scaling.factor * ramp + base * (1 - ramp)
    return inv.astype(np.float32)


def rope_table(positions: jax.Array, dim: int, theta: float,
               scaling=None) -> tuple:
    """cos/sin tables for given positions: (..., dim/2); ``scaling`` is a
    model's YaRN ``rope_scaling`` or None."""
    if scaling is None:
        inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                               / dim))
    else:
        inv = jnp.asarray(yarn_inv_freq(dim, theta, scaling))
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None and scaling.cos_sin_scale != 1.0:
        cos, sin = cos * scaling.cos_sin_scale, sin * scaling.cos_sin_scale
    return cos, sin


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., S, H, D); cos/sin: (S, D/2) or broadcastable."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    if cos.ndim == 2:                       # (S, D/2) -> (S, 1, D/2)
        cos, sin = cos[:, None, :], sin[:, None, :]
    rot1 = x1 * cos - x2 * sin
    rot2 = x2 * cos + x1 * sin
    return jnp.concatenate([rot1, rot2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------

def embedding_init(key, vocab: int, d: int) -> Params:
    return {"table": _init_dense(key, (vocab, d), scale=0.02)}


def embed(p: Params, ids: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return p["table"].astype(dtype)[ids]


def lm_head_init(key, d: int, vocab: int) -> Params:
    return linear_init(key, d, vocab)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key, d: int, d_ff: int, act: str = "silu") -> Params:
    ks = jax.random.split(key, 3)
    if act == "silu":                          # gated (SwiGLU)
        return {"wi": linear_init(ks[0], d, d_ff),
                "wg": linear_init(ks[1], d, d_ff),
                "wo": linear_init(ks[2], d_ff, d)}
    return {"wi": linear_init(ks[0], d, d_ff),
            "wo": linear_init(ks[2], d_ff, d)}


def mlp(p: Params, x: jax.Array, act: str = "silu",
        nmc_mode: str = "none") -> jax.Array:
    if "wg" in p:
        h = linear(p["wi"], x, nmc_mode=nmc_mode) * \
            linear(p["wg"], x, nmc_mode=nmc_mode, act="silu")
        h = shard_hidden(h)
    else:
        h = shard_hidden(linear(p["wi"], x, nmc_mode=nmc_mode, act=act))
    return linear(p["wo"], h, nmc_mode=nmc_mode)


def _quantize_expert_bank(w):
    """(…, E, d_in, d_out) expert weights -> int8 + per-(expert, out) scale."""
    amax = jnp.max(jnp.abs(w), axis=-2)                     # (…, E, d_out)
    s = jnp.maximum(amax, 1e-8) / 127.0
    wq = jnp.clip(jnp.round(w / s[..., None, :]), -127, 127).astype(jnp.int8)
    return wq, s


def quantize_tree(params, path_filter=None):
    """Recursively convert every linear ({'w': ...}) and MoE expert bank
    (router + wi/wg/wo arrays) in a param tree to its int8 NMC form.  Norm
    gains / embeddings / biases are left untouched (the paper never
    quantizes accumulators or normalization state)."""
    if isinstance(params, dict):
        if "w" in params and params["w"].ndim in (2, 3):
            return linear_quantize(params)
        if "router" in params and "wi" in params:           # MoE expert bank
            # router stays full precision: its logit margins decide top-k
            # routing and are tiny relative to int8 noise (standard practice)
            out = {k: (v if k == "router" else quantize_tree(v))
                   for k, v in params.items()
                   if k not in ("wi", "wg", "wo")}
            for k in ("wi", "wg", "wo"):
                wq, s = _quantize_expert_bank(params[k])
                out[f"{k}_q"], out[f"{k}_s"] = wq, s
            return out
        return {k: quantize_tree(v) for k, v in params.items()}
    return params
