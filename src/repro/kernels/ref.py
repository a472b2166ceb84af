"""Pure-jnp oracles for every Pallas kernel in this package.

These are the single source of truth for kernel correctness: each Pallas
kernel is swept over shapes/dtypes in ``tests/test_kernels.py`` and asserted
allclose (bit-exact for the integer kernels) against these references.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# nmc_matmul: W8A8 integer matmul with int32 accumulation + fused epilogue
# ---------------------------------------------------------------------------


def nmc_matmul(x_q: jax.Array, w_q: jax.Array, scale: jax.Array,
               bias: jax.Array | None = None, act: str = "none",
               out_dtype=jnp.float32) -> jax.Array:
    """y = act((x_q @ w_q) * scale + bias).

    x_q: (M, K) int8, w_q: (K, N) int8, scale: (N,) f32 (= s_x * s_w),
    bias: (N,) f32 or None.  Accumulation in int32 — the NM-Carus vmacc
    semantics (never accumulate at operand width)."""
    acc = jax.lax.dot_general(
        x_q, w_q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * scale[None, :]
    if bias is not None:
        y = y + bias[None, :]
    y = apply_act(y, act)
    return y.astype(out_dtype)


def nmc_expert_matmul(x_q: jax.Array, w_q: jax.Array, scale: jax.Array,
                      act: str = "none", out_dtype=jnp.float32) -> jax.Array:
    """y[e] = act((x_q[e] @ w_q[e]) * scale[e]): x_q (E, M, K) int8, w_q
    (E, K, N) int8, scale (E, N) f32; int32 accumulation per expert."""
    acc = jnp.einsum("emk,ekn->emn", x_q, w_q,
                     preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * scale[:, None, :]
    return apply_act(y, act).astype(out_dtype)


def apply_act(y: jax.Array, act: str) -> jax.Array:
    if act == "none":
        return y
    if act == "relu":
        return jnp.maximum(y, 0)
    if act == "silu":
        return y * jax.nn.sigmoid(y)
    if act == "gelu":
        return jax.nn.gelu(y)
    raise ValueError(act)


def quantize_rowwise(w: jax.Array, axis: int = 0) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-output-channel int8 quantization of a weight matrix."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-8) / 127.0
    wq = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
    return wq, s.reshape(-1)


def quantize_dynamic(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-tensor dynamic symmetric int8 quantization of activations."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.maximum(amax, 1e-8) / 127.0
    xq = jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8)
    return xq, s


# ---------------------------------------------------------------------------
# vrf_alu: the NM-Carus VPU as a fused element-wise program interpreter
# ---------------------------------------------------------------------------

# op ids (shared with the Pallas kernel)
VRF_OPS = ("add", "sub", "mul", "min", "max", "and", "or", "xor",
           "sll", "srl", "sra", "mv")
VRF_OP_ID = {n: i for i, n in enumerate(VRF_OPS)}
VRF_MODE_VV, VRF_MODE_VX = 0, 1


def _vrf_binop(opid, a, b, dtype):
    bits = dtype.itemsize * 8
    sh = (b.astype(jnp.uint32) % bits).astype(dtype)
    u = a.astype(jnp.dtype(f"uint{bits}"))
    return jnp.stack([
        a + b, a - b, a * b, jnp.minimum(a, b), jnp.maximum(a, b),
        a & b, a | b, a ^ b,
        (u << sh.astype(u.dtype)).astype(dtype),
        (u >> sh.astype(u.dtype)).astype(dtype),
        a >> sh,
        jnp.broadcast_to(b, a.shape),
    ])[opid]


def vrf_alu(vrf: jax.Array, prog: dict) -> jax.Array:
    """Execute `prog` over a (n_regs, vl) integer VRF; wraparound semantics.

    prog fields (int32 arrays, equal length): op, vd, vs1, vs2, scalar, mode.
    mode 0 = vv (operand b from vrf[vs1]); 1 = vx (operand b = scalar)."""
    dtype = vrf.dtype

    def step(vrf, ins):
        a = vrf[ins["vs2"]]
        b = jnp.where(ins["mode"] == VRF_MODE_VV, vrf[ins["vs1"]],
                      jnp.asarray(ins["scalar"], dtype))
        r = _vrf_binop(ins["op"], a, b.astype(dtype), dtype)
        return vrf.at[ins["vd"]].set(r.astype(dtype)), None

    vrf, _ = jax.lax.scan(step, vrf, prog)
    return vrf


# ---------------------------------------------------------------------------
# flash attention (blocked online-softmax reference: plain softmax here)
# ---------------------------------------------------------------------------


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: int | None = None,
              q_offset: int = 0) -> jax.Array:
    """Reference attention.  q: (B, Hq, Sq, D), k/v: (B, Hkv, Skv, D).
    GQA by head repetition.  `window` = sliding-window size (None = full).
    `q_offset` positions q tokens at kv index q_offset + i (for decode)."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(d)
    skv = k.shape[2]
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)
