"""Mixture-of-Experts with expert parallelism over the `model` mesh axis.

Design (DESIGN.md §8): activations enter the MoE block replicated across the
`model` axis (the attention output all-reduce already paid for that), and
each model shard owns E / model_size experts.  Dispatch is therefore fully
local — a capacity-bounded scatter into an (E_local, cap, d) buffer — and the
only collective is one psum over `model` to combine expert outputs: the same
collective a dense TP FFN needs.  No all_to_all, no GSPMD-surprising gathers,
deterministic HLO.  (A reduce-scatter + all2all variant is evaluated in the
§Perf hillclimb.)

Runs inside ``context.shard_map`` (``jax.shard_map``) when a mesh is
active; degrades to a single-shard call otherwise (unit tests).  Capacity-dropped tokens fall back
to zero contribution from routed experts (shared experts still apply),
standard top-k capacity semantics.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import context
from repro.models import layers as L
from repro.models.config import ModelConfig


def moe_init(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 5)
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    p = {
        "router": L.linear_init(ks[0], d, e),
        "wi": jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale,
        "wg": jax.random.normal(ks[2], (e, d, f), jnp.float32) * scale,
        "wo": jax.random.normal(ks[3], (e, f, d), jnp.float32) / jnp.sqrt(
            jnp.float32(f)),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.mlp_init(ks[4], d,
                                 cfg.moe_d_ff * cfg.n_shared_experts, "silu")
    return p


def _expert_ffn(buf, wi, wg, wo, dtype):
    """buf: (E_loc, cap, d) -> (E_loc, cap, d); SwiGLU per expert."""
    h = jnp.einsum("ecd,edf->ecf", buf, wi.astype(dtype))
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(dtype)))
    return jnp.einsum("ecf,efd->ecd", h * g, wo.astype(dtype))


def _moe_local(x, wr, wi, wg, wo, *, cfg: ModelConfig, axis: Optional[str]):
    """Token dispatch + expert FFN on one shard.  x: (t, d) local tokens
    (replicated over `axis`); wi/wg/wo: (E_local, ...) local expert slice."""
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    e_loc = wi.shape[0]
    cap = max(int(t * k * cfg.capacity_factor / e), 1)
    dtype = x.dtype

    logits = (x.astype(jnp.float32) @ wr.astype(jnp.float32))     # (t, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                        # (t, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # aux load-balancing loss (Switch-style), computed on local tokens
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))

    flat_e = top_e.reshape(-1)                                    # (t*k,)
    flat_p = top_p.reshape(-1).astype(dtype)
    tok_ix = jnp.repeat(jnp.arange(t), k)

    shard = 0 if axis is None else jax.lax.axis_index(axis)
    e0 = shard * e_loc
    local = (flat_e >= e0) & (flat_e < e0 + e_loc)
    le = jnp.clip(flat_e - e0, 0, e_loc - 1)

    # position of each assignment within its expert's capacity buffer
    onehot = jax.nn.one_hot(jnp.where(local, le, e_loc), e_loc + 1,
                            dtype=jnp.int32)                      # (t*k, E+1)
    pos = jnp.cumsum(onehot, axis=0) - 1
    slot = jnp.sum(pos * onehot, axis=-1)                         # (t*k,)
    keep = local & (slot < cap)
    slot_c = jnp.clip(slot, 0, cap - 1)

    buf = jnp.zeros((e_loc, cap, d), dtype)
    buf = buf.at[le, slot_c].add(
        jnp.where(keep, 1.0, 0.0).astype(dtype)[:, None] * x[tok_ix])

    out_buf = _expert_ffn(buf, wi, wg, wo, dtype)                 # (E,cap,d)

    contrib = out_buf[le, slot_c] * jnp.where(keep, flat_p, 0.0)[:, None]
    y = jnp.zeros((t, d), dtype).at[tok_ix].add(contrib)
    if axis is not None:
        y = jax.lax.psum(y, axis)
        aux = jax.lax.pmean(aux, axis)
    return y, aux


def _expert_weights(p, dtype):
    """Dense or NMC-quantized (w8) expert banks -> bf16 compute form.
    int8 banks halve expert HBM bytes — the dominant weights in MoE decode."""
    if "wi_q" in p:
        return tuple((p[f"{k}_q"].astype(dtype)
                      * p[f"{k}_s"].astype(dtype)[..., None, :])
                     for k in ("wi", "wg", "wo"))
    return p["wi"], p["wg"], p["wo"]


def moe_apply(p, x, cfg: ModelConfig):
    """x: (B, S, D) -> (y, aux_loss).  Routed experts (EP over `model`) +
    shared experts (plain TP MLP, computed once)."""
    b, s, d = x.shape
    mesh = context.get_mesh()
    x2 = x.reshape(-1, d)
    quant = "wi_q" in p
    wkeys = (("wi_q", "wi_s", "wg_q", "wg_s", "wo_q", "wo_s") if quant
             else ("wi", "wg", "wo"))
    wargs = [p[k] for k in wkeys]
    rw = p["router"].get("w")
    if rw is None:   # quantized router
        rw = (p["router"]["w_q"].astype(x.dtype)
              * p["router"]["scale"].astype(x.dtype)[None, :])

    def local_fn(xx, wr, *ws):
        if quant:
            pw = {k: v for k, v in zip(wkeys, ws)}
            wi, wg, wo = _expert_weights(pw, xx.dtype)
        else:
            wi, wg, wo = ws
        axis = context.MODEL_AXIS if mesh is not None and \
            context.has_model_axis() else None
        return _moe_local(xx, wr, wi, wg, wo, cfg=cfg, axis=axis)

    if mesh is not None and context.has_model_axis():
        dax = context.data_axes()
        espec = [P(context.MODEL_AXIS, *([None] * (w.ndim - 1)))
                 for w in wargs]
        y2, aux = context.shard_map(
            local_fn, mesh=mesh,
            in_specs=(P(dax if dax else None, None), P(None, None),
                      *espec),
            out_specs=(P(dax if dax else None, None), P()),
        )(x2, rw, *wargs)
    else:
        y2, aux = local_fn(x2, rw, *wargs)

    y = y2.reshape(b, s, d)
    if "shared" in p:
        y = y + L.mlp(p["shared"], x, nmc_mode=cfg.nmc_mode)
    return y, aux.astype(jnp.float32)
