"""Random served-form weights of a latent-attention expert model
(DeepSeek-V2's layout), made from a seed.

As in :mod:`bench.drivers.lm_weights`, the benchmark makes the weights in
one jitted call on the device, in the form they are served in
(``nmc_mode="w8a8"``): int8 GEMM weights with per-output-channel f32
scales, each routed expert's banks the same per expert, float32 routers.
Only the routed experts this chip holds are made.  The plain reference
reads the same arrays.
"""

from __future__ import annotations

import numpy as np

from bench import costs_mla_moe
from bench.drivers.lm_weights import INT8_STD, key_of

#: Standard deviation of a random router's logits over a unit-RMS input.
#: At 2 the softmax's top 6 of 64 carry 0.68 of its mass and the first
#: 0.30 (at 1: 0.36 and 0.11), as a trained router concentrates its mass
#: on the experts it picks; a router as flat as a fresh layer's would make
#: the routed experts a small part of every token's result.
ROUTER_LOGIT_SD = 2.0


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    import jax.numpy as jnp
    from repro.models.config import ModelConfig, YaRN

    d = costs_mla_moe.dims(cfg)
    rs = {k: v for k, v in cfg["rope_scaling"].items() if k != "type"}
    assert cfg["rope_scaling"]["type"] == "yarn", cfg["rope_scaling"]
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=d.layers,
        d_model=d.d_model, n_heads=d.heads, n_kv_heads=d.heads,
        d_ff=d.dense_ff, vocab_size=d.vocab, mla=True,
        kv_lora_rank=d.kv_lora, qk_nope_dim=d.qk_nope,
        qk_rope_dim=d.qk_rope, v_head_dim=d.v_head, moe=True,
        n_experts=d.experts, n_shared_experts=d.shared, top_k=d.top_k,
        moe_d_ff=d.expert_ff, first_dense_layers=d.dense_layers,
        dense_d_ff=d.dense_ff, norm_topk_prob=bool(cfg["norm_topk_prob"]),
        expert_first=d.first, experts_held=d.held,
        rope_theta=float(cfg["rope_theta"]), rope_scaling=YaRN(**rs),
        norm_eps=float(cfg["rms_norm_eps"]),
        nmc_mode=cfg.get("serving", {}).get("nmc_mode", "w8a8"),
        dtype=jnp.bfloat16, remat=False)


def _make(key, d: costs_mla_moe.Dims):
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(key, 64))

    def int8(shape):
        return jax.random.randint(next(keys), shape, -127, 128,
                                  dtype=jnp.int8)

    def scale(shape, k_in):
        return jax.random.uniform(next(keys), shape, jnp.float32, 0.8,
                                  1.2) / (INT8_STD * np.sqrt(k_in))

    def linear(lead, k_in, n_out):
        return {"w_q": int8(lead + (k_in, n_out)),
                "scale": scale(lead + (n_out,), k_in)}

    def gain(shape):
        return 1.0 + 0.05 * jax.random.normal(next(keys), shape, jnp.float32)

    h, r = d.heads, d.kv_lora

    def attn(n):
        lead = (n,)
        return {"wq": linear(lead, d.d_model, h * (d.qk_nope + d.qk_rope)),
                "w_dkv": linear(lead, d.d_model, r),
                "w_krope": linear(lead, d.d_model, d.qk_rope),
                "w_uk": linear(lead, r, h * d.qk_nope),
                "w_uv": linear(lead, r, h * d.v_head),
                "wo": linear(lead, h * d.v_head, d.d_model),
                "norm_ckv": {"g": gain((n, r))}}

    def mlp(n, ff):
        return {"wi": linear((n,), d.d_model, ff),
                "wg": linear((n,), d.d_model, ff),
                "wo": linear((n,), ff, d.d_model)}

    def block(n):
        return {"ln1": {"g": gain((n, d.d_model))}, "attn": attn(n),
                "ln2": {"g": gain((n, d.d_model))}}

    n, e, f = d.moe_layers, d.held, d.expert_ff
    moe = {"router": {"w": jax.random.normal(
               next(keys), (n, d.d_model, d.experts), jnp.float32)
               * ROUTER_LOGIT_SD / np.sqrt(d.d_model)},
           "wi_q": int8((n, e, d.d_model, f)),
           "wi_s": scale((n, e, f), d.d_model),
           "wg_q": int8((n, e, d.d_model, f)),
           "wg_s": scale((n, e, f), d.d_model),
           "wo_q": int8((n, e, f, d.d_model)),
           "wo_s": scale((n, e, d.d_model), f),
           "shared": mlp(n, d.shared * f)}
    return {
        "embed": {"table": 0.02 * jax.random.normal(
            next(keys), (d.vocab, d.d_model), jnp.float32)},
        "final_norm": {"g": gain((d.d_model,))},
        "head": {k: v[0] for k, v in
                 linear((1,), d.d_model, d.vocab).items()},
        "dense_layers": dict(block(d.dense_layers),
                             mlp=mlp(d.dense_layers, d.dense_ff)),
        "layers": dict(block(n), moe=moe),
    }


def make(cfg: dict, seed: int):
    """Served-form parameters for ``cfg``, on the default device."""
    import functools

    import jax

    fn = jax.jit(functools.partial(_make, d=costs_mla_moe.dims(cfg)))
    return jax.block_until_ready(fn(key_of(seed)))
