"""Random served-form weights of a dense decoder LM, made from a seed.

The benchmark, not the program, makes the weights: one jitted call on
the device, in the form they are served in (``nmc_mode="w8a8"``: int8
weights with per-output-channel f32 scales).  The plain reference reads
the same arrays, so it takes nothing that the program has made.
"""

from __future__ import annotations

import numpy as np

from bench import costs

INT8_STD = 73.6        # standard deviation of a uniform int over [-127, 127]


def key_of(seed: int):
    """An ``rbg`` PRNG key from any whole-number seed (large ones too)."""
    import jax
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.key(word, impl="rbg")


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    import jax.numpy as jnp
    from repro.models.config import ModelConfig

    d = costs.dims(cfg)
    serving = cfg.get("serving", {})
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=d.layers,
        d_model=d.d_model, n_heads=d.heads, n_kv_heads=d.kv_heads,
        d_ff=d.d_ff, vocab_size=d.vocab, head_dim=d.head_dim,
        qkv_bias=d.qkv_bias, rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        nmc_mode=serving.get("nmc_mode", "w8a8"),
        kv_cache_dtype="int8" if serving.get("kv_cache_dtype") == "int8"
        else "bf16",
        dtype=jnp.bfloat16, remat=False)


def _make(key, d: costs.Dims, tied: bool):
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(key, 32))
    L = d.layers

    def linear(k_in, n_out, bias):
        shape = (L, k_in, n_out)
        p = {"w_q": jax.random.randint(next(keys), shape, -127, 128,
                                       dtype=jnp.int8),
             "scale": jax.random.uniform(next(keys), (L, n_out), jnp.float32,
                                         0.8, 1.2)
             / (INT8_STD * np.sqrt(k_in))}
        if bias:
            p["b"] = 0.02 * jax.random.normal(next(keys), (L, n_out),
                                              jnp.float32)
        return p

    def gain(shape):
        return 1.0 + 0.05 * jax.random.normal(next(keys), shape, jnp.float32)

    qd, kvd = d.heads * d.head_dim, d.kv_heads * d.head_dim
    table = 0.02 * jax.random.normal(next(keys), (d.vocab, d.d_model),
                                     jnp.float32)
    if tied:
        w = table.T
        amax = jnp.max(jnp.abs(w), axis=0)
        s = jnp.maximum(amax, 1e-8) / 127.0
        head = {"w_q": jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8),
                "scale": s}
    else:
        head = {k: v[0] for k, v in linear(d.d_model, d.vocab, False).items()}
    return {
        "embed": {"table": table},
        "final_norm": {"g": gain((d.d_model,))},
        "head": head,
        "layers": {
            "ln1": {"g": gain((L, d.d_model))},
            "attn": {"wq": linear(d.d_model, qd, d.qkv_bias),
                     "wk": linear(d.d_model, kvd, d.qkv_bias),
                     "wv": linear(d.d_model, kvd, d.qkv_bias),
                     "wo": linear(qd, d.d_model, False)},
            "ln2": {"g": gain((L, d.d_model))},
            "mlp": {"wi": linear(d.d_model, d.d_ff, False),
                    "wg": linear(d.d_model, d.d_ff, False),
                    "wo": linear(d.d_ff, d.d_model, False)},
        },
    }


def make(cfg: dict, seed: int):
    """Served-form parameters for ``cfg``, on the default device."""
    import functools

    import jax

    d = costs.dims(cfg)
    fn = jax.jit(functools.partial(_make, d=d,
                                   tied=bool(cfg.get("tie_word_embeddings"))))
    return jax.block_until_ready(fn(key_of(seed)))
