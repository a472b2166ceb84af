"""Unified language model: one entry point for all ten architecture families.

Families and their block stacks (all scanned where homogeneous — HLO size is
O(1) in depth, required for 512-device dry-run compiles):

  dense / vlm    : [attn + MLP] x L                       (scan)
  moe            : first_dense x k (unrolled) + [attn + MoE] x (L-k)  (scan)
  hybrid (zamba2): [[mamba2 x attn_every] + shared attn/MLP block] x G
                   (outer scan over groups, inner scan over mamba layers;
                    the attention block's weights are SHARED across groups)
  xlstm          : mLSTM / sLSTM blocks (unrolled; tiny)
  encdec (whisper): encoder [attn + MLP] x Le (scan, non-causal)
                    + decoder [self + cross + MLP] x L (scan)

Three entry points per family: ``forward`` (teacher-forced training),
``prefill`` (build KV caches / recurrent states), ``decode_step``
(one token, donated caches).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models import xlstm as XL
from repro.models.config import ModelConfig

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _stack_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def _maybe_remat(fn, cfg: ModelConfig):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# decoder block (dense & moe & vlm)
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, use_moe: bool, d_ff: int | None = None):
    ks = jax.random.split(key, 2)
    attn_init = A.mla_init if cfg.mla else A.gqa_init
    p = {"ln1": L.rmsnorm_init(cfg.d_model),
         "attn": attn_init(ks[0], cfg),
         "ln2": L.rmsnorm_init(cfg.d_model)}
    if use_moe:
        p["moe"] = MOE.moe_init(ks[1], cfg)
    else:
        p["mlp"] = L.mlp_init(ks[1], cfg.d_model, d_ff or cfg.d_ff, cfg.act)
    return p


def _block_apply(p, x, cfg: ModelConfig, use_moe: bool):
    sp = L.shard_seq if cfg.seq_parallel else (lambda t: t)
    x = sp(x)
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    attn = A.mla_apply if cfg.mla else A.gqa_apply
    x = sp(x + attn(p["attn"], h, cfg))
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if use_moe:
        y, aux = MOE.moe_apply(p["moe"], h, cfg)
        return x + y, aux
    return x + L.mlp(p["mlp"], h, cfg.act, nmc_mode=cfg.nmc_mode), jnp.float32(0)


def _ffn_serve(p, h, cfg: ModelConfig, use_moe: bool):
    """The block's MLP or expert layer at prefill and decode: ``(y, rows
    routed to each held expert)``, the rows None but for served experts."""
    if not use_moe:
        return L.mlp(p["mlp"], h, cfg.act, nmc_mode=cfg.nmc_mode), None
    if MOE.serving(p["moe"], cfg):
        return MOE.moe_serve(p["moe"], h, cfg)
    return MOE.moe_apply(p["moe"], h, cfg)[0], None


def _block_prefill(p, x, cfg: ModelConfig, use_moe: bool, max_len: int):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    pre = A.mla_prefill if cfg.mla else A.gqa_prefill
    y, cache = pre(p["attn"], h, cfg, max_len)
    x = x + y
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    y, rows = _ffn_serve(p, h, cfg, use_moe)
    return x + y, (cache, rows)


def _block_decode(p, x, cfg: ModelConfig, use_moe: bool, cache, cache_len,
                  layer=None):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    dec = A.mla_decode if cfg.mla else A.gqa_decode
    y, cache = dec(p["attn"], h, cfg, cache, cache_len, layer)
    x = x + y
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    y, rows = _ffn_serve(p, h, cfg, use_moe)
    return x + y, (cache, rows)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 8)
    p: dict[str, Any] = {
        "embed": L.embedding_init(ks[0], cfg.vocab_size, cfg.d_model),
        "final_norm": L.rmsnorm_init(cfg.d_model),
        "head": L.lm_head_init(ks[1], cfg.d_model, cfg.vocab_size),
    }
    fam = cfg.family
    if fam in ("dense", "vlm"):
        p["layers"] = _stack_init(
            lambda k: _block_init(k, cfg, use_moe=False), ks[2], cfg.n_layers)
    elif fam == "moe":
        nd = cfg.first_dense_layers
        if nd:
            p["dense_layers"] = _stack_init(
                lambda k: _block_init(k, cfg, False,
                                      cfg.dense_d_ff or cfg.d_ff),
                ks[3], nd)
        p["layers"] = _stack_init(
            lambda k: _block_init(k, cfg, use_moe=True), ks[2],
            cfg.n_layers - nd)
    elif fam == "hybrid":
        groups = cfg.n_layers // cfg.attn_every
        p["mamba"] = jax.vmap(
            lambda k: _stack_init(lambda kk: SSM.mamba2_init(kk, cfg),
                                  k, cfg.attn_every)
        )(jax.random.split(ks[2], groups))
        p["shared_attn"] = _block_init(ks[3], cfg, use_moe=False)
    elif fam == "xlstm":
        p["blocks"] = []
        bkeys = jax.random.split(ks[2], cfg.n_layers)
        for i in range(cfg.n_layers):
            if i in cfg.slstm_layers:
                p["blocks"].append(XL.slstm_init(bkeys[i], cfg))
            else:
                p["blocks"].append(XL.mlstm_init(bkeys[i], cfg))
    elif fam == "encdec":
        p["enc_layers"] = _stack_init(
            lambda k: _enc_block_init(k, cfg), ks[2], cfg.n_enc_layers)
        p["layers"] = _stack_init(
            lambda k: _dec_block_init(k, cfg), ks[3], cfg.n_layers)
        p["pos_dec"] = {"table": 0.02 * jax.random.normal(
            ks[4], (32768, cfg.d_model), jnp.float32)}
        p["enc_norm"] = L.rmsnorm_init(cfg.d_model)
    else:
        raise ValueError(fam)
    if fam == "vlm":
        p["img_proj"] = L.linear_init(ks[5], cfg.d_model, cfg.d_model)
    return p


# ---------------------------------------------------------------------------
# forward (training) per family
# ---------------------------------------------------------------------------

def _embed_in(p, tokens, cfg):
    x = L.embed(p["embed"], tokens, cfg.dtype)
    return x


def _lm_logits(p, x, cfg):
    x = L.rmsnorm(p["final_norm"], x, cfg.norm_eps)
    logits = L.linear(p["head"], x, nmc_mode=cfg.nmc_mode)
    return L.shard_hidden(logits)         # vocab sharded over `model`


def forward(params, batch: dict, cfg: ModelConfig):
    """Teacher-forced forward.  Returns (logits, aux_loss)."""
    fam = cfg.family
    if fam == "encdec":
        return _forward_encdec(params, batch, cfg)
    if fam == "vlm":
        x = _vlm_embed(params, batch, cfg)
    else:
        x = _embed_in(params, batch["tokens"], cfg)
    aux = jnp.float32(0)

    if fam in ("dense", "vlm"):
        def body(h, lp):
            h, a = _block_apply(lp, h, cfg, use_moe=False)
            return h, a
        x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, params["layers"])
    elif fam == "moe":
        if cfg.first_dense_layers:
            def bodyd(h, lp):
                h, a = _block_apply(lp, h, cfg, use_moe=False)
                return h, a
            x, _ = jax.lax.scan(_maybe_remat(bodyd, cfg), x,
                                params["dense_layers"])

        def body(h, lp):
            h, a = _block_apply(lp, h, cfg, use_moe=True)
            return h, a
        x, auxs = jax.lax.scan(_maybe_remat(body, cfg), x, params["layers"])
        aux = jnp.sum(auxs)
    elif fam == "hybrid":
        shared = params["shared_attn"]

        def group(h, glp):
            def inner(hh, lp):
                return hh + SSM.mamba2_apply(lp, hh, cfg), None
            h, _ = jax.lax.scan(inner, h, glp)
            h, _ = _block_apply(shared, h, cfg, use_moe=False)
            return h, None
        x, _ = jax.lax.scan(_maybe_remat(group, cfg), x, params["mamba"])
    elif fam == "xlstm":
        for i, bp in enumerate(params["blocks"]):
            if i in cfg.slstm_layers:
                x = XL.slstm_apply(bp, x, cfg)
            else:
                x = XL.mlstm_apply(bp, x, cfg)
    return _lm_logits(params, x, cfg), aux


def _vlm_embed(params, batch, cfg):
    """Concat projected (stub) image patch embeddings with text embeddings.
    The result must be batch-sharded only: any model-axis sharding on the
    feature dim here poisons the residual stream for every layer."""
    img = L.linear(params["img_proj"], batch["images"].astype(cfg.dtype),
                   nmc_mode=cfg.nmc_mode)
    txt = _embed_in(params, batch["tokens"], cfg)
    return L.shard_batch_only(jnp.concatenate([img, txt], axis=1))


# ---------------------------------------------------------------------------
# whisper-style encoder-decoder
# ---------------------------------------------------------------------------

def _enc_block_init(key, cfg):
    ks = jax.random.split(key, 2)
    return {"ln1": L.layernorm_init(cfg.d_model),
            "attn": A.gqa_init(ks[0], cfg),
            "ln2": L.layernorm_init(cfg.d_model),
            "mlp": L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, "gelu")}


def _dec_block_init(key, cfg):
    ks = jax.random.split(key, 3)
    return {"ln1": L.layernorm_init(cfg.d_model),
            "attn": A.gqa_init(ks[0], cfg),
            "lnx": L.layernorm_init(cfg.d_model),
            "xattn": A.gqa_init(ks[1], cfg),
            "ln2": L.layernorm_init(cfg.d_model),
            "mlp": L.mlp_init(ks[2], cfg.d_model, cfg.d_ff, "gelu")}


def _encode(params, frames, cfg):
    x = frames.astype(cfg.dtype)

    def body(h, lp):
        hn = L.layernorm(lp["ln1"], h, cfg.norm_eps)
        h = h + A.gqa_apply(lp["attn"], hn, cfg, causal=False)
        hn = L.layernorm(lp["ln2"], h, cfg.norm_eps)
        return h + L.mlp(lp["mlp"], hn, "gelu", nmc_mode=cfg.nmc_mode), None

    x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, params["enc_layers"])
    return L.rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def _dec_block_apply(lp, h, enc_kv, cfg):
    hn = L.layernorm(lp["ln1"], h, cfg.norm_eps)
    h = h + A.gqa_apply(lp["attn"], hn, cfg, causal=True)
    hn = L.layernorm(lp["lnx"], h, cfg.norm_eps)
    h = h + A.gqa_apply(lp["xattn"], hn, cfg, causal=False, kv=enc_kv)
    hn = L.layernorm(lp["ln2"], h, cfg.norm_eps)
    return h + L.mlp(lp["mlp"], hn, "gelu", nmc_mode=cfg.nmc_mode)


def _cross_kv(lp, enc, cfg):
    b, se, _ = enc.shape
    hd = cfg.head_dim
    k = L.linear(lp["xattn"]["wk"], enc).reshape(b, se, cfg.n_kv_heads, hd)
    v = L.linear(lp["xattn"]["wv"], enc).reshape(b, se, cfg.n_kv_heads, hd)
    return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


def _forward_encdec(params, batch, cfg):
    enc = _encode(params, batch["frames"], cfg)
    tokens = batch["tokens"]
    s = tokens.shape[1]
    x = _embed_in(params, tokens, cfg) + \
        params["pos_dec"]["table"][:s].astype(cfg.dtype)

    def body(h, lp):
        enc_kv = _cross_kv(lp, enc, cfg)
        return _dec_block_apply(lp, h, enc_kv, cfg), None

    x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, params["layers"])
    return _lm_logits(params, x, cfg), jnp.float32(0)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def loss_fn(params, batch: dict, cfg: ModelConfig,
            aux_weight: float = 0.01):
    """Next-token cross entropy (+ MoE aux).  batch["tokens"] supervises;
    for VLM only the text positions are supervised."""
    logits, aux = forward(params, batch, cfg)
    tokens = batch["tokens"]
    if cfg.family == "vlm":
        logits = logits[:, batch["images"].shape[1]:]
    tgt = tokens[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    true_logit = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
    nll = (lse - true_logit)
    mask = batch.get("mask")
    if mask is not None:
        m = mask[:, 1:].astype(jnp.float32)
        loss = jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    else:
        loss = jnp.mean(nll)
    return loss + aux_weight * aux, {"nll": loss, "aux": aux}


# ---------------------------------------------------------------------------
# prefill / decode
# ---------------------------------------------------------------------------

#: The cache entry, beside the layers' state, in which the prefill and
#: decode programs of a served expert model carry the rows their last call
#: routed to each held expert, ``(expert layers, held)`` int32.  It holds
#: no per-slot state: :func:`cache_batch_axes` marks it ``NOT_PER_SLOT``.
EXPERT_ROWS = "expert_rows"
NOT_PER_SLOT = -1


def init_caches(params, cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> dict:
    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        n_scan = cfg.n_layers - (cfg.first_dense_layers if fam == "moe" else 0)
        one = (A.mla_cache_init(cfg, batch, max_len, dtype) if cfg.mla
               else A.gqa_cache_init(cfg, batch, max_len, dtype))
        stack = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_scan,) + x.shape), one)
        caches = {"layers": stack}
        if fam == "moe" and cfg.first_dense_layers:
            caches["dense_layers"] = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x, (cfg.first_dense_layers,) + x.shape), one)
        if fam == "moe" and MOE.serving(params["layers"]["moe"], cfg):
            caches[EXPERT_ROWS] = jnp.zeros(
                (n_scan, cfg.held_experts[1]), jnp.int32)
        return caches
    if fam == "hybrid":
        groups = cfg.n_layers // cfg.attn_every
        ms = SSM.mamba2_state_init(cfg, batch, dtype)
        mstack = jax.tree.map(
            lambda x: jnp.broadcast_to(
                x, (groups, cfg.attn_every) + x.shape), ms)
        ac = A.gqa_cache_init(cfg, batch, max_len, dtype)
        astack = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (groups,) + x.shape), ac)
        return {"mamba": mstack, "attn": astack}
    if fam == "xlstm":
        states = []
        for i in range(cfg.n_layers):
            states.append(XL.slstm_state_init(cfg, batch)
                          if i in cfg.slstm_layers
                          else XL.mlstm_state_init(cfg, batch))
        return {"blocks": states}
    if fam == "encdec":
        one = A.gqa_cache_init(cfg, batch, max_len, dtype)
        stack = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape), one)
        ek = jnp.zeros((cfg.n_layers, batch, cfg.n_kv_heads, cfg.enc_seq,
                        cfg.head_dim), dtype)
        return {"layers": stack, "cross_k": ek, "cross_v": ek}
    raise ValueError(fam)


def cache_batch_axes(cfg: ModelConfig, caches: dict) -> dict:
    """Explicit batch-axis metadata for a cache pytree from
    :func:`init_caches`: the same tree structure with an int axis per leaf.

    Per-slot cache writes (e.g. ``repro.serve.engine.ServeEngine``
    admission) need to know each leaf's batch axis.  Leaves stacked with a
    leading layer/group axis carry batch at position 1 (position 2 for the
    hybrid family's mamba states, stacked ``(groups, attn_every, B, ...)``);
    un-stacked leaves carry it at position 0.  Shape sniffing cannot
    recover this — a size-1 layer axis is indistinguishable from a size-1
    batch axis (single-layer configs) — so the family knowledge lives
    here, next to the ``init_caches`` stacking rules it mirrors."""
    fam = cfg.family

    def const(tree, ax: int):
        return jax.tree.map(lambda _: ax, tree)

    if fam in ("dense", "vlm", "moe", "encdec"):
        # every entry ("layers", "dense_layers", "cross_k/v") is stacked
        # with one leading layer axis -> batch at 1
        return {k: const(v, NOT_PER_SLOT if k == EXPERT_ROWS else 1)
                for k, v in caches.items()}
    if fam == "hybrid":
        return {"mamba": const(caches["mamba"], 2),
                "attn": const(caches["attn"], 1)}
    if fam == "xlstm":                 # per-layer list, batch leading
        return {"blocks": const(caches["blocks"], 0)}
    raise ValueError(fam)


def _decode_stack(layer_params, stack, x, cfg: ModelConfig, use_moe: bool,
                  cache_len):
    """Decode through a stack of blocks, the stacked cache carried whole
    through the layer loop: layer ``i`` writes its rows into ``stack`` at
    ``i`` in place and reads its slice from there.  Returns (x, stack,
    the rows routed to each held expert per layer or None)."""
    def body(carry, lp):
        h, st, i = carry
        h, (st, rows) = _block_decode(lp, h, cfg, use_moe, st, cache_len, i)
        return (h, st, i + 1), rows
    (x, stack, _), rows = jax.lax.scan(body, (x, stack, jnp.int32(0)),
                                       layer_params)
    return x, stack, rows


def decode_step(params, tokens, caches: dict, cache_len, cfg: ModelConfig):
    """One decode step.  tokens: (B, 1) int32 (the *new* token ids);
    cache_len: (B,) lengths INCLUDING the new token.  Returns
    (logits (B, vocab), new caches).

    For the dense, vlm and moe families each layer writes only the new
    token's row per slot into the stacked cache, which the layer loop
    carries whole: with ``caches`` donated, every stacked leaf stays one
    buffer from the program's input to its output, and the step moves
    nothing of the cache's size."""
    fam = cfg.family
    x = _embed_in(params, tokens, cfg)
    if fam in ("dense", "vlm", "moe"):
        use_moe = fam == "moe"
        new = dict(caches)
        if use_moe and cfg.first_dense_layers:
            x, new["dense_layers"], _ = _decode_stack(
                params["dense_layers"], caches["dense_layers"], x, cfg,
                False, cache_len)
        x, new["layers"], rows = _decode_stack(
            params["layers"], caches["layers"], x, cfg, use_moe, cache_len)
        if rows is not None:
            new[EXPERT_ROWS] = rows
    elif fam == "hybrid":
        shared = params["shared_attn"]

        def group(h, inp):
            glp, gms, gac = inp

            def inner(hh, inp2):
                lp, st = inp2
                y, nst = SSM.mamba2_decode(lp, hh, cfg, st)
                return hh + y, nst
            h, nms = jax.lax.scan(inner, h, (glp, gms))
            h, (nac, _) = _block_decode(shared, h, cfg, False, gac,
                                         cache_len)
            return h, (nms, nac)
        x, (nm, na) = jax.lax.scan(
            group, x, (params["mamba"], caches["mamba"], caches["attn"]))
        new = {"mamba": nm, "attn": na}
    elif fam == "xlstm":
        states = []
        for i, bp in enumerate(params["blocks"]):
            st = caches["blocks"][i]
            if i in cfg.slstm_layers:
                x, ns = XL.slstm_apply(bp, x, cfg, state=st,
                                       return_state=True)
            else:
                x, ns = XL.mlstm_apply(bp, x, cfg, state=st)
            states.append(ns)
        new = {"blocks": states}
    elif fam == "encdec":
        pos = jnp.clip(cache_len - 1, 0, params["pos_dec"]["table"].shape[0]
                       - 1)
        x = x + params["pos_dec"]["table"][pos][:, None, :].astype(cfg.dtype)

        def body(h, inp):
            lp, c, ck, cv = inp
            hn = L.layernorm(lp["ln1"], h, cfg.norm_eps)
            y, nc = A.gqa_decode(lp["attn"], hn, cfg, c, cache_len)
            h = h + y
            hn = L.layernorm(lp["lnx"], h, cfg.norm_eps)
            h = h + A.gqa_apply(lp["xattn"], hn, cfg, causal=False,
                                kv=(ck, cv))
            hn = L.layernorm(lp["ln2"], h, cfg.norm_eps)
            h = h + L.mlp(lp["mlp"], hn, "gelu", nmc_mode=cfg.nmc_mode)
            return h, nc
        x, nc = jax.lax.scan(body, x, (params["layers"], caches["layers"],
                                       caches["cross_k"], caches["cross_v"]))
        new = {"layers": nc, "cross_k": caches["cross_k"],
               "cross_v": caches["cross_v"]}
    else:
        raise ValueError(fam)
    logits = _lm_logits(params, x, cfg)[:, 0]
    return logits, new


def prefill(params, batch: dict, cfg: ModelConfig, max_len: int):
    """Process the prompt, return (last-position logits, caches)."""
    fam = cfg.family
    if fam == "encdec":
        enc = _encode(params, batch["frames"], cfg)
        tokens = batch["tokens"]
        s = tokens.shape[1]
        x = _embed_in(params, tokens, cfg) + \
            params["pos_dec"]["table"][:s].astype(cfg.dtype)

        def body(h, lp):
            enc_kv = _cross_kv(lp, enc, cfg)
            hn = L.layernorm(lp["ln1"], h, cfg.norm_eps)
            y, cache = A.gqa_prefill(lp["attn"], hn, cfg, max_len)
            h = h + y
            hn = L.layernorm(lp["lnx"], h, cfg.norm_eps)
            h = h + A.gqa_apply(lp["xattn"], hn, cfg, causal=False,
                                kv=enc_kv)
            hn = L.layernorm(lp["ln2"], h, cfg.norm_eps)
            h = h + L.mlp(lp["mlp"], hn, "gelu", nmc_mode=cfg.nmc_mode)
            return h, (cache, enc_kv)
        x, (caches, enc_kvs) = jax.lax.scan(_maybe_remat(body, cfg), x,
                                            params["layers"])
        new = {"layers": caches, "cross_k": enc_kvs[0], "cross_v": enc_kvs[1]}
        return _lm_logits(params, x, cfg)[:, -1], new

    if fam == "vlm":
        x = _vlm_embed(params, batch, cfg)
    else:
        x = _embed_in(params, batch["tokens"], cfg)

    if fam in ("dense", "vlm", "moe"):
        use_moe = fam == "moe"
        caches = {}
        if use_moe and cfg.first_dense_layers:
            def bodyd(h, lp):
                h, (cache, _) = _block_prefill(lp, h, cfg, False, max_len)
                return h, cache
            x, cd = jax.lax.scan(_maybe_remat(bodyd, cfg), x,
                                 params["dense_layers"])
            caches["dense_layers"] = cd

        def body(h, lp):
            return _block_prefill(lp, h, cfg, use_moe, max_len)
        x, (cs, rows) = jax.lax.scan(_maybe_remat(body, cfg), x,
                                     params["layers"])
        caches["layers"] = cs
        if rows is not None:
            caches[EXPERT_ROWS] = rows
    elif fam == "hybrid":
        shared = params["shared_attn"]

        def group(h, glp):
            def inner(hh, lp):
                y, st = SSM.mamba2_apply(lp, hh, cfg, return_state=True)
                return hh + y, st
            h, sts = jax.lax.scan(inner, h, glp)
            hn = L.rmsnorm(shared["ln1"], h, cfg.norm_eps)
            y, ac = A.gqa_prefill(shared["attn"], hn, cfg, max_len)
            h = h + y
            hn = L.rmsnorm(shared["ln2"], h, cfg.norm_eps)
            h = h + L.mlp(shared["mlp"], hn, cfg.act, nmc_mode=cfg.nmc_mode)
            return h, (sts, ac)
        x, (ms, ac) = jax.lax.scan(_maybe_remat(group, cfg), x,
                                   params["mamba"])
        caches = {"mamba": ms, "attn": ac}
    elif fam == "xlstm":
        states = []
        for i, bp in enumerate(params["blocks"]):
            if i in cfg.slstm_layers:
                x, st = XL.slstm_apply(bp, x, cfg, return_state=True)
            else:
                x, st = XL.mlstm_apply(bp, x, cfg, return_state=True)
            states.append(st)
        caches = {"blocks": states}
    else:
        raise ValueError(fam)
    return _lm_logits(params, x, cfg)[:, -1], caches
