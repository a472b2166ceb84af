"""Per-architecture smoke tests + family-specific equivalence checks."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, base as cb
from repro.models import layers as L
from repro.models import lm, moe, ssm, xlstm
from repro.models.config import ModelConfig

RNG = np.random.default_rng(0)


def make_batch(cfg, B=2, S=32):
    b = {"tokens": jnp.asarray(RNG.integers(0, cfg.vocab_size, (B, S)))}
    if cfg.family == "encdec":
        b["frames"] = jnp.asarray(
            RNG.normal(size=(B, cfg.enc_seq, cfg.d_model)).astype(np.float32))
    if cfg.family == "vlm":
        b["images"] = jnp.asarray(RNG.normal(
            size=(B, cfg.n_img_tokens, cfg.d_model)).astype(np.float32))
        b["tokens"] = b["tokens"][:, : S - cfg.n_img_tokens]
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward_and_decode(arch):
    """REDUCED config of the same family: one forward/train step on CPU,
    asserting output shapes + no NaNs, plus prefill + 2 decode steps."""
    cfg = cb.get(arch, smoke=True)
    p = lm.init_params(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg)
    loss, metrics = lm.loss_fn(p, batch, cfg)
    assert np.isfinite(float(loss)), arch
    logits, aux = lm.forward(p, batch, cfg)
    assert logits.shape[0] == 2 and logits.shape[-1] == cfg.vocab_size
    assert not np.isnan(np.asarray(logits, dtype=np.float32)).any()

    lg, caches = lm.prefill(p, batch, cfg, max_len=64)
    assert lg.shape == (2, cfg.vocab_size)
    clen = jnp.full((2,), 33, jnp.int32)
    for _ in range(2):
        tok = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
        lg, caches = lm.decode_step(p, tok, caches, clen, cfg)
        clen = clen + 1
        assert not np.isnan(np.asarray(lg, dtype=np.float32)).any()


def test_train_step_reduces_loss():
    from repro.optim import adamw
    from repro.train import step as step_lib
    cfg = cb.get("qwen1.5-0.5b", smoke=True)
    params, opt = step_lib.init_train_state(jax.random.PRNGKey(0), cfg)
    fn = jax.jit(step_lib.make_train_step(
        cfg, adamw.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=50)))
    batch = make_batch(cfg, B=4, S=64)   # fixed batch -> loss must drop
    first = None
    for i in range(12):
        params, opt, m = fn(params, opt, batch)
        if first is None:
            first = float(m["loss"])
    assert float(m["loss"]) < first - 0.5, (first, float(m["loss"]))


def test_prefill_decode_consistency_dense():
    """Teacher-forced forward logits == prefill+decode logits stepwise."""
    cfg = cb.get("h2o-danube-1.8b", smoke=True).scaled(dtype=jnp.float32)
    p = lm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (1, 16)))
    full_logits, _ = lm.forward(p, {"tokens": toks}, cfg)
    lg, caches = lm.prefill(p, {"tokens": toks[:, :8]}, cfg, max_len=32)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full_logits[:, 7]),
                               atol=2e-2)
    clen = jnp.full((1,), 9, jnp.int32)
    for t in range(8, 12):
        lg, caches = lm.decode_step(p, toks[:, t:t + 1], caches, clen, cfg)
        np.testing.assert_allclose(np.asarray(lg),
                                   np.asarray(full_logits[:, t]), atol=2e-2)
        clen = clen + 1


def test_mamba2_chunked_equals_recurrent():
    cfg = ModelConfig(name="t", family="hybrid", n_layers=1, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=64,
                      ssm_state=16, ssm_head_dim=16, ssm_chunk=8,
                      dtype=jnp.float32)
    p = ssm.mamba2_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64), jnp.float32)
    y_full, cache = ssm.mamba2_apply(p, x, cfg, return_state=True)
    st = ssm.mamba2_state_init(cfg, 2, jnp.float32)
    ys = []
    for t in range(32):
        yt, st = ssm.mamba2_decode(p, x[:, t:t + 1], cfg, st)
        ys.append(yt)
    np.testing.assert_allclose(np.asarray(y_full),
                               np.asarray(jnp.concatenate(ys, 1)), atol=1e-4)
    np.testing.assert_allclose(np.asarray(cache["ssm"]),
                               np.asarray(st["ssm"]), atol=1e-4)


def test_mlstm_chunked_equals_step():
    cfg = ModelConfig(name="t", family="xlstm", n_layers=1, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=64,
                      ssm_chunk=8, dtype=jnp.float32)
    p = xlstm.mlstm_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64), jnp.float32)
    y_full = xlstm.mlstm_apply(p, x, cfg)
    st = xlstm.mlstm_state_init(cfg, 2)
    ys = []
    for t in range(32):
        yt, st = xlstm.mlstm_apply(p, x[:, t:t + 1], cfg, state=st)
        ys.append(yt)
    np.testing.assert_allclose(np.asarray(y_full),
                               np.asarray(jnp.concatenate(ys, 1)), atol=1e-3)


def test_moe_matches_dense_reference():
    cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=96, vocab_size=64,
                      moe=True, n_experts=8, top_k=2, moe_d_ff=96,
                      capacity_factor=4.0, dtype=jnp.float32)
    p = moe.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64), jnp.float32)
    y, aux = moe.moe_apply(p, x, cfg)
    t = x.reshape(-1, 64)
    logits = t @ p["router"]["w"]
    pr = jax.nn.softmax(logits, -1)
    tp, te = jax.lax.top_k(pr, 2)
    tp = tp / tp.sum(-1, keepdims=True)
    h = jnp.einsum("td,edf->tef", t, p["wi"]) * \
        jax.nn.silu(jnp.einsum("td,edf->tef", t, p["wg"]))
    eo = jnp.einsum("tef,efd->ted", h, p["wo"])
    yr = jnp.zeros_like(t)
    for kk in range(2):
        sel = jnp.take_along_axis(
            eo, te[:, kk][:, None, None].repeat(64, -1), 1)[:, 0]
        yr = yr + tp[:, kk:kk + 1] * sel
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr.reshape(x.shape)),
                               atol=1e-4)


def test_nmc_quantized_serving_close_to_fp():
    """The paper's technique as a framework feature: int8 NMC serving logits
    stay close to the bf16 ones (top-1 agreement on most positions)."""
    cfg = cb.get("qwen1.5-0.5b", smoke=True)
    p = lm.init_params(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg)
    base, _ = lm.forward(p, batch, cfg)
    qp = L.quantize_tree(p)
    qcfg = cfg.scaled(nmc_mode="w8a8")
    qlog, _ = lm.forward(qp, batch, qcfg)
    agree = (jnp.argmax(base, -1) == jnp.argmax(qlog, -1)).mean()
    assert float(agree) > 0.9, float(agree)


def test_param_count_sane():
    # rough published sizes (whisper-tiny is ~39M; moonshot/deepseek ~16B)
    for arch in ARCH_IDS:
        cfg = cb.get(arch)
        n = cfg.param_count()
        assert 3e7 < n < 3e10, (arch, n)


def test_yarn_follows_deepseek_v2():
    """DeepSeek-V2-Lite's YaRN: the ramp runs between frequency indices 10
    and 23 of the 64-wide rope, the softmax scale gains mscale^2, and the
    frequencies follow the published formula."""
    import math
    cfg = cb.get("deepseek-v2-lite-16b")
    rs, d, b = cfg.rope_scaling, cfg.qk_rope_dim, cfg.rope_theta
    assert (d, b, rs.factor) == (64, 10000.0, 40.0)

    def corr(n):
        return d * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(b))
    lo, hi = math.floor(corr(32)), math.ceil(corr(1))
    assert (lo, hi) == (10, 23) == L.yarn_range(d, b, rs)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale == pytest.approx(1.26080, abs=1e-5)
    assert rs.softmax_scale == pytest.approx(mscale ** 2) \
        == pytest.approx(1.58963, abs=1e-5)
    assert rs.cos_sin_scale == 1.0
    want = []
    for i in range(d // 2):
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        f = b ** (-2 * i / d)
        want.append(f / 40 * ramp + f * (1 - ramp))
    got = L.yarn_inv_freq(d, b, rs)
    np.testing.assert_allclose(got, np.float32(want), rtol=1e-6)
    assert got[lo] == pytest.approx(b ** (-2 * lo / d))       # not yet cut
    assert got[hi] == pytest.approx(b ** (-2 * hi / d) / 40)  # cut by 40
    cos, sin = L.rope_table(jnp.arange(5), d, b, rs)
    np.testing.assert_allclose(np.asarray(cos),
                               np.cos(np.arange(5)[:, None] * got),
                               atol=1e-6)
    from repro.models import attention
    assert attention.mla_softmax_scale(cfg) == pytest.approx(
        1.58963 / math.sqrt(192), rel=1e-5)


def _served_moe(key, n_experts=16, top_k=4, **kw):
    """A served (int8 banks, f32 router) expert layer of a small config."""
    cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=96, vocab_size=64,
                      moe=True, n_experts=n_experts, top_k=top_k,
                      moe_d_ff=48, n_shared_experts=2, nmc_mode="w8a8",
                      dtype=jnp.float32, **kw)
    p = L.quantize_tree(moe.moe_init(key, cfg))
    return cfg, p


@pytest.mark.parametrize("norm", [False, True])
def test_router_softmax_top_k(norm):
    """Softmax over every expert, greedy top-k; the chosen weights are the
    softmax's own where ``norm_topk_prob`` is false, renormalized to 1
    where it is true."""
    cfg, p = _served_moe(jax.random.PRNGKey(0), norm_topk_prob=norm)
    x = jax.random.normal(jax.random.PRNGKey(1), (10, 64), jnp.float32)
    w, e = moe.route(x, p["router"]["w"], cfg)
    probs = np.asarray(jax.nn.softmax(x @ p["router"]["w"], -1))
    want_e = np.argsort(-probs, -1)[:, :4]
    assert (np.asarray(e) == want_e).all()
    chosen = np.take_along_axis(probs, want_e, -1)
    if norm:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w), chosen, rtol=1e-5)
    assert (np.asarray(w).sum(-1) < 0.999).all() != norm


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips' shares of a 16-expert layer (2 experts each): the
    routed parts every share computes, with the shared experts counted
    once, add up to the layer computed with every expert held; and every
    token reaches its top-k experts (nothing dropped)."""
    cfg, p = _served_moe(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 64), jnp.float32)
    whole, rows = moe.moe_serve(p, x, cfg)
    assert int(rows.sum()) == 2 * 12 * cfg.top_k
    banks = ("wi_q", "wi_s", "wg_q", "wg_s", "wo_q", "wo_s")
    total = L.mlp(p["shared"], x, nmc_mode="w8a8").reshape(-1, 64)
    share_rows = []
    for i in range(8):
        part = {"router": p["router"],
                **{k: p[k][2 * i:2 * i + 2] for k in banks}}
        y, r = moe.routed_part(part, x.reshape(-1, 64),
                               cfg.scaled(expert_first=2 * i,
                                          experts_held=2))
        total = total + y
        share_rows.append(np.asarray(r))
    np.testing.assert_allclose(np.asarray(total).reshape(x.shape),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    assert (np.concatenate(share_rows) == np.asarray(rows)).all()


def test_served_experts_drop_no_token():
    """Every token routed to one expert: its buffer takes them all, and
    the result is each token's top-k SwiGLU computed one by one."""
    cfg, p = _served_moe(jax.random.PRNGKey(4), n_experts=8, top_k=2)
    p["router"]["w"] = p["router"]["w"].at[:, 0].add(50.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64),
                                  jnp.float32))
    y, rows = moe.routed_part(p, x[0], cfg)
    assert int(rows[0]) == 24
    w, e = moe.route(x[0], p["router"]["w"], cfg)

    def bank(k, i):
        return np.asarray(p[f"{k}_q"][i], np.float32) * \
            np.asarray(p[f"{k}_s"][i])[None]
    want = np.zeros((24, 64), np.float32)
    for t in range(24):
        for j in range(2):
            i, xt = int(e[t, j]), np.asarray(x[0, t])
            u = (xt @ bank("wi", i)) * np.asarray(
                jax.nn.silu(xt @ bank("wg", i)))
            want[t] += float(w[t, j]) * (u @ bank("wo", i))
    # int8 activations, one scale per expert input: ~1% of the output
    np.testing.assert_allclose(np.asarray(y), want, rtol=0.05,
                               atol=0.03 * np.abs(want).max())
