"""Operation and byte counts of the LM kernels, against hand counts and
against the shapes the program really traces."""

import json

import pytest

from bench import costs
from bench import harness as H

FULL = json.loads((H.BENCH / "configs" / "qwen1.5-0.5b-w8a8.json")
                  .read_text())
D = costs.dims(FULL)


def test_dims_of_the_published_model():
    assert (D.layers, D.d_model, D.heads, D.kv_heads, D.head_dim, D.d_ff,
            D.vocab) == (24, 1024, 16, 16, 64, 2816, 151936)
    # 4 * 1024^2 attention + 3 * 1024 * 2816 MLP weights per layer
    assert costs.layer_params(D) == 4 * 1024 * 1024 + 3 * 1024 * 2816
    # 0.46 B parameters with the tied table counted once
    total = costs.layer_params(D) * 24 + 151936 * 1024
    assert 0.45e9 < total < 0.47e9


def test_nmc_matmul_hand_count():
    ops, nbytes = costs.nmc_matmul(2, 3, 4, bias=True)
    assert ops == 2 * 2 * 3 * 4
    # x 6 B + w 12 B + scale 16 B + bias 16 B + bf16 out 16 B
    assert nbytes == 6 + 12 + 16 + 16 + 16
    assert costs.nmc_matmul(2, 3, 4, bias=False)[1] == nbytes - 16


def test_flash_attention_hand_count():
    # causal 3 x 3: 6 query-key pairs, 2 heads of 4, QK^T and PV
    ops, nbytes = costs.flash_attention(3, 3, 2, 2, 4)
    assert ops == 4 * 2 * 4 * 6
    assert nbytes == (2 * 3 * 2 + 2 * 3 * 2) * 4 * 2
    assert costs.flash_attention(3, 3, 2, 2, 4, causal=False)[0] == \
        4 * 2 * 4 * 9


def test_useful_ops_hand_count():
    p = 10
    attn = 4 * 16 * 64 * (p * (p + 1) // 2) * 24
    want = p * 2 * costs.layer_params(D) * 24 + attn + 2 * 1024 * 151936
    assert costs.useful_ops_prefill(D, p) == want
    assert costs.useful_ops_decode(D, 7) == \
        2 * costs.layer_params(D) * 24 + 2 * 1024 * 151936 + \
        4 * 16 * 64 * 7 * 24


@pytest.mark.parametrize("m", [4, 24])
def test_step_gemms_are_the_shapes_the_program_traces(monkeypatch, m):
    """Every ``nmc_matmul`` the program's decode and prefill trace, times
    the layers its scan runs them, equals ``costs.step_gemms``."""
    import jax
    import jax.numpy as jnp
    from bench.drivers import lm_weights
    from repro.kernels import ops as kops
    from repro.models import lm

    smoke = dict(FULL, hidden_size=64, intermediate_size=96,
                 num_hidden_layers=3, num_attention_heads=4,
                 num_key_value_heads=4, vocab_size=256)
    d = costs.dims(smoke)
    cfg = lm_weights.model_config(smoke)
    params = jax.eval_shape(lambda: lm_weights._make(
        lm_weights.key_of(0), d, True))
    seen = []
    orig = kops.nmc_matmul

    def record(x_q, w_q, scale, bias=None, **kw):
        seen.append((x_q.shape[0], x_q.shape[1], w_q.shape[1],
                     bias is not None))
        return orig(x_q, w_q, scale, bias, **kw)
    monkeypatch.setattr(kops, "nmc_matmul", record)

    caches = lm.init_caches(params, cfg, m, 32, dtype=jnp.bfloat16)
    jax.eval_shape(lambda p, c: lm.decode_step(
        p, jnp.zeros((m, 1), jnp.int32), c, jnp.ones((m,), jnp.int32), cfg),
        params, caches)
    layer, head = seen[:-1], seen[-1:]
    assert sorted(layer * d.layers + head) == \
        sorted(costs.step_gemms(d, m, m))
    seen.clear()
    jax.eval_shape(lambda p: lm.prefill(
        p, {"tokens": jnp.zeros((1, m), jnp.int32)}, cfg, 32), params)
    layer, head = seen[:-1], seen[-1:]
    assert sorted(layer * d.layers + head) == \
        sorted(costs.step_gemms(d, m, m))
