"""HLO analyzer: trip-count expansion must recover known FLOP counts."""

import jax
import jax.numpy as jnp

from repro.launch import hlo_analysis


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_flops_expanded():
    """k scanned matmuls must count k * 2mnk, not 2mnk (the cost_analysis
    body-once bug this module exists to fix)."""
    m = n = kdim = 128
    k_steps = 7

    def fn(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=k_steps)
        return out

    x = jax.ShapeDtypeStruct((m, kdim), jnp.float32)
    w = jax.ShapeDtypeStruct((kdim, n), jnp.float32)
    txt = _compile_text(fn, x, w)
    r = hlo_analysis.analyze(txt)
    expected = k_steps * 2 * m * n * kdim
    assert abs(r["flops"] - expected) / expected < 0.01, \
        (r["flops"], expected)

    # and the body-once XLA number would be ~1/k of that
    cost = jax.jit(fn).lower(x, w).compile().cost_analysis()
    assert cost["flops"] < r["flops"] / (k_steps - 1)


def test_plain_matmul_flops():
    def fn(a, b):
        return a @ b
    a = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 32), jnp.float32)
    r = hlo_analysis.analyze(_compile_text(fn, a, b))
    assert abs(r["flops"] - 2 * 64 * 256 * 32) / (2 * 64 * 256 * 32) < 0.01


def test_nested_scan_flops():
    def fn(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    r = hlo_analysis.analyze(_compile_text(fn, x, w))
    expected = 15 * 2 * 32 ** 3
    assert abs(r["flops"] - expected) / expected < 0.01, r["flops"]


def test_bytes_positive_and_bounded():
    def fn(a):
        return jnp.tanh(a) + 1.0
    a = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    r = hlo_analysis.analyze(_compile_text(fn, a))
    nbytes = 1024 * 1024 * 4
    assert nbytes <= r["hbm_bytes"] <= 6 * nbytes
