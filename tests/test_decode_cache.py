"""Decode's cache write: one row per slot, into the donated stacked cache.

The decode step of the dense, vlm and moe families carries each stacked
cache leaf through its layer loop and scatters the new token's row of
every slot into it.  Two checks:

* it leaves the same cache contents and logits as the path it replaced
  (kept here as the reference): the stack scanned as ``xs``, each layer's
  cache rebuilt with a one-hot blend and restacked as ``ys``;
* its optimized program holds nothing of a cache leaf's size but the
  in-place writes and attention's reads, and keeps no temporary that
  large, while the reference's program does.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cb
from repro.models import attention as A
from repro.models import lm
from repro.serve.engine import make_decode_step

B, MAX_LEN, STEPS = 4, 32, 3


def _one_hot_rows(cache, rows, idx, layer=None, *, pos_axis):
    """The reference's write: one layer's whole leaf rewritten as
    ``cache * (1 - oh) + new * oh``, ``oh`` one-hot at each slot's
    position."""
    assert layer is None, "the reference scans one layer's leaf at a time"
    s = cache.shape[pos_axis]
    oh = jax.nn.one_hot(idx, s, dtype=cache.dtype)
    oh = oh.reshape((idx.shape[0],) + (1,) * (pos_axis - 1) + (s,)
                    + (1,) * (cache.ndim - pos_axis - 1))
    new = jnp.expand_dims(rows.astype(cache.dtype), pos_axis)
    return cache * (1 - oh) + new * oh


def _one_hot_decode(params, tokens, caches, cache_len, cfg):
    """The reference decode step, with ``A._write_rows`` patched to
    :func:`_one_hot_rows`: each stack scanned as ``xs`` and its new
    layers restacked as ``ys``."""
    x = lm._embed_in(params, tokens, cfg)
    new = dict(caches)

    def run(x, layer_params, stack, use_moe):
        def body(h, inp):
            lp, c = inp
            h, (nc, _) = lm._block_decode(lp, h, cfg, use_moe, c, cache_len)
            return h, nc
        return jax.lax.scan(body, x, (layer_params, stack))

    if "dense_layers" in caches:
        x, new["dense_layers"] = run(x, params["dense_layers"],
                                     caches["dense_layers"], False)
    x, new["layers"] = run(x, params["layers"], caches["layers"],
                           cfg.family == "moe")
    return lm._lm_logits(params, x, cfg)[:, 0], new


# (config, max_len, each slot's length at the first step); over STEPS
# steps the slots write positions 0-2, 5-7, 16-18 and 29-31 (= max_len - 1)
CASES = {
    "gqa_bf16": (lambda: cb.get("qwen1.5-0.5b", smoke=True).scaled(
        n_layers=3), [1, 6, 17, MAX_LEN - 2]),
    "gqa_int8": (lambda: cb.get("qwen1.5-0.5b", smoke=True).scaled(
        n_layers=3, kv_cache_dtype="int8"), [1, 6, 17, MAX_LEN - 2]),
    # window 32 over a 32-position cache: a ring; slot 2 wraps from
    # position 31 to 0, slot 3 sits a lap ahead
    "ring": (lambda: cb.get("h2o-danube-1.8b", smoke=True).scaled(
        n_layers=3), [1, MAX_LEN - 2, MAX_LEN, 2 * MAX_LEN - 2]),
    # one dense layer, then two MoE layers
    "mla_moe": (lambda: cb.get("deepseek-v2-lite-16b", smoke=True).scaled(
        n_layers=3), [1, 6, 17, MAX_LEN - 2]),
}


def _filled_caches(params, cfg, key):
    """The cache tree of ``B`` x ``MAX_LEN`` with every entry random (the
    scales positive), so a write that lands anywhere else shows."""
    caches = lm.init_caches(params, cfg, B, MAX_LEN, dtype=jnp.bfloat16)
    leaves, tree = jax.tree_util.tree_flatten_with_path(caches)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, leaf), k in zip(leaves, keys):
        name = jax.tree_util.keystr(path)
        if leaf.dtype == jnp.int8:
            out.append(jax.random.randint(k, leaf.shape, -127, 128,
                                          jnp.int8))
        elif name.endswith("_s']"):
            out.append(jax.random.uniform(k, leaf.shape, jnp.float32, 0.005,
                                          0.02).astype(leaf.dtype))
        else:
            out.append(jax.random.normal(k, leaf.shape).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.mark.parametrize("case", sorted(CASES))
def test_row_write_matches_the_one_hot_path(monkeypatch, case):
    """Over several steps, slots at different lengths: the same cache
    entries, value for value, and the same logits."""
    make_cfg, lens = CASES[case]
    cfg = make_cfg()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    caches = _filled_caches(params, cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(2)
    tokens = [jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
              for _ in range(STEPS)]

    def steps(fn, caches):
        clen = jnp.asarray(lens, jnp.int32)
        logits = []
        for tok in tokens:
            lg, caches = fn(params, tok, caches, clen)
            logits.append(lg)
            clen = clen + 1
        return jnp.stack(logits), caches

    got_logits, got = steps(jax.jit(make_decode_step(cfg)), caches)
    monkeypatch.setattr(A, "_write_rows", _one_hot_rows)
    want_logits, want = steps(jax.jit(functools.partial(
        _one_hot_decode, cfg=cfg)), caches)

    assert jax.tree.structure(got) == jax.tree.structure(caches)
    for name in ("dense_layers", "layers"):
        if name not in want:
            continue
        for g, w in zip(jax.tree.leaves(got[name]),
                        jax.tree.leaves(want[name])):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w, np.float32))
    g = np.asarray(got_logits, np.float32)
    w = np.asarray(want_logits, np.float32)
    np.testing.assert_allclose(g, w, rtol=0, atol=1e-3 * np.abs(w).max())


# ---------------------------------------------------------------------------
# what the optimized program moves
# ---------------------------------------------------------------------------

_ELEMENTWISE = {
    "abs", "add", "and", "clamp", "compare", "convert", "divide",
    "exponential", "maximum", "minimum", "multiply", "negate", "or",
    "select", "subtract"}
#: Ops that rewrite or move a whole cache leaf or layer: what the one-hot
#: blend (elementwise), the restack (dynamic-update-slice of a layer into
#: the stack) and layout changes (copy) compile to.
_MOVES = {"copy", "broadcast", "dynamic-update-slice"} | _ELEMENTWISE
_INSTR = re.compile(r"\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(")


def _cache_moves(compiled, caches) -> list:
    """Every instruction of the optimized program, fused ones included,
    that rewrites or moves something shaped like a stacked cache leaf or
    one layer of it."""
    sizes = set()
    for leaf in jax.tree.leaves(caches):
        sizes |= {leaf.shape, leaf.shape[1:], (1,) + leaf.shape[1:]}
    found = []
    for line in compiled.as_text().splitlines():
        m = _INSTR.match(line)
        if m and m.group(3) in _MOVES:
            shape = tuple(int(d) for d in m.group(2).split(",") if d)
            if shape in sizes:
                found.append(line.strip()[:120])
    return found


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "deepseek-v2-lite-16b"])
def test_decode_program_moves_no_cache(monkeypatch, arch):
    """The jitted, donated decode program of a small dense and a small
    latent-attention model: no copy, broadcast, restack or elementwise op
    of a cache leaf's or a layer's size, temporaries under one stacked
    leaf, every leaf aliased to its input.  The reference's program
    fails the first two.  f32 caches, so the CPU compiler widens none,
    and long enough that a leaf outweighs the step's other temporaries."""
    cfg = cb.get(arch, smoke=True).scaled(n_layers=4, dtype=jnp.float32)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    caches = lm.init_caches(params, cfg, B, 64 * MAX_LEN, dtype=jnp.float32)
    leaf = max(x.nbytes for x in jax.tree.leaves(caches["layers"]))
    args = (params, jnp.zeros((B, 1), jnp.int32), caches,
            jnp.full((B,), 5, jnp.int32))

    def compile_(fn):
        return jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()

    now = compile_(make_decode_step(cfg))
    assert _cache_moves(now, caches) == []
    mem = now.memory_analysis()
    assert mem.temp_size_in_bytes < leaf, (mem.temp_size_in_bytes, leaf)
    cache_bytes = sum(x.nbytes for x in jax.tree.leaves(caches))
    assert mem.alias_size_in_bytes >= cache_bytes

    monkeypatch.setattr(A, "_write_rows", _one_hot_rows)
    before = compile_(functools.partial(_one_hot_decode, cfg=cfg))
    assert _cache_moves(before, caches)
    assert before.memory_analysis().temp_size_in_bytes >= leaf
