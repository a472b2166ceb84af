"""Serving engine: continuous batching, donated caches, NMC quantized mode."""

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import base as cb
from repro.models import lm
from repro.serve.engine import Request, ServeEngine, quantize_params


def _greedy_reference(cfg, params, prompt, n_new):
    """Step-by-step single-sequence greedy decode as ground truth."""
    lg, caches = lm.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                            cfg, max_len=128)
    toks = [int(jnp.argmax(lg[0]))]
    clen = jnp.asarray([len(prompt) + 1], jnp.int32)
    for _ in range(n_new - 1):
        lg, caches = lm.decode_step(
            params, jnp.asarray([[toks[-1]]], jnp.int32), caches, clen, cfg)
        toks.append(int(jnp.argmax(lg[0])))
        clen = clen + 1
    return toks


def test_continuous_batching_matches_single_stream():
    cfg = cb.get("h2o-danube-1.8b", smoke=True).scaled(dtype=jnp.float32)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 13, 7, 11)]   # more requests than slots
    eng = ServeEngine(cfg, params, n_slots=2, max_len=128)
    for i, pr in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=pr, max_new=6))
    done = eng.run()
    assert len(done) == len(prompts)
    for req in done:
        ref = _greedy_reference(cfg, params, req.prompt, 6)
        assert req.out == ref, (req.rid, req.out, ref)


def test_nmc_quantized_serving_runs():
    """The paper's technique end-to-end in serving: int8 NMC params."""
    cfg = cb.get("qwen1.5-0.5b", smoke=True).scaled(nmc_mode="w8a8")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_params(params, cfg)
    # all 2-D linears converted
    leaves = jax.tree_util.tree_flatten_with_path(qparams)[0]
    assert any("w_q" in str(p) for p, _ in leaves)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    eng = ServeEngine(cfg, qparams, n_slots=1, max_len=64)
    eng.submit(Request(rid=0, prompt=prompt, max_new=4))
    done = eng.run()
    assert len(done) == 1 and len(done[0].out) == 4


def test_w8a8_projection_shards_across_tile_array():
    """ServeEngine.nmc_project runs a W8A8 projection on the simulated
    NMC tile array, sharded across nmc_tiles by the partitioning planner
    (DESIGN.md §9) — bit-exact int8 wrap semantics, identical across tile
    counts, riding the shared nmc runtime's jit cache."""
    from repro import nmc
    cfg = cb.get("qwen1.5-0.5b", smoke=True).scaled(nmc_mode="w8a8")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_params(params, cfg)
    rng = np.random.default_rng(3)
    x8 = rng.integers(-128, 128, (4, 4), dtype=np.int8)
    w8 = rng.integers(-128, 128, (4, 24), dtype=np.int8)
    oracle = (x8.astype(np.int64) @ w8.astype(np.int64)).astype(np.int8)
    eng1 = ServeEngine(cfg, qparams, n_slots=1, max_len=32)
    eng4 = ServeEngine(cfg, qparams, n_slots=1, max_len=32, nmc_tiles=4)
    assert eng1.nmc_tiles == 1 and eng4.nmc_tiles == 4
    y1 = eng1.nmc_project(x8, w8)
    y4 = eng4.nmc_project(x8, w8)
    assert y1.shape == y4.shape == (4, 24)
    assert (y1 == oracle).all() and (y4 == oracle).all()
    # the projection kernels dispatch through the shared default runtime
    # (one jit cache for serving offloads and nmc.jit kernel calls)
    assert nmc.default_runtime().queue.submitted > 0
    # an engine given a PRIVATE queue routes projection waves through it,
    # not the global default (regression: nmc_project used to ignore
    # nmc_queue entirely)
    own = nmc.DispatchQueue(pool=nmc.ResidentPool(
        pool=nmc.default_runtime().bucketed))   # share the jit cache only
    engq = ServeEngine(cfg, qparams, n_slots=1, max_len=32,
                       nmc_queue=own, nmc_tiles=2)
    yq = engq.nmc_project(x8, w8)
    assert (yq == oracle).all()
    assert own.submitted == 2                   # the 2-shard wave
    assert len(own.pool.tiles) == 2             # resident on the own pool


def test_cache_donation_shapes_stable():
    cfg = cb.get("qwen1.5-0.5b", smoke=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, n_slots=2, max_len=32)
    shapes_before = jax.tree.map(lambda x: x.shape, eng.caches)
    eng.submit(Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                       max_new=3))
    eng.run()
    shapes_after = jax.tree.map(lambda x: x.shape, eng.caches)
    assert shapes_before == shapes_after

def test_int8_kv_cache_close_to_bf16():
    """Beyond-paper NMC extension: int8 KV cache (per-token/head scales)
    must track the bf16 cache's logits closely under teacher forcing."""
    import jax.numpy as jnp
    from repro.configs import base as cb
    cfg = cb.get("h2o-danube-1.8b", smoke=True).scaled(dtype=jnp.float32)
    cfg8 = cfg.scaled(kv_cache_dtype="int8")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)))
    forced = rng.integers(0, cfg.vocab_size, (5, 2)).astype(np.int32)
    logit_traces = {}
    for name, c in (("bf16", cfg), ("int8", cfg8)):
        lg, caches = lm.prefill(params, {"tokens": toks}, c, max_len=32)
        clen = jnp.full((2,), 13, jnp.int32)
        trace = [lg]
        for t in range(5):                      # same forced continuation
            tok = jnp.asarray(forced[t][:, None])
            lg, caches = lm.decode_step(params, tok, caches, clen, c)
            clen = clen + 1
            trace.append(lg)
        logit_traces[name] = jnp.stack(trace)
        if name == "int8":
            assert caches["layers"]["k"].dtype == jnp.int8
    scale = float(jnp.std(logit_traces["bf16"]))
    err = float(jnp.max(jnp.abs(logit_traces["bf16"]
                                - logit_traces["int8"])))
    assert err < 0.15 * scale, (err, scale)


def test_moe_expert_quantization():
    """NMC w8 on MoE expert banks: router stays fp (routing margins are
    below int8 noise), experts quantize per-(expert, out-channel)."""
    import jax.numpy as jnp
    from repro.configs import base as cb
    cfg = cb.get("moonshot-v1-16b-a3b", smoke=True).scaled(dtype=jnp.float32)
    p = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)))}
    base, _ = lm.forward(p, batch, cfg)
    qp = quantize_params(p, cfg)
    flat = jax.tree_util.tree_flatten_with_path(qp)[0]
    assert any("wi_q" in str(path) for path, _ in flat)
    assert any("router" in str(path) and "'w'" in str(path)
               for path, _ in flat)          # router NOT quantized
    qcfg = cfg.scaled(nmc_mode="w8")
    qlog, _ = lm.forward(qp, batch, qcfg)
    agree = float((jnp.argmax(base, -1) == jnp.argmax(qlog, -1)).mean())
    assert agree > 0.85, agree
    # decode path runs with quantized experts
    lg, caches = lm.prefill(qp, batch, qcfg, max_len=32)
    lg2, _ = lm.decode_step(qp, jnp.argmax(lg, -1)[:, None].astype(jnp.int32),
                            caches, jnp.full((2,), 17, jnp.int32), qcfg)
    assert np.isfinite(np.asarray(lg2)).all()


def test_nmc_project_cache_keyed_on_full_shape():
    """Regression (PR 8): the projection kernel cache was keyed on (m, k)
    only — two weights with the same activation shape but different output
    widths n must not share a cache entry, and sew=32 exact-accumulation
    callers must not collide with the default wrap-at-8 path."""
    cfg = cb.get("qwen1.5-0.5b", smoke=True).scaled(nmc_mode="w8a8")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_params(params, cfg)
    eng = ServeEngine(cfg, qparams, n_slots=1, max_len=32, nmc_tiles=2)
    rng = np.random.default_rng(11)
    x8 = rng.integers(-128, 128, (4, 4), dtype=np.int8)
    w_wide = rng.integers(-128, 128, (4, 24), dtype=np.int8)
    w_narrow = rng.integers(-128, 128, (4, 8), dtype=np.int8)
    y_wide = eng.nmc_project(x8, w_wide)
    y_narrow = eng.nmc_project(x8, w_narrow)       # same (m, k), new n
    assert y_wide.shape == (4, 24) and y_narrow.shape == (4, 8)
    assert (y_wide ==
            (x8.astype(np.int64) @ w_wide.astype(np.int64))
            .astype(np.int8)).all()
    assert (y_narrow ==
            (x8.astype(np.int64) @ w_narrow.astype(np.int64))
            .astype(np.int8)).all()
    assert (4, 4, 24, 8) in eng._nmc_proj and (4, 4, 8, 8) in eng._nmc_proj
    # sew=32: exact int32 accumulation (true W8A8 GEMM), own cache entry
    y32 = eng.nmc_project(x8, w_wide, sew=32)
    assert (y32 == x8.astype(np.int64) @ w_wide.astype(np.int64)).all()
    assert (4, 4, 24, 32) in eng._nmc_proj


def test_max_new_exact_token_counts():
    """Regression (PR 8): a max_new=1 request used to ride one decode step
    after its prefill and emit two tokens — exhausted slots must retire at
    admission time."""
    cfg = cb.get("h2o-danube-1.8b", smoke=True).scaled(dtype=jnp.float32)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    for max_new in (1, 2, 16):
        eng = ServeEngine(cfg, params, n_slots=2, max_len=128)
        eng.submit(Request(
            rid=0, prompt=rng.integers(0, cfg.vocab_size, 5).astype(np.int32),
            max_new=max_new))
        done = eng.run()
        assert len(done) == 1
        assert len(done[0].out) == max_new, (max_new, done[0].out)


def test_single_layer_cache_slot_insert():
    """Regression (PR 8): slot insertion sniffed the batch axis from leaf
    shapes, which misreads a single-layer stack (layer dim of 1 looks like
    a batch dim of 1) — axes now come from lm.cache_batch_axes."""
    cfg = cb.get("h2o-danube-1.8b", smoke=True).scaled(dtype=jnp.float32,
                                                       n_layers=1)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 7)]
    eng = ServeEngine(cfg, params, n_slots=3, max_len=64)
    for i, pr in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=pr, max_new=4))
    done = eng.run()
    assert len(done) == 3
    for req in done:
        ref = _greedy_reference(cfg, params, req.prompt, 4)
        assert req.out == ref, (req.rid, req.out, ref)


def test_continuous_batching_invariants():
    """PR 8 coverage: FIFO admission order, slot reuse after retirement,
    and run() draining both the request queue and every slot."""
    cfg = cb.get("h2o-danube-1.8b", smoke=True).scaled(dtype=jnp.float32)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    eng = ServeEngine(cfg, params, n_slots=1, max_len=64)
    for i in range(3):
        eng.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, 4).astype(np.int32),
            max_new=2))
    done = eng.run()
    # one slot served all three requests (reused after each retirement),
    # completing in submission order
    assert [r.rid for r in done] == [0, 1, 2]
    assert all(len(r.out) == 2 for r in done)
    # run() drains: no queued requests, no occupied slots
    assert not eng.queue and not any(eng.slot_req)


def test_max_len_truncates_generation():
    """PR 8 coverage: a slot retires when its sequence hits max_len, so a
    request can emit at most max_len - len(prompt) tokens."""
    cfg = cb.get("h2o-danube-1.8b", smoke=True).scaled(dtype=jnp.float32)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
    eng = ServeEngine(cfg, params, n_slots=1, max_len=8)
    eng.submit(Request(rid=0, prompt=prompt, max_new=16))
    done = eng.run()
    assert len(done) == 1
    assert len(done[0].out) == 8 - len(prompt)


def test_max_prefills_bounds_admission():
    """PR 8: admission control — at most max_prefills prefills launch per
    step even with more free slots and queued requests."""
    import pytest
    cfg = cb.get("h2o-danube-1.8b", smoke=True).scaled(dtype=jnp.float32)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(8)
    eng = ServeEngine(cfg, params, n_slots=4, max_len=64, max_prefills=1)
    for i in range(4):
        eng.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, 5).astype(np.int32),
            max_new=3))
    eng._admit()
    assert sum(r is not None for r in eng.slot_req) == 1
    assert len(eng.queue) == 3
    # the bound is per step, not global: everything still completes, in
    # FIFO order, bit-identical to unbounded admission
    done = eng.run()
    assert sorted(r.rid for r in done) == [0, 1, 2, 3]
    eng_ref = ServeEngine(cfg, params, n_slots=4, max_len=64)
    for i, req in enumerate(sorted(done, key=lambda r: r.rid)):
        eng_ref.submit(Request(rid=i, prompt=req.prompt, max_new=3))
    ref = eng_ref.run()
    for a, b in zip(sorted(done, key=lambda r: r.rid),
                    sorted(ref, key=lambda r: r.rid)):
        assert a.out == b.out
    with pytest.raises(ValueError):
        ServeEngine(cfg, params, n_slots=1, max_len=32, max_prefills=0)


def test_dispatch_queue_counters_mixed_traffic():
    """DispatchQueue counter invariants for tile-program traffic through
    one private queue, with serving traffic on the same engine beside
    it."""
    from repro import nmc
    cfg = cb.get("qwen1.5-0.5b", smoke=True).scaled(nmc_mode="w8a8")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_params(params, cfg)
    own = nmc.DispatchQueue(pool=nmc.ResidentPool(
        pool=nmc.default_runtime().bucketed))
    eng = ServeEngine(cfg, qparams, n_slots=2, max_len=32,
                      nmc_queue=own, nmc_tiles=2)
    rng = np.random.default_rng(9)
    x8 = rng.integers(-128, 128, (3, 4), dtype=np.int8)
    w8 = rng.integers(-128, 128, (4, 16), dtype=np.int8)
    y = eng.nmc_project(x8, w8)                     # 2-shard tile wave
    assert (y == (x8.astype(np.int64) @ w8.astype(np.int64))
            .astype(np.int8)).all()
    eng.submit(Request(
        rid=0, prompt=rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
        max_new=3))
    eng.run()                                       # serving traffic
    own.drain()
    # lifecycle conservation: everything submitted launched and resolved
    assert own.submitted == own.launched == own.resolved == 2
    assert own.waves >= 1
    # a second projection through the same queue keeps the books balanced
    eng.nmc_project(x8, w8)
    assert own.submitted == own.launched == own.resolved == 4


def test_serving_runs_its_programs_without_the_nmc_queue():
    """Prefill and decode are called directly: an engine whose NMC queue
    refuses every submit serves requests to completion, with the tokens
    of an engine on the default queue."""
    from repro import nmc

    class RefusingQueue(nmc.DispatchQueue):
        def submit(self, *args, **kwargs):
            raise AssertionError("serving submitted to the NMC queue")

    cfg = cb.get("h2o-danube-1.8b", smoke=True).scaled(dtype=jnp.float32)
    params = lm.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 7)]
    outs = []
    for queue in (RefusingQueue(), None):
        eng = ServeEngine(cfg, params, n_slots=2, max_len=64,
                          nmc_queue=queue)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new=4))
        done = sorted(eng.run(), key=lambda r: r.rid)
        assert [len(r.out) for r in done] == [4, 4, 4]
        outs.append([r.out for r in done])
    assert outs[0] == outs[1]
