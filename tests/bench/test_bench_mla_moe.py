"""The latent-attention expert model's benchmark pieces: its weights, its
reference against the serving engine, its driver and cell, its control
and planted faults, its cost counts, at a smoke size a CPU run can hold."""

import json
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control_mla_moe as control, costs_mla_moe as C, \
    harness as H
from bench.drivers import mla_moe_weights as W, serve_closed_loop, \
    serve_mla_moe as S
from bench.ref import deepseek as ref

FULL = json.loads((H.BENCH / "configs" / "deepseek-v2-lite-w8a8-ep8.json")
                  .read_text())
# every width cut, the layer pattern kept: one dense layer, then two
# expert layers holding 4 of 16 routed experts, 6 a token
SMOKE = dict(FULL, hidden_size=64, intermediate_size=128,
             moe_intermediate_size=48, num_hidden_layers=3,
             num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             vocab_size=512, n_routed_experts=4,
             experts_held={"first": 0, "count": 4, "published": 16})
CHAT96 = H.load_traffic("chat96")
# The chip's limits are read at the published size; the smoke runs keep
# limits of their own, as the qwen smoke runs do.  Here, over 200 checked
# tokens and seeds 20-25, the widest gap of the program reads 0.08-0.23,
# of the 4-bit control 1.7-4.7 and of the held experts skipped 0.65-2.08;
# the mean gap 0.0015-0.0078, 0.57-0.78 and 0.025-0.107.
TRAFFIC = dict(CHAT96, clients=4, n_slots=4, max_len=128,
               prompt_buckets=[8, 24], prompt_weights=[0.5, 0.5], block=10,
               output={"dist": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 2, "max": 32},
               ramp_steps=4, check_tokens=200,
               limits={"max_logit_gap_sd": 0.6, "mean_logit_gap_sd": 0.014})
CHIP = json.loads((H.ROOT / "tests" / "bench" / "data" /
                   "serve_control_dsv2lite_v5e.json").read_text())


@pytest.fixture(scope="module")
def params():
    return W.make(SMOKE, 8)


def _run(seed=2**31 + 17, seconds=1.0):
    return S.run(H.Harness(seconds, time.perf_counter()), SMOKE, TRAFFIC,
                 seed)


def test_config_keeps_the_published_keys():
    """Every key of the source's config.json is in the file with its
    published value, but the experts held here, which ``reduced``
    names; the published count stands beside them."""
    assert FULL["reduced"] == ["n_routed_experts"]
    assert FULL["n_routed_experts"] == FULL["experts_held"]["count"] == 8
    assert FULL["experts_held"] == {"first": 0, "count": 8, "published": 64}
    assert FULL["norm_topk_prob"] is False
    assert FULL["tie_word_embeddings"] is False
    assert FULL["rope_scaling"]["type"] == "yarn"
    d = C.dims(FULL)
    assert (d.layers, d.dense_layers, d.d_model, d.heads, d.kv_lora,
            d.qk_nope, d.qk_rope, d.v_head, d.dense_ff, d.expert_ff,
            d.shared, d.experts, d.top_k, d.vocab) == \
        (27, 1, 2048, 16, 512, 128, 64, 128, 10944, 1408, 2, 64, 6, 102400)
    cfg = W.model_config(FULL)
    assert cfg.held_experts == (0, 8) and cfg.n_experts == 64
    assert cfg.rope_scaling.softmax_scale == pytest.approx(1.58963, abs=1e-5)


def test_weights_are_deterministic_and_served_form():
    a, b = W.make(SMOKE, 5), W.make(SMOKE, 5)
    c = W.make(SMOKE, 2**33 + 5)
    wa, wb, wc = (np.asarray(p["layers"]["moe"]["wi_q"]) for p in (a, b, c))
    assert wa.dtype == np.int8 and np.array_equal(wa, wb)
    assert not np.array_equal(wa, wc)
    moe = a["layers"]["moe"]
    assert moe["wi_q"].shape == (2, 4, 64, 48)          # held experts only
    assert moe["wo_s"].shape == (2, 4, 64)
    assert moe["router"]["w"].shape == (2, 64, 16)      # every expert
    assert moe["router"]["w"].dtype == jnp.float32
    assert a["head"]["w_q"].dtype == jnp.int8           # untied
    assert a["dense_layers"]["mlp"]["wi"]["w_q"].shape == (1, 64, 128)


def _engine_logits(params, cfg, toks, prompt):
    """``ServeEngine``'s prefill of ``toks[:prompt]`` into slot 0, then
    its decode step fed the rest one token at a time (slot 1 idle): the
    logits at positions ``prompt - 1 ...``."""
    from repro.serve import engine as E

    eng = E.ServeEngine(cfg, params, n_slots=2, max_len=64)
    lg, one = eng.prefill(params, {"tokens": jnp.asarray(toks[None,
                                                            :prompt])})
    caches = jax.tree.map(lambda f, o, a: E._insert_slot(f, o, 0, a),
                          eng.caches, one, eng._cache_axes)
    out = [np.asarray(lg[0], np.float32)]
    for t in range(prompt, len(toks)):
        tok = jnp.asarray([[toks[t]], [0]], jnp.int32)
        lg, caches = eng.decode(params, tok, caches,
                                jnp.asarray([t + 1, 1], jnp.int32))
        out.append(np.asarray(lg[0], np.float32))
    return np.stack(out)


TOKS = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)
PROMPT = 32


def _tolerances(params) -> dict:
    """``{name: (reading, limit)}``; each limit with its reason.

    * ``served``: prefill, then 8 decode steps through the latent cache,
      against the float32 reference, in SD of the reference's logits.
      0.5: the program rounds the residual stream, the cache and every
      GEMM's output to bfloat16 and every GEMM's input to int8; it reads
      0.2-0.35 here.
    * ``w8a8``: the prefill with the compute dtype at float32 against the
      reference that rounds every GEMM input to int8 as the configuration
      states, one scale per input tensor and per expert.  1e-3: the two
      then do the same arithmetic but for summation order (7e-7 here);
      experts computed without int8 activations read 0.09.
    """
    cfg = W.model_config(SMOKE)
    pos = np.arange(PROMPT - 1, len(TOKS))
    want = ref.logits(params, SMOKE, TOKS, pos, 64, 16)
    sd = want.std(-1, keepdims=True)
    got = _engine_logits(params, cfg, TOKS, PROMPT)
    exact = ref.logits(params, SMOKE, TOKS[:PROMPT], [PROMPT - 1], PROMPT,
                       1, act_int8=True)
    f32 = _engine_logits(params, cfg.scaled(dtype=jnp.float32),
                         TOKS[:PROMPT], PROMPT)
    return {"served": (float((np.abs(got - want) / sd).max()), 0.5),
            "w8a8": (float(np.abs(f32 - exact).max() / exact.std()), 1e-3)}


def test_engine_agrees_with_the_reference(params):
    """Prefill then decode through ``ServeEngine`` agrees with the plain
    reference within each tolerance; the 4-bit control does not."""
    tol = _tolerances(params)
    for name, (got, limit) in tol.items():
        assert got < limit, (name, got, limit)
    pos = np.arange(PROMPT - 1, len(TOKS))
    want = ref.logits(params, SMOKE, TOKS, pos, 64, 16)
    low = ref.logits(params, SMOKE, TOKS, pos, 64, 16, bits=4)
    ctrl = np.abs(low - want).max() / want.std()
    assert ctrl > 3 * tol["served"][0], (ctrl, tol)


def test_experts_without_int8_activations_fail_a_tolerance(params,
                                                           monkeypatch):
    """The expert banks dequantized to bfloat16 and their inputs left in
    bfloat16 (W8A16, what the expert layer ran before it ran W8A8)."""
    from repro.models import moe

    def w8a16(x_q, w_q, scale, *, act="none", out_dtype=jnp.bfloat16):
        w = (w_q.astype(jnp.float32) * scale[:, None, :]).astype(
            jnp.bfloat16)
        y = jnp.einsum("etk,ekn->etn", x_q.astype(jnp.bfloat16), w,
                       preferred_element_type=jnp.float32)
        return moe.kops.ref.apply_act(y, act).astype(out_dtype)

    monkeypatch.setattr(moe.kops, "nmc_expert_matmul", w8a16)
    monkeypatch.setattr(moe, "_quantize_per_expert",
                        lambda buf: (buf, jnp.ones(buf.shape[0])))
    tol = _tolerances(params)
    assert any(got >= limit for got, limit in tol.values()), tol


def test_every_seed_asks_for_the_same_work_as_chat():
    """chat96 is chat's fit at another load: the same lengths in the same
    order, the same checks."""
    chat = H.load_traffic("chat")
    same = {k: v for k, v in chat.items()
            if k not in ("driver", "about", "clients", "n_slots", "limits")}
    assert {k: CHAT96[k] for k in same} == same
    assert CHAT96["driver"] == "serve_mla_moe"
    assert CHAT96["clients"] == CHAT96["n_slots"] == 96
    a = serve_closed_loop.requests(chat, 102400, 3)
    b = serve_closed_loop.requests(CHAT96, 102400, 3)
    for _ in range(200):
        (p, m), (q, n) = next(a), next(b)
        assert np.array_equal(p, q) and m == n


def test_driver_end_to_end():
    res = _run()
    assert res.correct, [(c.name, c.value, c.limit) for c in res.compared]
    e = res.end_to_end
    assert e["tokens_per_s"] > 0 and e["ttft_p95_ms"] > 0
    assert e["itl_p95_ms"] >= 0 and res.attempted > 0
    f = res.facts
    assert f["useful_ops"] > 0 and f["admit_step_ms"]
    # the untraced window reads no counter
    assert f["expert_calls"] == f["expert_rows_computed"] == 0


def test_counters_read_in_the_traced_steps():
    """Under the profiler every program of a step reports the rows it
    routed to each held expert of each expert layer: a decode step
    computes ``n_slots`` rows per held expert, of which at least one
    token's worth is routed."""
    h = H.Harness(0.5, time.perf_counter(), trace_s=0.3,
                  trace_dir=H.make_trace_dir())
    res = S.run(h, SMOKE, TRAFFIC, 2**31 + 5)
    f = res.facts
    d = C.dims(SMOKE)
    assert f["expert_calls"] > 0 and f["expert_calls"] % d.moe_layers == 0
    assert 0 < f["expert_rows_routed"] < f["expert_rows_computed"]
    assert f["expert_rows_computed"] % d.held == 0
    assert f["traced_decode_steps"] > 0
    share = H.load_metric("moe.pad_share").read(
        types.SimpleNamespace(facts=f))
    assert 0 < share < 100


def test_control_reads_incorrect():
    """The 4-bit reference in the program's place fails the limit."""
    params, engine = S.build(SMOKE, TRAFFIC, 21)
    loop = S.drive(H.Harness(1.0, time.perf_counter()), engine, TRAFFIC,
                   512, 21)
    reqs = S.sample(loop.finished, TRAFFIC, 21)
    res = S.check(params, SMOKE, TRAFFIC, reqs, control=True)
    limits = TRAFFIC["limits"]
    assert res["max_gap"] <= limits["max_logit_gap_sd"], res
    assert res["mean_gap"] <= limits["mean_logit_gap_sd"], res
    assert res["control_max_gap"] > limits["max_logit_gap_sd"] or \
        res["control_mean_gap"] > limits["mean_logit_gap_sd"], res


@pytest.mark.parametrize("fault", ["stale_cache", "token_altered",
                                   "experts_skipped"])
def test_planted_fault_reads_incorrect(monkeypatch, fault):
    """Decode returning the cache it was given; every third step's tokens
    changed; the held experts' routed part set to zero."""
    from repro.serve import engine as E
    monkeypatch.setattr(*control.FAULTS[fault](E))
    res = _run(seed=23)
    assert not res.correct, [(c.name, c.value) for c in res.compared]


def _main_line(monkeypatch, capsys):
    """``bench/run.py`` on the CPU at the smoke size: the look for a chip,
    the peaks and the compile cache are stood in for, the rest runs."""
    from bench import run

    monkeypatch.setattr(H, "accelerator", lambda n: jax.devices()[:n])
    v5e = H.load_peaks("TPU v5 lite")
    monkeypatch.setattr(H, "load_peaks", lambda kind: v5e)
    monkeypatch.setattr(H, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(H, "load_config", lambda entry: SMOKE)
    monkeypatch.setattr(H, "load_traffic", lambda name: TRAFFIC)
    assert run.main(["--workload", "dsv2lite.chat", "--seed",
                     str(2**31 + 41), "--seconds", "1", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_result_line_of_the_cell(monkeypatch, capsys):
    line = _main_line(monkeypatch, capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "ttft_p95_ms",
                                    "itl_p95_ms", "setup_s"}
    assert set(line["compared"]) == {"max_logit_gap_sd", "mean_logit_gap_sd"}


def test_readers_on_hand_counts():
    """``moe.pad_share`` and ``moe.expert_matmul_roofline`` from facts
    and device times given by hand: the kernel's and that of the slice
    that brings it a layer's bank count, other GEMMs and the quantized
    activations do not; both silent without the counters (the parent
    program, or another model)."""
    from types import SimpleNamespace as NS

    pad = H.load_metric("moe.pad_share")
    roof = H.load_metric("moe.expert_matmul_roofline")
    v5e = H.load_peaks("TPU v5 lite")
    facts = {"expert_calls": 26, "expert_rows_computed": 26 * 8 * 96,
             "expert_rows_routed": 26 * 72}
    kernel, banks = 2e-3, 3e-3
    trace = NS(op_seconds=lambda pred: kernel * pred(
        "%nmc_expert_matmul.3 = bf16[8,96,1408]{2,1,0} custom-call(%x)")
        + banks * pred("%dynamic-slice_bitcast_fusion.24 = "
                       "s8[8,2048,1408]{2,1,0:T(8,128)(4,1)S(1)} fusion(%w)")
        + 9.0 * pred("%nmc_matmul.1 = bf16[96,3072]{1,0} custom-call(%x)")
        + 7.0 * pred("%fusion.247 = s8[8,96,2048]{2,1,0} fusion(%x)"))
    secs = kernel + banks
    ctx = NS(facts=facts, cfg=FULL, peaks=v5e, trace=trace)
    assert pad.read(ctx) == pytest.approx(100 * (1 - 72 / 768))
    ops, nbytes = C.expert_matmul(C.dims(FULL), 26, 26 * 72)
    want = 100 * max(ops / v5e["int8_ops_per_s"],
                     nbytes / v5e["hbm_bytes_per_s"]) / secs
    assert roof.read(ctx) == pytest.approx(want)
    empty = NS(facts={"n_slots": 32}, cfg=FULL, peaks=v5e, trace=trace)
    assert pad.read(empty) is None and roof.read(empty) is None


def test_costs_by_hand():
    d = C.dims(FULL)
    # one MoE layer: MLA 13.76 M, shared experts 17.3 M, held experts 69.2 M
    mla = sum(k * n for _, k, n, _ in C.attn_gemms(d, 1, prefill=True))
    assert mla == 2048 * 3072 + 2048 * 512 + 2048 * 64 + 2 * 512 * 2048 \
        + 2048 * 2048 == 13_762_560
    shared = sum(k * n for _, k, n, _ in C.mlp_gemms(d, 1, 2 * 1408))
    assert shared == 3 * 2048 * 2816 == 17_301_504
    held = d.held * sum(k * n for k, n in C.expert_bank_gemms(d))
    assert held == 8 * 3 * 2048 * 1408 == 69_206_016
    # nmc_expert_matmul at one decode layer: 96 rows, 72 of them routed
    ops, nbytes = C.expert_matmul(d, 1, 72)
    assert ops == 2 * 72 * 3 * 2048 * 1408
    assert nbytes == 8 * (3 * 2048 * 1408 + 4 * (1408 + 1408 + 2048)) \
        + 72 * ((2048 + 2 * 1408) * 2 + 1408 + 4 * 2048)
    # decode: q, latent, rope key and out GEMMs; prefill adds the two
    # expansions, and recomputes the latent and rope key for the cache
    dec = C.step_gemms(d, 96, 96, prefill=False)
    pre = C.step_gemms(d, 32, 32, prefill=True)
    assert len(dec) == 4 * 27 + 3 + 3 * 26 + 1
    assert len(pre) == 8 * 27 + 3 + 3 * 26 + 1
    assert dec[-1] == (96, 2048, 102400, False)
    # useful work of a token: routed experts at 6 * 8 / 64 of a token
    tok = C.token_ops(d)
    attn = 2048 * 3072 + 2048 * 512 + 2048 * 64 + 2048 * 2048
    want = 2 * (27 * attn + 3 * 2048 * 10944
                + 26 * (3 * 2048 * 2816 + 0.75 * 3 * 2048 * 1408))
    assert tok == int(want)
    assert C.latent_attention_ops(d, 100) == \
        2 * 16 * (128 * 512 + 100 * 576 + 100 * 512 + 512 * 128)
    assert C.useful_ops_decode(d, 100) == tok + 27 * \
        C.latent_attention_ops(d, 100) + 2 * 2048 * 102400
    assert C.useful_ops_prefill(d, 4) == 4 * (tok + 27 * 2 * 512 * 16 * 256) \
        + 27 * 2 * 16 * 320 * 10 + 2 * 2048 * 102400


def test_limits_lie_between_the_chip_readings():
    """Each limit lies above every reading of the program, read on the
    chip over a dozen seeds or more; the 4-bit control and each planted
    fault (three seeds or more) read above at least one of them, the
    control's widest gap at three times the program's most or more."""
    limits = CHAT96["limits"]
    top, mean = limits["max_logit_gap_sd"], limits["mean_logit_gap_sd"]
    rows = [r for r in CHIP["readings"] if r["cell"] == "dsv2lite.chat"]
    runs = [r for r in CHIP["runs"] if r["cell"] == "dsv2lite.chat"]
    faults = [r for r in CHIP["faults"] if r["cell"] == "dsv2lite.chat"]
    with_mean = [r for r in rows if r.get("program_mean") is not None]
    assert len({r["seed"] for r in with_mean}) >= 12
    assert {r["device"] for r in rows + runs + faults} == {"TPU v5 lite"}
    program = max(r["program"] for r in rows + runs)
    program_mean = max(r["program_mean"] for r in rows + runs
                       if r.get("program_mean") is not None)
    assert program < top and program_mean < mean, (program, program_mean)
    assert min(r["control"] for r in rows) >= 3 * program
    for r in with_mean:
        assert r["control"] > top or r["control_mean"] > mean, r
    assert {r["fault"] for r in faults} == {"stale_cache", "token_altered",
                                            "experts_skipped"}
    for name in {r["fault"] for r in faults}:
        seen = [r for r in faults if r["fault"] == name]
        assert len(seen) >= 3, name
        for r in seen:
            assert r["reading"] > top or \
                (r.get("reading_mean") or 0) > mean, r
