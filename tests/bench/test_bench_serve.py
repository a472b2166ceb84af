"""The serving driver, its traffic, its reference, its control and planted
faults, at a smoke size a CPU test run can hold."""

import json
import time

import numpy as np
import pytest

from bench import harness as H
from bench.drivers import lm_weights, serve_closed_loop as S
from bench.ref import qwen as qref

FULL = json.loads((H.BENCH / "configs" / "qwen1.5-0.5b-w8a8.json")
                  .read_text())
SMOKE = dict(FULL, hidden_size=64, intermediate_size=128,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=4, vocab_size=512)
CHAT = H.load_traffic("chat")
TRAFFIC = dict(CHAT, clients=4, n_slots=4, max_len=128,
               prompt_buckets=[8, 24], prompt_weights=[0.5, 0.5], block=10,
               output={"dist": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 2, "max": 32},
               ramp_steps=4, check_tokens=60)


def _run(seed=2**31 + 17, seconds=1.0):
    return S.run(H.Harness(seconds, time.perf_counter()), SMOKE, TRAFFIC,
                 seed)


@pytest.mark.parametrize("name", ["chat", "docqa"])
def test_every_seed_asks_for_the_same_work(name):
    tr = H.load_traffic(name)
    block = tr["block"]

    def sizes(seed):
        g = S.requests(tr, 151936, seed)
        return [next(g) for _ in range(2 * block)]
    a, b, c = sizes(1), sizes(1), sizes(2**31 + 3)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for x, y in zip(a, b))
    for k in range(2):
        blk = slice(k * block, (k + 1) * block)
        assert sorted((len(p), m) for p, m in a[blk]) != [] and \
            sorted(len(p) for p, _ in a[blk]) == \
            sorted(len(p) for p, _ in c[blk])
        assert sorted(m for _, m in a[blk]) == sorted(m for _, m in c[blk])
    assert not all(np.array_equal(x[0], y[0]) for x, y in zip(a, c))
    for p, m in a:
        assert len(p) + m <= tr["max_len"]
        assert tr["output"]["min"] <= m <= tr["output"]["max"]


def test_block_shares_follow_the_weights():
    assert S.block_counts([0.35, 0.25, 0.20, 0.12, 0.08], 100) == \
        [35, 25, 20, 12, 8]
    assert S.block_counts([0.5, 0.3, 0.2], 10) == [5, 3, 2]
    lens = S.output_lengths(CHAT["output"], 100)
    assert 120 < np.median(lens) < 136
    assert min(lens) >= 16 and max(lens) <= 512


def test_sample_takes_the_longest_and_several_requests():
    """One long request that covers ``check_tokens`` alone still leaves
    ``CHECK_REQUESTS`` requests in the sample, the longest among them."""
    from types import SimpleNamespace as R
    finished = [R(out=[0] * n) for n in [20] * 30 + [512] + [30] * 30]
    tr = dict(CHAT, check_tokens=400)
    got = S.sample(finished, tr, 2**31 + 7)
    assert got[0] is finished[30] and len(got) == S.CHECK_REQUESTS
    assert len({id(r) for r in got}) == S.CHECK_REQUESTS
    assert S.sample(finished, tr, 2**31 + 7) == got
    assert S.sample(finished, tr, 5) != got
    assert S.sample(finished[:3], tr, 1) and not S.sample([], tr, 1)


def test_weights_are_deterministic_and_served_form():
    a = lm_weights.make(SMOKE, 5)
    b = lm_weights.make(SMOKE, 5)
    c = lm_weights.make(SMOKE, 2**33 + 5)
    wa, wb, wc = (np.asarray(p["layers"]["mlp"]["wi"]["w_q"]) for p in (a, b, c))
    assert wa.dtype == np.int8 and np.array_equal(wa, wb)
    assert not np.array_equal(wa, wc)
    head = a["head"]
    table = np.asarray(a["embed"]["table"])
    deq = np.asarray(head["w_q"], np.float32) * np.asarray(head["scale"])
    assert np.abs(deq - table.T).max() <= np.asarray(head["scale"]).max()


def test_reference_agrees_with_the_program_prefill():
    """At a small size the program's prefill logits and the reference's
    differ by much less than the 4-bit control's."""
    import jax
    from repro.serve.engine import ServeEngine

    params = lm_weights.make(SMOKE, 8)
    eng = ServeEngine(lm_weights.model_config(SMOKE), params, n_slots=2,
                      max_len=64)
    toks = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)
    got, _ = eng.prefill(params, {"tokens": jax.numpy.asarray(toks[None])})
    got = np.asarray(got[0], np.float32)
    ref = qref.logits(params, SMOKE, toks, [39], 64, 4)[0]
    low = qref.logits(params, SMOKE, toks, [39], 64, 4, bits=4)[0]
    sd = ref.std()
    prog_err = np.abs(got - ref).max() / sd
    ctrl_err = np.abs(low - ref).max() / sd
    assert prog_err < 0.5, prog_err
    assert ctrl_err > 3 * prog_err, (prog_err, ctrl_err)


def test_driver_end_to_end():
    res = _run()
    assert res.correct, [(c.name, c.value, c.limit) for c in res.compared]
    e = res.end_to_end
    assert e["tokens_per_s"] > 0 and e["ttft_p95_ms"] > 0
    assert e["itl_p95_ms"] >= 0 and res.attempted > 0
    assert res.facts["useful_ops"] > 0 and res.facts["admit_step_ms"]


def test_control_reads_incorrect():
    """The 4-bit reference in the program's place fails the limit."""
    params, engine = S.build(SMOKE, TRAFFIC, 21)
    loop = S.drive(H.Harness(1.0, time.perf_counter()), engine, TRAFFIC,
                   512, 21)
    reqs = S.sample(loop.finished, TRAFFIC, 21)
    res = S.check(params, SMOKE, TRAFFIC, reqs, control=True)
    limit = TRAFFIC["limits"]["max_logit_gap_sd"]
    assert res["max_gap"] <= limit < res["control_max_gap"], res


def test_fault_cache_left_unchanged_reads_incorrect(monkeypatch):
    """Decode returns the KV cache it was given: generated tokens never
    enter it."""
    from repro.serve import engine as E
    orig = E.make_decode_step

    def stale(cfg):
        step = orig(cfg)

        def decode(params, tokens, caches, cache_len):
            logits, _ = step(params, tokens, caches, cache_len)
            return logits, caches
        return decode
    monkeypatch.setattr(E, "make_decode_step", stale)
    res = _run(seed=23)
    assert not res.correct, [(c.name, c.value) for c in res.compared]


def test_fault_token_altered_reads_incorrect(monkeypatch):
    """Every third step replaces the token it produced for each slot."""
    from repro.serve import engine as E
    orig = E.ServeEngine.step
    count = [0]

    def step(self):
        out = orig(self)
        count[0] += 1
        if count[0] % 3 == 0:
            for req in self.slot_req:
                if req is not None and req.out:
                    req.out[-1] = (req.out[-1] + 1) % self.cfg.vocab_size
        return out
    monkeypatch.setattr(E.ServeEngine, "step", step)
    res = _run(seed=29)
    assert not res.correct, [(c.name, c.value) for c in res.compared]
