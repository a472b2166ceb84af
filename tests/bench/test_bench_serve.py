"""The serving driver, its traffic, its reference, its control and planted
faults, at a smoke size a CPU test run can hold."""

import json
import time

import numpy as np
import pytest

from bench import control, harness as H
from bench.drivers import lm_weights, serve_closed_loop as S
from bench.ref import qwen as qref

FULL = json.loads((H.BENCH / "configs" / "qwen1.5-0.5b-w8a8.json")
                  .read_text())
SMOKE = dict(FULL, hidden_size=64, intermediate_size=128,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=4, vocab_size=512)
CHAT = H.load_traffic("chat")
# The chip's limits were read at the published size, where the 4-bit
# control reads 3.4 SD or more; at this size it reads 1.3-2.9 and the
# program under 0.2, so the smoke runs keep a limit of their own.
TRAFFIC = dict(CHAT, clients=4, n_slots=4, max_len=128,
               prompt_buckets=[8, 24], prompt_weights=[0.5, 0.5], block=10,
               output={"dist": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 2, "max": 32},
               ramp_steps=4, check_tokens=60,
               limits={"max_logit_gap_sd": 1.0})
CHIP = json.loads((H.ROOT / "tests" / "bench" / "data" /
                   "serve_control_v5e.json").read_text())


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
    same_order = [(len(p), m) for p, m in a] == [(len(p), m) for p, m in c]
    assert same_order == ("order_seed" in tr)
    assert not all(np.array_equal(x[0], y[0]) for x, y in zip(a, c))
    for p, m in a:
        assert len(p) + m <= tr["max_len"]
        assert tr["output"]["min"] <= m <= tr["output"]["max"]


def test_block_shares_follow_the_weights():
    assert S.block_counts([0.35, 0.25, 0.20, 0.12, 0.08], 100) == \
        [35, 25, 20, 12, 8]
    assert S.block_counts([0.5, 0.3, 0.2], 10) == [5, 3, 2]
    assert S.block_counts(CHAT["prompt_weights"], 100) == [52, 20, 15, 8, 5]
    lens = S.output_lengths(CHAT["output"], 100)
    assert 150 < np.median(lens) < 172
    assert min(lens) >= 16 and sorted(lens)[-7:] == [507] + [512] * 6


def test_chat_mix_is_fitted_to_its_source():
    """The chat mix's numbers follow from the moments its source gives: a
    lognormal with the published mean and standard deviation of tokens per
    prompt and per response; a prompt is served at the smallest bucket
    that holds it, and the share over the largest bucket is cut to it."""
    import math
    import statistics

    def fit(moments):
        m, sd = moments["mean"], moments["sd"]
        s2 = math.log(1 + (sd / m) ** 2)
        return statistics.NormalDist(math.log(m) - s2 / 2, math.sqrt(s2))

    prompt = fit(CHAT["fit"]["prompt_tokens"])
    cdf = [prompt.cdf(math.log(b)) for b in CHAT["prompt_buckets"][:-1]]
    shares = np.diff([0.0] + cdf + [1.0])
    assert np.allclose(CHAT["prompt_weights"], shares, atol=1e-4)
    out = fit(CHAT["fit"]["response_tokens"])
    assert math.isclose(CHAT["output"]["median"], math.exp(out.mean),
                        abs_tol=0.01)
    assert math.isclose(CHAT["output"]["sigma"], out.stdev, abs_tol=1e-4)
    assert CHAT["prompt_buckets"][-1] + CHAT["output"]["max"] == \
        CHAT["max_len"]


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


def _faulty_run_is_incorrect(monkeypatch, capsys, fault, entry):
    """Plant ``fault`` (``bench/control.py``'s) in the program and run the
    smoke cell through the driver or through the whole of ``bench/run.py``."""
    from repro.serve import engine as E
    monkeypatch.setattr(*control.FAULTS[fault](E))
    if entry == "driver":
        res = _run(seed=23)
        assert not res.correct, [(c.name, c.value) for c in res.compared]
    else:
        line = _main_line(monkeypatch, capsys, "qwen05b.chat")
        assert line["correct"] is False, line["compared"]


@pytest.mark.parametrize("entry", ["driver", "run.main"])
def test_fault_cache_left_unchanged_reads_incorrect(monkeypatch, capsys,
                                                    entry):
    """Decode returns the KV cache it was given: generated tokens never
    enter it."""
    _faulty_run_is_incorrect(monkeypatch, capsys, "stale_cache", entry)


@pytest.mark.parametrize("entry", ["driver", "run.main"])
def test_fault_token_altered_reads_incorrect(monkeypatch, capsys, entry):
    """Every third step replaces the token it produced for each slot."""
    _faulty_run_is_incorrect(monkeypatch, capsys, "token_altered", entry)


def _main_line(monkeypatch, capsys, cell):
    """``bench/run.py`` on the CPU at the smoke size: the look for a chip,
    the peaks and the compile cache are stood in for, the rest runs."""
    import jax
    from bench import run

    monkeypatch.setattr(H, "accelerator", lambda n: jax.devices()[:n])
    v5e = H.load_peaks("TPU v5 lite")
    monkeypatch.setattr(H, "load_peaks", lambda kind: v5e)
    monkeypatch.setattr(H, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(H, "load_config", lambda entry: SMOKE)
    monkeypatch.setattr(H, "load_traffic", lambda name: TRAFFIC)
    assert run.main(["--workload", cell, "--seed", str(2**31 + 41),
                     "--seconds", "1", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_result_line_of_a_serving_cell(monkeypatch, capsys):
    line = _main_line(monkeypatch, capsys, "qwen05b.chat")
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "ttft_p95_ms",
                                    "itl_p95_ms", "setup_s"}
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == {"max_logit_gap_sd"}


@pytest.mark.parametrize("cell", ["qwen05b.chat", "qwen05b.docqa"])
def test_limit_lies_between_the_chip_readings(cell):
    """Each serving mix's limit lies above every reading of the program
    and below every reading of the 4-bit control and of each planted
    fault, read on the chip over a dozen seeds or more (a fault on three
    or more), the control's least at three times the program's most or
    more: a new limit has to bring its readings with it."""
    limit = H.load_traffic(cell.split(".", 1)[1])["limits"][
        "max_logit_gap_sd"]
    rows = [r for r in CHIP["readings"] if r["cell"] == cell]
    runs = [r for r in CHIP["runs"] if r["cell"] == cell]
    faults = [r for r in CHIP["faults"] if r["cell"] == cell]
    assert len({r["seed"] for r in rows}) >= 12
    assert {r["device"] for r in rows + runs + faults} == {"TPU v5 lite"}
    program = max(r["program"] for r in rows + runs)
    control = min(r["control"] for r in rows)
    assert program < limit < control, (program, limit, control)
    assert control >= 3 * program
    for name in {r["fault"] for r in faults}:
        seen = [r["reading"] for r in faults if r["fault"] == name]
        assert len(seen) >= 3 and limit < min(seen), (name, seen)
    if cell in {w["name"] for w in H.load_benchmark()["workloads"]}:
        assert {r["fault"] for r in faults} >= {"stale_cache"}
