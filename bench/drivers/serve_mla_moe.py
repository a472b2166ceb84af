"""Driver: a closed loop of clients against ``ServeEngine`` serving a
latent-attention expert model (DeepSeek-V2's architecture) as one chip's
share of an expert-parallel deployment.

The loop is ``serve_closed_loop``'s: the same traffic generator, clients,
warm-up, window and sample of finished requests.  What differs is the
model: its weights (:mod:`bench.drivers.mla_moe_weights`), its reference
(:mod:`bench.ref.deepseek`), its useful work (:mod:`bench.costs_mla_moe`)
and the expert layer's counters.  Inside the traced part of the window
only, after each engine step, the rows every program of the step routed
to each held expert are read back (``ServeEngine.expert_rows``); the
untraced window reads nothing, so its steps gain no sync.

Correctness compares two numbers.  ``serve_closed_loop``'s: the widest
gap by which a served token's reference logit lies below the
reference's best at that position, in standard deviations of the
reference's logits there.  And the mean of those gaps over every checked
token: a fault that moves every token a little, such as the held
experts' part going missing (an eighth of the routed mass), moves the
mean far more than the program's own rounding does, while the widest gap
of the program alone swings with near-ties in the router and the logits.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import costs_mla_moe as costs, harness
from bench.drivers import mla_moe_weights as weights
from bench.drivers.serve_closed_loop import drive, end_to_end, sample, warm
from bench.ref import deepseek as ref


class ExpertCounters:
    """What the held experts' kernel did in the traced steps: expert
    layers called, rows computed, rows routed."""

    def __init__(self, h: harness.Harness, engine):
        self.h = h
        self.calls = self.computed = self.routed = 0
        self._step = engine.step
        self.engine = engine
        engine.step = self.step

    def step(self):
        out = self._step()
        if self.h.traced is not None and self.h.traced[1] is None:
            for rows, counts in self.engine.expert_rows:
                c = np.asarray(counts)
                self.calls += c.shape[0]
                self.computed += rows * c.size
                self.routed += int(c.sum())
        return out


def build(cfg: dict, traffic: dict, seed: int):
    """``(params, engine)``: weights from the seed and a warmed engine."""
    from repro.serve.engine import ServeEngine

    model = weights.model_config(cfg)
    params = weights.make(cfg, seed)
    engine = ServeEngine(model, params,
                         n_slots=int(traffic["n_slots"]),
                         max_len=int(traffic["max_len"]))
    warm(engine, traffic, costs.dims(cfg).vocab)
    return params, engine


def check(params, cfg: dict, traffic: dict, reqs: list, control: bool = False
          ) -> dict:
    """Reference gaps of every served token of ``reqs``, each in standard
    deviations of the reference's logits at its position: their widest
    and their mean; with ``control`` also those of the tokens the 4-bit
    control puts first."""
    pad_to = int(traffic["max_len"])
    pos_pad = int(traffic["output"]["max"])
    gaps, ctrl, spacing = [], [], []
    for req in reqs:
        out = np.asarray(req.out, np.int32)
        seq = np.concatenate([req.prompt, out[:-1]])
        pos = np.arange(len(req.prompt) - 1, len(seq))
        lg = ref.logits(params, cfg, seq, pos, pad_to, pos_pad)
        sd = lg.std(-1)
        gaps.append(ref.served_gaps(lg, out) / sd)
        top2 = np.sort(lg, -1)[:, -2:]
        spacing.append((top2[:, 1] - top2[:, 0]) / sd)
        if control:
            low = ref.logits(params, cfg, seq, pos, pad_to, pos_pad, bits=4)
            ctrl.append(ref.served_gaps(lg, low.argmax(-1)) / sd)
    cat = np.concatenate
    res = {"tokens": int(sum(g.size for g in gaps)),
           "max_gap": float(cat(gaps).max()) if gaps else float("inf"),
           "mean_gap": float(cat(gaps).mean()) if gaps else float("inf"),
           "median_top2_spacing": float(np.median(cat(spacing)))
           if spacing else float("nan")}
    if control:
        res["control_max_gap"] = float(cat(ctrl).max()) if ctrl \
            else float("inf")
        res["control_mean_gap"] = float(cat(ctrl).mean()) if ctrl \
            else float("inf")
    return res


def run(h: harness.Harness, cfg: dict, traffic: dict, seed: int
        ) -> harness.Result:
    d = costs.dims(cfg)
    params, engine = build(cfg, traffic, seed)
    counters = ExpertCounters(h, engine)
    loop = drive(h, engine, traffic, d.vocab, seed)
    attempted = len(loop.finished) + len(loop.flight)
    useful = sum(costs.useful_ops_prefill(d, p) for p in loop.prefill_lens) \
        + sum(costs.useful_ops_decode(d, c) for c in loop.decode_ctx)
    facts = {
        "admit_step_ms": loop.admit_ms,
        "useful_ops": useful,
        "window_s": h.window_s,
        "traced_decode_steps": loop.traced_decode_steps,
        "traced_prefill_lens": loop.traced_prefill_lens,
        "n_slots": int(traffic["n_slots"]),
        "expert_calls": counters.calls,
        "expert_rows_computed": counters.computed,
        "expert_rows_routed": counters.routed,
    }
    e2e = end_to_end(loop, h)
    notes = [f"{loop.tokens} tokens, {len(loop.ttft)} first tokens, "
             f"{len(loop.itl)} gaps, {len(loop.finished)} requests finished "
             f"in the window; ttft p50 {harness.percentile(loop.ttft, 50)!r} "
             f"ms, itl p50 {harness.percentile(loop.itl, 50)!r} ms"]
    reqs = sample(loop.finished, traffic, seed)
    del engine, loop, counters
    gc.collect()
    t = time.perf_counter()
    res = check(params, cfg, traffic, reqs)
    notes.append(f"reference: {len(reqs)} requests, {res['tokens']} served "
                 f"tokens in {time.perf_counter() - t:.1f} s; median "
                 f"top-2 logit spacing {res['median_top2_spacing']!r}")
    limits = traffic["limits"]
    return harness.Result(
        attempted=attempted, failed=0, end_to_end=e2e,
        compared=[harness.Compared("max_logit_gap_sd", res["max_gap"],
                                   float(limits["max_logit_gap_sd"])),
                  harness.Compared("mean_logit_gap_sd", res["mean_gap"],
                                   float(limits["mean_logit_gap_sd"]))],
        facts=facts, notes=notes)
