"""Driver: a closed loop of clients against ``ServeEngine``.

Set-up makes the weights from the seed (``lm_weights``), builds one
``ServeEngine`` and warms exactly the shapes the mix uses: one prefill
per prompt bucket and the decode step.  The clients then start, and the
loop runs ``ramp_steps`` engine steps so that the window opens on a
steady state rather than on every client arriving at once.  In the
window each client sends its next request as soon as the last one
finished (no think time); the loop calls ``ServeEngine.step`` and reads
the tokens each step delivered.

Requests come from the seed in blocks of ``block``: every block holds the
same prompt lengths and output lengths (fixed shares of the buckets,
output lengths at fixed quantiles of their distribution), in an order
and with token ids drawn from the seed.  So every seed asks for the same
work.  A mix that names an ``order_seed`` draws the order from it
instead, the same for every seed: in a closed loop the order decides
which admissions share a step, and with it the tail of TTFT.

Correctness: once the window has closed and the engine is freed, a
sample of the finished requests drawn from the seed, the longest among
them, is run through the plain reference (:mod:`bench.ref.qwen`) over
each prompt with its served tokens.  The number compared is the widest
gap by which a served token's reference logit lies below the
reference's best at that position, in standard deviations of the
reference's logits there.
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from bench import costs, harness
from bench.drivers import lm_weights
from bench.ref import qwen as qref


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

def block_counts(weights, block: int) -> list[int]:
    """Largest-remainder split of ``block`` requests over the buckets."""
    raw = [w * block / sum(weights) for w in weights]
    counts = [int(r) for r in raw]
    for i in sorted(range(len(raw)), key=lambda i: counts[i] - raw[i])[
            :block - sum(counts)]:
        counts[i] += 1
    return counts


def output_lengths(spec: dict, block: int) -> list[int]:
    """Output lengths at the quantiles ``(i + 0.5) / block`` of the mix's
    distribution (``lognormal``: median, sigma; ``uniform``), clipped."""
    lo, hi = int(spec["min"]), int(spec["max"])
    qs = [(i + 0.5) / block for i in range(block)]
    if spec["dist"] == "lognormal":
        nd = statistics.NormalDist(np.log(spec["median"]), spec["sigma"])
        vals = [round(float(np.exp(nd.inv_cdf(q)))) for q in qs]
    elif spec["dist"] == "uniform":
        vals = [lo + int(q * (hi - lo + 1)) for q in qs]
    else:
        raise KeyError(spec["dist"])
    return [min(max(v, lo), hi) for v in vals]


def requests(traffic: dict, vocab: int, seed: int):
    """Endless stream of ``(prompt, max_new)`` drawn from the seed (the
    order from the mix's ``order_seed`` where it names one)."""
    rng = np.random.default_rng([seed, 7])
    order = np.random.default_rng(int(traffic["order_seed"])) \
        if "order_seed" in traffic else rng
    block = int(traffic["block"])
    prompts = np.repeat(traffic["prompt_buckets"],
                        block_counts(traffic["prompt_weights"], block))
    outs = np.asarray(output_lengths(traffic["output"], block))
    while True:
        p, o = order.permutation(prompts), order.permutation(outs)
        for n, m in zip(p, o):
            yield rng.integers(0, vocab, int(n), dtype=np.int32), int(m)


# ---------------------------------------------------------------------------
# the loop
# ---------------------------------------------------------------------------

class _Flight:
    __slots__ = ("req", "submitted", "n", "last")

    def __init__(self, req, now):
        self.req, self.submitted, self.n, self.last = req, now, 0, None


class Loop:
    """The clients, the engine and what the window saw."""

    def __init__(self, h, engine, traffic, vocab, seed):
        from repro.serve.engine import Request

        self.Request = Request
        self.h, self.engine = h, engine
        self.stream = requests(traffic, vocab, seed)
        self.rid = 0
        self.flight: dict[int, _Flight] = {}
        self.done_seen = len(engine.done)
        self.recording = False
        self.tokens = 0
        self.ttft: list[float] = []
        self.itl: list[float] = []
        self.admit_ms: list[float] = []
        self.finished: list = []
        self.prefill_lens: list[int] = []
        self.decode_ctx: list[int] = []
        self.traced_decode_steps = 0
        self.traced_prefill_lens: list[int] = []
        now = time.perf_counter()
        for _ in range(int(traffic["clients"])):
            self.submit(now)

    def submit(self, now):
        prompt, max_new = next(self.stream)
        req = self.Request(self.rid, prompt, max_new=max_new)
        self.rid += 1
        self.engine.submit(req)
        self.flight[req.rid] = _Flight(req, now)

    def step(self):
        t = time.perf_counter()
        with self.h.span("serve.step"):
            decoded = self.engine.step()
        now = time.perf_counter()
        rec = self.recording
        tracing = self.h.traced is not None and self.h.traced[1] is None
        admitted = False
        for f in self.flight.values():
            n = len(f.req.out)
            if n == f.n:
                continue
            p = len(f.req.prompt)
            if f.n == 0:
                admitted = True
                if rec:
                    self.ttft.append((now - f.submitted) * 1e3)
                    self.prefill_lens.append(p)
                if tracing:
                    self.traced_prefill_lens.append(p)
            elif rec:
                self.itl.append((now - f.last) * 1e3)
            if rec:
                self.itl.extend([0.0] * (n - f.n - 1))
                self.tokens += n - f.n
                self.decode_ctx.extend(p + j - 1 for j in range(f.n + 1, n + 1)
                                       if j > 1)
            f.n, f.last = n, now
        if rec and admitted:
            self.admit_ms.append((now - t) * 1e3)
        if tracing and decoded:
            self.traced_decode_steps += 1
        done = self.engine.done
        while self.done_seen < len(done):
            req = done[self.done_seen]
            self.done_seen += 1
            self.flight.pop(req.rid)
            if rec:
                self.finished.append(req)
            self.submit(now)


def warm(engine, traffic: dict, vocab: int) -> None:
    """Compile every shape the mix uses: one prefill per prompt bucket,
    the slot insert and the decode step."""
    from repro.serve.engine import Request

    for i, n in enumerate(traffic["prompt_buckets"]):
        engine.submit(Request(-1 - i, np.zeros(n, np.int32) + i % vocab,
                              max_new=2))
    engine.run()
    engine.done.clear()


def build(cfg: dict, traffic: dict, seed: int):
    """``(params, engine)``: weights from the seed and a warmed engine."""
    from repro.serve.engine import ServeEngine

    params = lm_weights.make(cfg, seed)
    engine = ServeEngine(lm_weights.model_config(cfg), params,
                         n_slots=int(traffic["n_slots"]),
                         max_len=int(traffic["max_len"]))
    warm(engine, traffic, costs.dims(cfg).vocab)
    return params, engine


def drive(h: harness.Harness, engine, traffic: dict, vocab: int, seed: int
          ) -> Loop:
    loop = Loop(h, engine, traffic, vocab, seed)
    for _ in range(int(traffic["ramp_steps"])):
        loop.step()
    h.open_window()
    loop.recording = True
    while not h.done():
        loop.step()
    h.close_window()
    loop.recording = False
    return loop


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

CHECK_REQUESTS = 8      # fewest finished requests the check compares


def sample(finished: list, traffic: dict, seed: int) -> list:
    """The longest finished request and others drawn from the seed, until
    ``check_tokens`` served tokens and ``CHECK_REQUESTS`` requests are
    covered (so one long request never stands for every slot)."""
    if not finished:
        return []
    rng = np.random.default_rng([seed, 3])
    longest = max(range(len(finished)), key=lambda i: len(finished[i].out))
    order = [longest] + [int(i) for i in rng.permutation(len(finished))
                         if i != longest]
    out, n = [], 0
    for i in order:
        if n >= int(traffic["check_tokens"]) and \
                len(out) >= CHECK_REQUESTS:
            break
        out.append(finished[i])
        n += len(finished[i].out)
    return out


def check(params, cfg: dict, traffic: dict, reqs: list, control: bool = False
          ) -> dict:
    """Reference gaps of every served token of ``reqs``, each in standard
    deviations of the reference's logits at its position (so the number
    does not depend on the scale of the weights); with ``control`` also
    the gaps of the tokens the 4-bit control puts first."""
    pad_to = int(traffic["max_len"])
    pos_pad = int(traffic["output"]["max"])
    gaps, ctrl, spacing = [], [], []
    for req in reqs:
        out = np.asarray(req.out, np.int32)
        seq = np.concatenate([req.prompt, out[:-1]])
        pos = np.arange(len(req.prompt) - 1, len(seq))
        ref = qref.logits(params, cfg, seq, pos, pad_to, pos_pad)
        sd = ref.std(-1)
        gaps.append(qref.served_gaps(ref, out) / sd)
        top2 = np.sort(ref, -1)[:, -2:]
        spacing.append((top2[:, 1] - top2[:, 0]) / sd)
        if control:
            low = qref.logits(params, cfg, seq, pos, pad_to, pos_pad, bits=4)
            ctrl.append(qref.served_gaps(ref, low.argmax(-1)) / sd)
    cat = np.concatenate
    res = {"tokens": int(sum(g.size for g in gaps)),
           "max_gap": float(cat(gaps).max()) if gaps else float("inf"),
           "median_top2_spacing": float(np.median(cat(spacing)))
           if spacing else float("nan")}
    if control:
        res["control_max_gap"] = float(cat(ctrl).max()) if ctrl \
            else float("inf")
    return res


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def end_to_end(loop: Loop, h: harness.Harness) -> dict:
    return {"tokens_per_s": loop.tokens / h.window_s,
            "ttft_p95_ms": harness.percentile(loop.ttft, 95),
            "itl_p95_ms": harness.percentile(loop.itl, 95)}


def run(h: harness.Harness, cfg: dict, traffic: dict, seed: int
        ) -> harness.Result:
    d = costs.dims(cfg)
    params, engine = build(cfg, traffic, seed)
    loop = drive(h, engine, traffic, d.vocab, seed)
    n_slots = int(traffic["n_slots"])
    attempted = len(loop.finished) + len(loop.flight)
    useful = sum(costs.useful_ops_prefill(d, p) for p in loop.prefill_lens) \
        + sum(costs.useful_ops_decode(d, c) for c in loop.decode_ctx)
    facts = {
        "admit_step_ms": loop.admit_ms,
        "useful_ops": useful,
        "window_s": h.window_s,
        "traced_decode_steps": loop.traced_decode_steps,
        "traced_prefill_lens": loop.traced_prefill_lens,
        "n_slots": n_slots,
    }
    e2e = end_to_end(loop, h)
    notes = [f"{loop.tokens} tokens, {len(loop.ttft)} first tokens, "
             f"{len(loop.itl)} gaps, {len(loop.finished)} requests finished "
             f"in the window; ttft p50 {harness.percentile(loop.ttft, 50)!r} "
             f"ms, itl p50 {harness.percentile(loop.itl, 50)!r} ms"]
    reqs = sample(loop.finished, traffic, seed)
    del engine, loop
    gc.collect()
    t = time.perf_counter()
    res = check(params, cfg, traffic, reqs)
    notes.append(f"reference: {len(reqs)} requests, {res['tokens']} served "
                 f"tokens in {time.perf_counter() - t:.1f} s; median "
                 f"top-2 logit spacing {res['median_top2_spacing']!r}")
    return harness.Result(
        attempted=attempted, failed=0, end_to_end=e2e,
        compared=[harness.Compared(
            "max_logit_gap_sd", res["max_gap"],
            float(traffic["limits"]["max_logit_gap_sd"]))],
        facts=facts, notes=notes)
