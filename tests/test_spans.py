"""Stage spans (``repro.spans``): the span tree of an ``nmc.jit`` call and
of a ``DispatchQueue.run_builds`` pass, self times, the profiler gate, the
cap, and the ten ``bench/metrics`` readers that read the spans."""

import contextlib
import types
from collections import Counter

import jax
import numpy as np
import pytest

from bench.harness import load_metric
from repro import nmc, spans

#: spans the benchmark harness opens itself: a program span may not share
#: a name with one (bench/trace_reduce.py tells them apart by name)
HARNESS_SPANS = {"bench.traced", "nmc.run_builds", "nmc.call_async",
                 "nmc.result", "serve.step"}


def _axpy(t, x, y):
    t.store((t.load(x) * 3 + t.load(y)).max(0))


X = np.arange(256, dtype=np.int64).astype(np.int8)
Y = np.ones(256, np.int8)


@contextlib.contextmanager
def _recording(tmp_path):
    spans.clear()
    with jax.profiler.trace(str(tmp_path)):
        yield
    spans.clear()


def _jit_call(tiles):
    def run():
        ck = nmc.jit(_axpy, tiles=tiles, runtime=nmc.NmcRuntime())
        ck(X, Y)                                    # warm, unrecorded
        return lambda: ck.call_async(X, Y).result()
    return run


def _run_builds():
    lks = [nmc.jit(_axpy, engine=e, opt="off").lower(X, Y)
           for e in ("caesar", "carus", "caesar")]
    queue = nmc.DispatchQueue()
    queue.run_builds(lks, n_tiles=2)                # warm, unrecorded
    return lambda: queue.run_builds(lks, n_tiles=2)


def _tree(shards):
    """``Counter`` of ``(name, parent name)`` of one call over ``shards``
    tiles (``shards == 1``: the unpartitioned path, no planner)."""
    tree = Counter({("frontend.call", None): 1,
                    ("frontend.trace", "frontend.call"): 1,
                    ("frontend.opt", "frontend.call"): shards,
                    ("frontend.verify", "frontend.call"): 1,
                    ("dispatch.submit", "frontend.call"): shards,
                    ("dispatch.stage", "dispatch.submit"): shards,
                    ("dispatch.launch", None): 1,
                    ("dispatch.wait", None): shards,
                    ("dispatch.extract", None): shards})
    if shards == 1:
        tree[("frontend.lower", "frontend.call")] = 1
    else:
        tree[("frontend.schedule", "frontend.call")] = 1
        tree[("frontend.lower", "frontend.schedule")] = shards
    return tree


# three builds on two tiles: the third stages when its wave launches
RUN_BUILDS_TREE = Counter({("dispatch.submit", None): 3,
                           ("dispatch.stage", "dispatch.submit"): 2,
                           ("dispatch.stage", "dispatch.launch"): 1,
                           ("dispatch.launch", None): 2,
                           ("dispatch.wait", None): 3,
                           ("dispatch.extract", None): 3})

CASES = {"jit_tiles1": (_jit_call(1), _tree(1), True),
         "jit_tiles4": (_jit_call(4), _tree(4), True),
         "run_builds": (_run_builds, RUN_BUILDS_TREE, False)}


def _self(recs):
    """Self seconds of each record, by identity."""
    inner = Counter()
    for r in recs:
        if r.parent is not None:
            inner[id(r.parent)] += r.duration
    return {id(r): r.duration - inner[id(r)] for r in recs}


def _record(case, tmp_path):
    """The records of one run of ``case`` and their ``self_times``."""
    go = CASES[case][0]()
    with _recording(tmp_path):
        go()
        recs = spans.records()
        agg = spans.self_times(min(r.start for r in recs),
                               max(r.end for r in recs))
    return recs, agg


@pytest.mark.parametrize("case", sorted(CASES))
def test_span_tree(case, tmp_path):
    recs, _ = _record(case, tmp_path)
    _, tree, in_call = CASES[case]
    got = Counter((r.name, r.parent.name if r.parent else None)
                  for r in recs)
    assert got == tree
    assert not {r.name for r in recs} & HARNESS_SPANS
    assert all(r.parent is None or r.parent in recs for r in recs)
    calls = {r.call for r in recs}
    if in_call:            # one id on every span, dispatch spans included
        assert len(calls) == 1 and None not in calls
    else:
        assert calls == {None}


@pytest.mark.parametrize("case", sorted(CASES))
def test_self_times_add_up_to_roots(case, tmp_path):
    recs, agg = _record(case, tmp_path)
    own = _self(recs)
    for root in (r for r in recs if r.parent is None):
        def subtree(s):
            while s is not None:
                if s is root:
                    return True
                s = s.parent
            return False
        total = sum(own[id(r)] for r in recs if subtree(r))
        assert total == pytest.approx(root.duration, rel=1e-9, abs=1e-12)
        assert all(own[id(r)] >= 0 for r in recs)
    # the aggregate matches record by record
    assert {k: n for k, (n, _) in agg.items()} == \
        dict(Counter(r.name for r in recs))
    assert sum(s for _, s in agg.values()) == pytest.approx(
        sum(r.duration for r in recs if r.parent is None), rel=1e-9)


@pytest.mark.parametrize("case", sorted(CASES))
def test_nothing_recorded_with_profiler_off(case):
    go = CASES[case][0]()
    spans.clear()
    assert spans.span("frontend.call") is spans.span("dispatch.wait")
    go()
    assert spans.records() == [] and spans.dropped() == 0
    assert spans.current_ids() == {}


def test_cap_counts_what_it_drops(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "CAP", 3)
    with _recording(tmp_path):
        with spans.span("outer", call=7):
            for _ in range(4):
                with spans.span("inner"):
                    pass
        assert [r.name for r in spans.records()] == ["inner"] * 3
        assert spans.dropped() == 2
        assert all(r.call == 7 for r in spans.records())
        spans.clear()
        assert spans.records() == [] and spans.dropped() == 0


# ---------------------------------------------------------------------------
# the ten per-layer readers, on hand-built spans
# ---------------------------------------------------------------------------

class _Clock:
    now = 0.0

    def __call__(self):
        return self.now


def _play(clock, node):
    name, a, b, kids = node
    clock.now = a
    with spans.span(name):
        for k in kids:
            _play(clock, k)
        clock.now = b


def _call(t):
    """One hand-timed ``nmc.jit`` call starting at ``t`` (seconds)."""
    return ("frontend.call", t, t + 10, [
        ("frontend.trace", t + 1, t + 2, []),
        ("frontend.schedule", t + 2, t + 6,
         [("frontend.lower", t + 3, t + 5, [])]),
        ("frontend.opt", t + 6, t + 7, []),
        ("frontend.verify", t + 7, t + 7.5, []),
        ("dispatch.submit", t + 8, t + 9.5,
         [("dispatch.stage", t + 8.5, t + 9, [])])])


HAND = [_call(0), ("dispatch.launch", 10, 12, []),
        ("dispatch.wait", 12, 16, []), ("dispatch.extract", 16, 16.25, []),
        _call(20), ("dispatch.launch", 30, 31, []),
        ("dispatch.wait", 31, 32, []), ("dispatch.extract", 32, 33, []),
        _call(50)]                      # ends after the traced window
WINDOW = (0.0, 40.0)
# ms per call (frontend.*) or per work item (dispatch.*), 2 of each
EXPECTED = {"frontend.trace_ms": 1000.0, "frontend.lower_ms": 2000.0,
            "frontend.opt_ms": 1000.0, "frontend.verify_ms": 500.0,
            "frontend.schedule_ms": 2000.0, "dispatch.submit_ms": 1000.0,
            "dispatch.stage_ms": 500.0, "dispatch.launch_ms": 1500.0,
            "dispatch.wait_ms": 2500.0, "dispatch.extract_ms": 625.0}


def _ctx(traced):
    return types.SimpleNamespace(harness=types.SimpleNamespace(
        traced=traced), facts={}, trace=None)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_reads_hand_built_spans(metric, tmp_path, monkeypatch):
    reader = load_metric(metric)
    clock = _Clock()
    monkeypatch.setattr(spans, "_clock", clock)
    with _recording(tmp_path):
        assert reader.read(_ctx(WINDOW)) is None       # no spans yet
        for node in HAND:
            _play(clock, node)
        assert reader.read(_ctx(WINDOW)) == pytest.approx(EXPECTED[metric])
        assert reader.read(_ctx(None)) is None          # untraced run
        assert reader.read(_ctx((100.0, 200.0))) is None
        spans.clear()
        assert reader.read(_ctx(WINDOW)) is None
