"""Trace reduction against hand counts: a synthetic trace whose numbers are
worked out below, and a trace recorded on a TPU v5e."""

import pathlib

import pytest

from bench import trace_reduce as T

DATA = pathlib.Path(__file__).parent / "data"
US = 1_000_000      # picoseconds per microsecond


def _events(pairs):
    return "\n".join(f"events {{ metadata_id: {m} offset_ps: {a * US} "
                     f"duration_ps: {(b - a) * US} }}" for m, a, b in pairs)


def _meta(names):
    return "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for i, n in names.items())


SYNTHETIC = f"""
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 1000
    {_events([(1, 0, 100), (2, 10, 40), (3, 50, 90), (4, 60, 70)])} }}
  {_meta({1: "bench.traced", 2: "nmc.run_builds", 3: "serve.step",
          4: "nmc.result"})} }}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000
    {_events([(1, 5, 15), (2, 12, 20), (1, 55, 58), (3, 95, 110)])} }}
  lines {{ id: 3 name: "XLA Modules" timestamp_ns: 1000
    {_events([(4, 5, 20), (4, 55, 58)])} }}
  {_meta({1: "_caesar_kernel", 2: "fusion.1", 3: "copy.2",
          4: "jit_decode_step(7)"})} }}
"""


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData
    return T.reduce(ProfileData.from_text_proto(SYNTHETIC),
                    {"nmc.run_builds", "serve.step", "nmc.result"})


def test_window_and_busy_union(synthetic):
    assert synthetic.window_s == pytest.approx(100e-6)
    # [5, 20] + [55, 58] + [95, 100] (the last op clipped at the window)
    assert synthetic.busy_s == pytest.approx(23e-6)
    assert synthetic.idle_share == pytest.approx(0.77)


def test_kernel_time_by_name(synthetic):
    assert synthetic.ops["_caesar_kernel"] == pytest.approx(13e-6)
    assert synthetic.ops["fusion.1"] == pytest.approx(8e-6)
    assert synthetic.ops["copy.2"] == pytest.approx(5e-6)
    assert synthetic.op_counts["_caesar_kernel"] == 2
    secs, n = synthetic.module_seconds(lambda s: "decode_step" in s)
    assert (secs, n) == (pytest.approx(18e-6), 2)


def test_idle_gaps_by_innermost_host_span(synthetic):
    # [0, 5]: no span; [20, 55]: inside nmc.run_builds at its middle;
    # [58, 95]: serve.step (nmc.result ends at 70, before the middle)
    assert synthetic.gaps == {T.NO_SPAN: pytest.approx(5e-6),
                              "nmc.run_builds": pytest.approx(35e-6),
                              "serve.step": pytest.approx(37e-6)}
    top = synthetic.breakdown()
    assert top["idle_gaps"][0][0] == "serve.step"
    assert top["device_ops"][0][0] == "_caesar_kernel"


def test_union_and_gaps_helpers():
    assert T.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert T.gaps_in([(1, 4), (5, 7)], 0, 10) == [(0, 1), (4, 5), (7, 10)]
    assert T.innermost([("a", 0, 10), ("b", 2, 5)], 3) == "b"
    assert T.innermost([("a", 0, 10)], 11) == T.NO_SPAN


def test_trace_without_window_is_refused():
    from jax.profiler import ProfileData
    text = SYNTHETIC.replace('"bench.traced"', '"other"')
    with pytest.raises(ValueError):
        T.reduce(ProfileData.from_text_proto(text))


@pytest.fixture(scope="module")
def recorded():
    """A trace recorded on one TPU v5e: one run_builds pass of four Table
    V programs on 16 tiles, one nmc.jit call on 4 tiles, and three serving
    steps of a two-layer W8A8 model (``tests/bench/data``)."""
    import gzip
    from jax.profiler import ProfileData
    raw = gzip.decompress((DATA / "v5e_probe.xplane.pb.gz").read_bytes())
    return T.reduce(ProfileData.from_serialized_xspace(raw),
                    {"nmc.run_builds", "nmc.call_async", "nmc.result",
                     "serve.step"})


def test_recorded_trace_against_hand_counts(recorded):
    """Numbers counted from the raw events: the window span, the merged
    busy intervals, and the events of each kernel as named on the chip."""
    from bench import kernels as K
    assert recorded.window_s == pytest.approx(53293491e-9)
    assert recorded.busy_s == pytest.approx(2290141e-9)
    want = {"caesar": (3, 1744574e-9), "carus": (2, 391004e-9),
            "nmc_matmul": (45, 12965e-9), "flash_attention": (2, 4082e-9)}
    for kernel, (n, secs) in want.items():
        pred = K.is_kernel(kernel)
        assert sum(c for op, c in recorded.op_counts.items()
                   if pred(op)) == n, kernel
        assert recorded.op_seconds(pred) == pytest.approx(secs), kernel
    secs, n = recorded.module_seconds(K.is_decode_program)
    assert (n, secs) == (2, pytest.approx((44718 + 45063) * 1e-9))


def test_recorded_gaps_and_breakdown(recorded):
    assert sum(recorded.gaps.values()) == pytest.approx(
        recorded.window_s - recorded.busy_s)
    spans = {label.split(" > ")[0] for label in recorded.gaps}
    assert {"nmc.run_builds", "serve.step", T.NO_SPAN} <= spans
    top = recorded.breakdown()
    assert len(top["device_ops"]) == 10 and len(top["idle_gaps"]) == 10
    assert top["device_ops"][0][0] == "_unknown_.1 s32[8192] custom-call"
    assert not any(" while" in k for k, _ in top["device_ops"])
