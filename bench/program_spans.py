"""Per-layer readings from the program's own stage spans
(``repro.spans``, recorded while the profiler ran the traced window).

A reading is one stage's self time in the harness's traced window
(``ctx.harness.traced``), in ms, per span of a unit stage: per
``frontend.call`` (one ``nmc.jit`` call) or per ``dispatch.submit`` (one
work item, one tile program).  A program without ``repro.spans``, a run
without a traced window, or a window without both spans reads None.
"""

from __future__ import annotations


def ms_per(ctx, stage: str, unit: str):
    try:
        from repro import spans
    except ImportError:
        return None
    traced = getattr(ctx.harness, "traced", None)
    if not traced or traced[1] is None:
        return None
    times = spans.self_times(*traced)
    n = times.get(unit, (0, 0.0))[0]
    if stage not in times or not n:
        return None
    return times[stage][1] * 1e3 / n
