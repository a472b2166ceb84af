"""Stage spans of the host program, recorded only while the JAX profiler
records (DESIGN.md §15).

::

    from repro import spans

    with spans.span("frontend.call", call=spans.new_call()):
        ...                                   # nested spans inherit call=

With the profiler off a span costs one check (``TraceMe.is_enabled()``)
and records nothing.  While ``jax.profiler`` records, a span

* enters ``jax.profiler.TraceAnnotation(name)``, so the span sits on the
  profiler's clock beside the device operations (Perfetto, TensorBoard
  and ``bench/trace_reduce.py`` read it there), and
* appends one :class:`Span` record to an in-memory list: name, start and
  end on ``time.perf_counter()``, the enclosing span on this thread, and
  the ids given as keywords, merged over those of the enclosing span.

Records are capped at :data:`CAP`; :func:`dropped` counts what the cap
turned away.  :func:`self_times` is what ``bench/`` readers read: keep its
signature and meaning stable.

Names are ``<layer>.<stage>``, one span per stage per call, work item or
wave: never one per instruction or per operation.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Optional

import jax

#: Most records kept; later spans are counted in :func:`dropped`.
CAP = 200_000

_enabled = jax.profiler.TraceAnnotation.is_enabled
_clock = time.perf_counter
_NO_IDS: dict = {}
_OFF = contextlib.nullcontext()
_records: list["Span"] = []
_dropped = 0
_calls = itertools.count(1)
_local = threading.local()


class Span:
    """One recorded span; ``end`` is None while it is open."""

    __slots__ = ("name", "start", "end", "parent", "ids", "_ann")

    def __init__(self, name: str, parent: Optional["Span"], ids: dict):
        self.name = name
        self.parent = parent
        self.ids = ids
        self.start = self.end = None
        self._ann = None

    @property
    def call(self) -> Optional[int]:
        """Id of the ``nmc.jit`` call this span belongs to, or None."""
        return self.ids.get("call")

    @property
    def duration(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "Span":
        # the profiler's event opens first and closes last, so the
        # bookkeeping here falls inside it, not in the gap between events
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        _stack().append(self)
        self.start = _clock()
        return self

    def __exit__(self, *exc) -> None:
        global _dropped
        self.end = _clock()
        _stack().pop()
        if len(_records) < CAP:
            _records.append(self)
        else:
            _dropped += 1
        self._ann.__exit__(*exc)
        self._ann = None


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def span(name: str, **ids):
    """Context manager over one stage.  ``ids`` (such as ``call=``) are
    kept with the record and inherited by the spans opened inside it."""
    if not _enabled():
        return _OFF
    stack = _stack()
    parent = stack[-1] if stack else None
    if parent is not None and parent.ids:
        ids = {**parent.ids, **ids}
    return Span(name, parent, ids)


def new_call() -> int:
    """A fresh call id, for the root span of one ``nmc.jit`` call."""
    return next(_calls)


def current_ids() -> dict:
    """The ids of the innermost span open on this thread (empty where
    none is open): work that runs later, such as a queued item, keeps
    them to label its own spans.  Do not mutate the result."""
    stack = getattr(_local, "stack", None)
    return stack[-1].ids if stack else _NO_IDS


def records() -> list:
    """The closed spans recorded so far, in the order they ended."""
    return list(_records)


def dropped() -> int:
    """Spans the cap turned away since the last :func:`clear`."""
    return _dropped


def clear() -> None:
    """Forget every record and the dropped count."""
    global _dropped
    _records.clear()
    _dropped = 0


def self_times(t0: float, t1: float) -> dict:
    """``{name: (count, self_seconds)}`` over the spans that end in
    ``[t0, t1]`` (``time.perf_counter()`` seconds).  A span's self time
    is its duration less the durations of the spans recorded inside it."""
    inner: dict = {}
    for s in _records:
        if s.parent is not None:
            inner[id(s.parent)] = inner.get(id(s.parent), 0.0) + s.duration
    out: dict = {}
    for s in _records:
        if t0 <= s.end <= t1:
            n, secs = out.get(s.name, (0, 0.0))
            out[s.name] = (n + 1, secs + s.duration - inner.get(id(s), 0.0))
    return out
