"""Spans and counters of the serving program, on the profiler's clock.

``Recorder.span(name, **ids)`` times a block of host work in two ways at
once.  It opens a ``jax.profiler.TraceAnnotation``, so the block shows on
the profiler's host plane, on the clock of the device operations it
issued.  It also adds the block's duration to a ``(count, seconds)`` total
per name on its ``Recorder``.  ``Recorder.count(name, n)`` adds to a named
counter.

One ``jax.monitoring`` listener per process counts the programs JAX builds
(``programs_built``) and the ones it loads from the persistent compile
cache (``programs_from_cache``).  Each is credited to the recorder of the
innermost span open in the thread at the time, once under the plain name
and once under ``<name>@<span>`` (``programs_built@engine.decode``), so the
counters say which span built a program.

Each recorder also keeps a bounded history of its closed spans and counts
on the host clock (``time.perf_counter``); ``window(t0, t1)`` sums what
every live recorder did between two host times.

There is no switch: with no profiler running a span costs a microsecond or
two.  Open no span inside a per-row or per-token loop; count such work
with one ``count(name, len(rows))``.
"""
from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from typing import Dict, Tuple

import jax

BUILT = "programs_built"
FROM_CACHE = "programs_from_cache"
HISTORY = 1 << 15                  # closed spans and counts kept per recorder

_EVENTS = {"/jax/core/compile/backend_compile_duration": BUILT,
           "/jax/compilation_cache/cache_hits": FROM_CACHE}
_open = threading.local()          # .spans: the thread's open spans
_live: "weakref.WeakSet[Recorder]" = weakref.WeakSet()


def _stack() -> list:
    try:
        return _open.spans
    except AttributeError:
        _open.spans = []
        return _open.spans


class Span:
    """One open span; ``seconds`` holds its duration once it has closed."""
    __slots__ = ("rec", "name", "ann", "t0", "seconds")

    def __init__(self, rec: "Recorder", name: str, ids: dict):
        self.rec, self.name, self.seconds = rec, name, 0.0
        self.ann = jax.profiler.TraceAnnotation(name, **ids)

    def __enter__(self) -> "Span":
        self.ann.__enter__()
        _stack().append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.seconds = t1 - self.t0
        _stack().pop()
        self.ann.__exit__(*exc)
        self.rec._close(self.name, self.seconds, t1)
        return False


class Recorder:
    """Span totals and counters of one component of the program."""

    def __init__(self):
        self._totals: Dict[str, list] = {}
        self._counters: Dict[str, int] = {}
        self._spans: deque = deque(maxlen=HISTORY)    # (t_end, name, s)
        self._counts: deque = deque(maxlen=HISTORY)   # (t, name, n)
        _live.add(self)

    def span(self, name: str, **ids) -> Span:
        return Span(self, name, ids)

    def _close(self, name: str, seconds: float, t_end: float):
        tot = self._totals.get(name)
        if tot is None:
            self._totals[name] = [1, seconds]
        else:
            tot[0] += 1
            tot[1] += seconds
        self._spans.append((t_end, name, seconds))

    def count(self, name: str, n: int = 1):
        self._counters[name] = self._counters.get(name, 0) + n
        self._counts.append((time.perf_counter(), name, n))

    def seconds(self, name: str) -> float:
        return self._totals.get(name, (0, 0.0))[1]

    def totals(self) -> Dict[str, Tuple[int, float]]:
        """Span name -> (times closed, seconds inside)."""
        return {k: (c, s) for k, (c, s) in self._totals.items()}

    def counters(self) -> Dict[str, int]:
        return dict(self._counters)

    def reset(self):
        self._totals.clear()
        self._counters.clear()
        self._spans.clear()
        self._counts.clear()


def window(t0: float, t1: float) -> Tuple[Dict[str, Tuple[int, float]],
                                          Dict[str, int]]:
    """Span totals and counters of every live recorder over the spans
    closed and the counts made between host times ``t0`` and ``t1``
    (``time.perf_counter``), as far as the recorders' histories reach."""
    totals: Dict[str, list] = {}
    counters: Dict[str, int] = {}
    for rec in list(_live):
        for t, name, s in rec._spans:
            if t0 <= t <= t1:
                tot = totals.setdefault(name, [0, 0.0])
                tot[0] += 1
                tot[1] += s
        for t, name, n in rec._counts:
            if t0 <= t <= t1:
                counters[name] = counters.get(name, 0) + n
    return {k: (c, s) for k, (c, s) in totals.items()}, counters


def _on_program(event: str, *args, **kw):
    name = _EVENTS.get(event)
    spans = _stack()
    if name is None or not spans:
        return
    inner = spans[-1]
    inner.rec.count(name)
    inner.rec.count(f"{name}@{inner.name}")


jax.monitoring.register_event_listener(_on_program)
jax.monitoring.register_event_duration_secs_listener(_on_program)
