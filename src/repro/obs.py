"""Spans and counters inside the service's host paths.

The tracer is off by default.  Then `span()` returns one shared no-op
context manager and `count()` returns at once: instrumented code pays a
function call, and nothing is allocated, stamped or kept.  `enable()` turns
recording on for the whole process, `disable()` off again; `reset()` clears
what was recorded and `snapshot()` returns it:

    {"spans": {name: {"count", "total_s", "self_s"}},
     "counters": {name: value}}

Memory is aggregates only, one entry per name, never a log of events.  A
span's self time is its duration minus that of the spans entered inside it
on the same thread (a thread-local stack).  With `enable(annotate=True)`
each span is also a `jax.profiler.TraceAnnotation` that carries its ids as
arguments, so in a profiler trace the program's spans share the device's
clock; jax is imported only then, so `repro.store` stays import-light.

Names are `lotaru.<layer>.<phase>`.  Spans and counts sit at layer
boundaries, once per dispatch or pass, never inside a per-query or per-task
loop; counters add bulk values (the bytes of the arrays shipped, the cells
of a padded fit).
"""
from __future__ import annotations

import threading
import time
from contextlib import nullcontext
from typing import Dict, Iterable, List, Optional

_NULL = nullcontext()


class Tracer:
    """Aggregates of named spans and counters, bounded by the number of
    names.  The module-level functions drive the process's one tracer."""

    def __init__(self):
        self.on = False
        self._annotation = None          # TraceAnnotation while annotating
        self._lock = threading.Lock()
        self._local = threading.local()
        self._spans: Dict[str, List[float]] = {}   # name -> [n, total, self]
        self._counters: Dict[str, float] = {}

    def enable(self, annotate: bool = False) -> None:
        if annotate:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
        else:
            self._annotation = None
        self.on = True

    def disable(self) -> None:
        self.on = False

    def reset(self) -> None:
        with self._lock:
            self._spans = {}
            self._counters = {}

    def span(self, name: str, **ids):
        """A context manager timing one phase; `ids` (a dispatch or pass
        number) go to the profiler annotation only."""
        if not self.on:
            return _NULL
        return _Span(self, name, ids)

    def count(self, name: str, n: float) -> None:
        if not self.on:
            return
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def stamp(self) -> Optional[float]:
        """The host clock while recording, else None: a start for `since`
        that another thread may close."""
        return time.perf_counter() if self.on else None

    def since(self, name: str, stamps: Iterable[Optional[float]]) -> None:
        """Record one span per stamp, from the stamp to now.  For intervals
        that cross threads (a caller's batch waiting for the worker), so
        they have no children and no profiler annotation; stamps taken
        while the tracer was off (None) are skipped."""
        if not self.on:
            return
        now = time.perf_counter()
        waits = [now - t for t in stamps if t is not None]
        if waits:
            total = sum(waits)
            self._add(name, len(waits), total, total)

    def snapshot(self) -> dict:
        with self._lock:
            return {"spans": {k: {"count": int(v[0]), "total_s": v[1],
                                  "self_s": v[2]}
                              for k, v in self._spans.items()},
                    "counters": dict(self._counters)}

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _add(self, name: str, n: int, total: float, self_s: float) -> None:
        with self._lock:
            agg = self._spans.get(name)
            if agg is None:
                self._spans[name] = [n, total, self_s]
            else:
                agg[0] += n
                agg[1] += total
                agg[2] += self_s


class _Span:
    __slots__ = ("tracer", "name", "ids", "t0", "child", "ann")

    def __init__(self, tracer: Tracer, name: str, ids: dict):
        self.tracer = tracer
        self.name = name
        self.ids = ids
        self.child = 0.0
        self.ann = None

    def __enter__(self) -> "_Span":
        if self.tracer._annotation is not None:
            self.ann = self.tracer._annotation(self.name, **self.ids)
            self.ann.__enter__()
        self.tracer._stack().append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self.t0
        st = self.tracer._stack()
        st.pop()
        if st:
            st[-1].child += dt
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.tracer._add(self.name, 1, dt, dt - self.child)


TRACER = Tracer()                # the process's one tracer

enable = TRACER.enable
disable = TRACER.disable
reset = TRACER.reset
snapshot = TRACER.snapshot
span = TRACER.span
count = TRACER.count
stamp = TRACER.stamp
since = TRACER.since


def enabled() -> bool:
    """Whether spans and counts are being recorded: guards the work of
    computing a counter's value."""
    return TRACER.on
