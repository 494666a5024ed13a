"""Host spans and counters on the profiler's clock.

``span(name, **counts)`` marks a host phase of the compiled drivers
(``Session.sweep``, ``Session.run``). It always enters
``jax.profiler.TraceAnnotation(name)``, so the span lands on the host plane
of the same profile as the device operations, and a device idle gap can be
put down to what the host was doing. Only while a profiler session records
(``TraceAnnotation.is_enabled()``) does it also keep an in-memory
``Record``: its name, ``perf_counter_ns`` start and end, the index in
``records()`` of the enclosing span on the same thread, and its counts.
Counts are given at entry or added through the yielded handle
(``s.add(key, n)``); every recorded span also counts ``compiles``, the XLA
backend compiles between its entry and exit
(``repro.lint.runtime.compile_count``).

With no profiler session a span costs one annotation enter/exit and one
boolean check; nothing is recorded and no lock is taken. Record times are
host-clock offsets of this process; the profile's own copy of each span
(an event of the same bare name) is what joins them to the device trace.

Every span name starts with ``repro.``. The names and counts, and what reads
them, are listed in README ("Tracing") and PERF.md §3.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

from repro.lint import runtime

MAX_RECORDS = 100_000  # beyond it the newest records are dropped, and counted

_LOCK = threading.Lock()
_DONE: List[tuple] = []  # (seq, parent_seq, name, start_ns, end_ns, counts)
_DROPPED = 0
_SEQ = itertools.count()
_LOCAL = threading.local()


class Record(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]  # index into records() of the enclosing span
    counts: Dict[str, int]


class _Quiet(TraceAnnotation):
    """A span while no profiler session records: the annotation alone."""

    def add(self, key: str, n: int = 1) -> None:
        pass


def _stack() -> list:
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    return stack


class _Span:
    """A recorded span; ``add`` adds to one of its counts."""

    __slots__ = ("name", "counts", "_ann", "_seq", "_parent", "_start",
                 "_compiles")

    def __init__(self, name: str, counts: Dict[str, int]):
        self.name, self.counts = name, counts

    def add(self, key: str, n: int = 1) -> None:
        self.counts[key] = self.counts.get(key, 0) + n

    def __enter__(self):
        stack = _stack()
        self._parent = stack[-1]._seq if stack else None
        self._seq = next(_SEQ)
        stack.append(self)
        runtime.install_compile_counter()
        self._compiles = runtime.compile_count()
        self._ann = TraceAnnotation(self.name, **self.counts)
        self._ann.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self.counts["compiles"] = runtime.compile_count() - self._compiles
        _stack().pop()
        global _DROPPED
        with _LOCK:
            if len(_DONE) < MAX_RECORDS:
                _DONE.append((self._seq, self._parent, self.name,
                              self._start, end, self.counts))
            else:
                _DROPPED += 1
        return False


def span(name: str, **counts: int):
    """A host span named ``name`` (a ``repro.`` name); see the module
    docstring. Use as ``with span("repro.x", lanes=n) as s: ...``."""
    if not TraceAnnotation.is_enabled():
        return _Quiet(name)
    return _Span(name, counts)


def records() -> List[Record]:
    """The finished spans recorded so far, in the order they were entered.
    ``parent`` indexes this list; it is ``None`` for a root, and for a span
    whose enclosing span was dropped, cleared or is still open."""
    with _LOCK:
        done = sorted(_DONE)
    index = {seq: i for i, (seq, *_) in enumerate(done)}
    return [Record(name, start, end, index.get(parent), dict(counts))
            for _, parent, name, start, end, counts in done]


def dropped() -> int:
    """Records dropped because ``MAX_RECORDS`` were held."""
    return _DROPPED


def clear() -> None:
    """Forget every record and the count of dropped ones."""
    global _DROPPED
    with _LOCK:
        _DONE.clear()
        _DROPPED = 0
