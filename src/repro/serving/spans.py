"""Host spans of the serving engine, kept in a ring and shown to the profiler.

``SpanLog.span(name, **args)`` is a context manager that opens
``jax.profiler.TraceAnnotation(name, **args)`` and records a :class:`Span`
(``time.perf_counter_ns`` at entry and exit).  While a profiler runs, the
span lands in its trace on the device events' clock, under ``name``
unchanged, with ``args`` as the event's stats.  Traced or not, the record
goes into a ring that keeps the spans of the last :data:`STEPS_KEPT` steps,
so a slow step can be taken apart after the fact.  A span opened while no
other is open starts a new step.
"""
from __future__ import annotations

import collections
import time
from typing import Deque, Dict, List, NamedTuple, Optional

import jax

STEPS_KEPT = 4096


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]     # the enclosing span's name; None for a step
    args: Dict[str, int]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class SpanLog:
    """The spans of the last :data:`STEPS_KEPT` steps, oldest first."""

    def __init__(self):
        self._steps: Deque[List[Span]] = collections.deque(maxlen=STEPS_KEPT)
        self._open: List[str] = []

    def span(self, name: str, **args) -> "_Open":
        return _Open(self, name, args)

    def steps(self) -> List[List[Span]]:
        """Every kept step's spans, the step's own span first, by start."""
        return [sorted(step, key=lambda s: (s.start_ns, -s.end_ns))
                for step in self._steps]


def phase_seconds(step: List[Span]) -> Dict[str, float]:
    """Seconds of one step's spans by name, the step's own span left out
    (a name that recurs in the step, such as one admission per slot,
    adds up)."""
    out: Dict[str, float] = collections.defaultdict(float)
    for s in step:
        if s.parent is not None:
            out[s.name] += s.seconds
    return dict(out)


class _Open:
    __slots__ = ("log", "name", "args", "parent", "start", "annotation")

    def __init__(self, log: SpanLog, name: str, args: Dict[str, int]):
        self.log, self.name, self.args = log, name, args

    def __enter__(self):
        log = self.log
        if log._open:
            self.parent = log._open[-1]
        else:
            self.parent = None
            log._steps.append([])
        log._open.append(self.name)
        self.annotation = jax.profiler.TraceAnnotation(self.name, **self.args)
        self.annotation.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self.annotation.__exit__(*exc)
        log = self.log
        log._open.pop()
        log._steps[-1].append(
            Span(self.name, self.start, end, self.parent, self.args))
        return False
