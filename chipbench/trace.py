"""Reduction of one profiler trace (``.xplane.pb``) to intervals.

Device planes are the ``/device:TPU:<n>`` planes.  On each, the line
``XLA Modules`` holds one event per execution of a compiled program,
named ``<module>(<id>)`` (``jit_decode(…)`` is the engine's decode step),
and the line ``XLA Ops`` one event per operation run.  Host spans are the
``jax.profiler.TraceAnnotation`` events on the host plane's threads.  All
events share the trace's nanosecond clock; the harness marks its measured
window with the span :data:`WINDOW`.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Dict, List, Optional, Tuple

WINDOW = "chipbench.window"
# the host spans the harness opens (its own and the engine methods it wraps)
SPAN_PREFIXES = ("chipbench.", "Engine.")
# spans of one step nest a few deep: the innermost open span lies among
# the last few started
SPAN_LOOKBACK = 64
MODULES, OPS = "XLA Modules", "XLA Ops"

Interval = Tuple[float, float]          # (start_ns, end_ns)


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, "
                           f"found {len(found)}")
    return found[0]


def module_name(event_name: str) -> str:
    return event_name.split("(")[0]


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return event_name.split(" = ")[0]


def self_times(ops) -> List[float]:
    """Seconds of each op not covered by ops nested in it (a ``while``
    contains its body's ops); ``ops`` sorted by start, longest first."""
    out = [(e - s) / 1e9 for _, _, s, e in ops]
    stack: List[int] = []
    for i, (_, _, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][3] <= s:
            stack.pop()
        if stack:
            out[stack[-1]] -= (e - s) / 1e9
        stack.append(i)
    return out


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: List[Interval], w: Interval) -> List[Interval]:
    return [(max(s, w[0]), min(e, w[1])) for s, e in intervals
            if e > w[0] and s < w[1]]


class Trace:
    """The events of one trace, kept as plain tuples."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        self.modules: List[Tuple[str, float, float]] = []   # (module, s, e)
        self.ops: List[Tuple[str, str, float, float]] = []  # (module, op, s, e)
        self.spans: List[Tuple[str, float, float]] = []     # host (name, s, e)
        self.self_s: List[float] = []                       # per op
        self.n_devices = 0
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                self._device(plane)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIXES):
                            self.spans.append((ev.name, ev.start_ns,
                                               ev.start_ns + ev.duration_ns))
        windows = [(s, e) for n, s, e in self.spans if n == WINDOW]
        self.window: Optional[Interval] = windows[0] if windows else None

    def _device(self, plane):
        lines = {line.name: line for line in plane.lines}
        if MODULES not in lines:
            return
        self.n_devices += 1
        mods = []
        for ev in lines[MODULES].events:
            iv = (module_name(ev.name), ev.start_ns,
                  ev.start_ns + ev.duration_ns)
            mods.append(iv)
        self.modules += mods
        if OPS in lines:
            mods.sort(key=lambda m: m[1])
            starts = [m[1] for m in mods]
            evs = sorted(((ev.start_ns, -ev.duration_ns, ev.name)
                          for ev in lines[OPS].events))
            for s, neg_d, name in evs:
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][0] if i >= 0 and s < mods[i][2] else "?"
                self.ops.append((mod, op_name(name), s, s - neg_d))
            self.self_s += self_times(self.ops[len(self.ops) - len(evs):])

    # -- readings, all inside the measured window ---------------------------
    def executions(self, module: str) -> List[Interval]:
        """Executions of ``module`` that start inside the window."""
        w = self.window
        return [(s, e) for m, s, e in self.modules
                if m == module and w[0] <= s < w[1]]

    def busy(self, ops: bool = False) -> List[Interval]:
        """Merged intervals in which a program ran on a device; with
        ``ops``, in which one of its operations ran (the gaps between a
        program's operations are then idle too)."""
        src = ([(s, e) for _, _, s, e in self.ops] if ops
               else [(s, e) for _, s, e in self.modules])
        return merge(clip(src, self.window))

    def busy_s(self, ops: bool = False) -> float:
        """Busy seconds, averaged over the devices traced."""
        return (sum(e - s for s, e in self.busy(ops)) / 1e9
                / max(self.n_devices, 1))

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def top_ops(self, n: int = 10) -> List[List]:
        """The ops that ran longest in the window, by self time."""
        tot: Dict[str, float] = collections.Counter()
        for (mod, op, s, e), own in zip(self.ops, self.self_s):
            if self.window[0] <= s < self.window[1]:
                tot[f"{mod}/{op}"] += own
        return [[k, v] for k, v in tot.most_common(n)]

    def idle_by_span(self, n: int = 10) -> List[List]:
        """Idle device seconds in the window (no program running), by the
        innermost host span open at the middle of each idle gap."""
        busy = self.busy()
        edges = [self.window[0]] + [x for iv in busy for x in iv] \
            + [self.window[1]]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        # the harness's spans nest on one thread, so the innermost span
        # open at t is the latest-started one that has not ended
        spans = sorted((s, e, name) for name, s, e in self.spans
                       if name != WINDOW)
        starts = [s for s, _, _ in spans]
        tot: Dict[str, float] = collections.Counter()
        for a, b in idle:
            mid = (a + b) / 2
            name = "(no span)"
            hi = bisect.bisect_right(starts, mid)
            for i in range(hi - 1, max(hi - SPAN_LOOKBACK, 0) - 1, -1):
                if spans[i][1] > mid:
                    name = spans[i][2]
                    break
            tot[name] += (b - a) / 1e9
        return [[k, v] for k, v in tot.most_common(n)]
