"""Spans and counters of the planner's own phases, kept in memory.

``span(name)`` times a block on ``time.perf_counter_ns`` and records
``(id, parent_id, root_id, name, start_ns, end_ns)`` when it closes, in a
ring of the last 65,536 records. A span opened while another is open is
its child; the outermost open span is the root, and every span under it
carries the root's id. The block also runs inside
``jax.profiler.TraceAnnotation("repro." + name)``, so that in a profiler
session each span lands on the host plane on the device's clock.

``count(name, n)`` adds to a counter of the open root (nothing happens
when no root is open); Python's garbage collector counts its runs and
pauses there too (``gc.collections``, ``gc.pause_ns``). ``roots(name)``
reads the ring back: each root of that name with its counters and the
summed durations of the spans under it, by name.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

__all__ = ["Span", "Recorder", "span", "count", "roots"]

PREFIX = "repro."
MAXLEN = 1 << 16


class Span(NamedTuple):
    id: int
    parent_id: Optional[int]
    root_id: int
    name: str
    start_ns: int
    end_ns: int
    counters: Optional[Dict[str, int]] = None   # roots only


class Recorder:
    """A bounded ring of closed spans; each thread nests its own."""

    def __init__(self, maxlen: int = MAXLEN):
        self.records: collections.deque = collections.deque(maxlen=maxlen)
        self.dropped = 0
        self._dropped_end_ns = -1     # end of the newest dropped record
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._gc_start_ns = 0

    def _stack(self) -> List:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextlib.contextmanager
    def span(self, name: str):
        stack = self._stack()
        sid = next(self._ids)
        parent, root = (stack[-1][0], stack[0][0]) if stack else (None, sid)
        counters = None if stack else {}
        with jax.profiler.TraceAnnotation(PREFIX + name):
            stack.append((sid, counters))
            start = time.perf_counter_ns()
            try:
                yield
            finally:
                end = time.perf_counter_ns()
                stack.pop()
                if len(self.records) == self.records.maxlen:
                    self.dropped += 1
                    self._dropped_end_ns = self.records[0].end_ns
                self.records.append(
                    Span(sid, parent, root, name, start, end, counters))

    def count(self, name: str, n: int = 1) -> None:
        stack = self._stack()
        if stack:
            counters = stack[0][1]
            counters[name] = counters.get(name, 0) + n

    def on_gc(self, phase: str, info: Dict) -> None:
        """A ``gc.callbacks`` hook: counts collections and their pause
        while a root is open."""
        if phase == "start":
            self._gc_start_ns = time.perf_counter_ns()
        elif self._gc_start_ns and self._stack():
            self.count("gc.collections")
            self.count("gc.pause_ns", time.perf_counter_ns()
                       - self._gc_start_ns)

    def roots(self, name: str) -> List[Dict]:
        """Root spans named ``name``, oldest first, each with ``s`` (its
        seconds), ``counters``, ``children_s`` (seconds of the spans
        under it, summed by name) and ``complete`` (no span under it
        was dropped from the ring)."""
        under: Dict[int, Dict[str, int]] = {}
        found = []
        for r in list(self.records):
            if r.parent_id is None:
                if r.name == name:
                    found.append(r)
            else:
                sums = under.setdefault(r.root_id, {})
                sums[r.name] = sums.get(r.name, 0) + r.end_ns - r.start_ns
        return [{"id": r.id, "start_ns": r.start_ns, "end_ns": r.end_ns,
                 "s": (r.end_ns - r.start_ns) * 1e-9,
                 "counters": dict(r.counters),
                 "children_s": {k: v * 1e-9
                                for k, v in under.get(r.id, {}).items()},
                 "complete": r.start_ns > self._dropped_end_ns}
                for r in found]


RECORDER = Recorder()
span, count, roots = RECORDER.span, RECORDER.count, RECORDER.roots
gc.callbacks.append(RECORDER.on_gc)
