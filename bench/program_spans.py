"""Arithmetic the readers of the program's own spans and counters share
(``repro.spans``, in the process that ran the window).

A window's roots are the last ``record["window"]["queries"]`` roots of
the cell's root span, one per query. In a traced run the readers read
the first of them, the profiled query: the device metrics read that
query too, so the program's phases split the same query's time, and
the query after the profile runs slow (on a TPU v5e host its pack took
250 ms against 34 ms). Where the trace holds no device (the CPU), they stay
silent as the device metrics do. A reader also returns ``None`` in a
program without ``repro.spans``, when fewer roots were recorded than
the window ran, or when the ring dropped any span under them.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from bench import readings


def window_roots(record: Dict, name: str) -> Optional[List[Dict]]:
    """The roots named ``name`` that the readers read, oldest first."""
    try:
        from repro import spans
    except ImportError:
        return None
    n = record["window"]["queries"]
    found = spans.roots(name)
    if n < 1 or len(found) < n:
        return None
    picked = found[-n:]
    trace = record.get("trace")
    if trace is not None:
        if not trace.get("devices"):
            return None
        picked = picked[:1]
    if not all(r["complete"] for r in picked):
        return None
    return picked


def child_s(record: Dict, name: str, child: str) -> Optional[float]:
    """Mean over the window's roots of the seconds in spans ``child``."""
    roots = window_roots(record, name)
    if roots is None:
        return None
    return readings.mean(r["children_s"].get(child, 0.0) for r in roots)


def outside_s(record: Dict, name: str, child: str) -> Optional[float]:
    """Mean over the window's roots of the root's seconds less those in
    spans ``child``."""
    roots = window_roots(record, name)
    if roots is None:
        return None
    return readings.mean(r["s"] - r["children_s"].get(child, 0.0)
                         for r in roots)


def share_pct(record: Dict, name: str, part: str,
              *whole: str) -> Optional[float]:
    """100 × counter ``part`` over the counters ``whole``, each summed
    over the window's roots."""
    roots = window_roots(record, name)
    if roots is None:
        return None

    def total(*counters: str) -> int:
        return sum(r["counters"].get(c, 0) for r in roots for c in counters)
    den = total(*whole)
    return 100.0 * total(part) / den if den else None
