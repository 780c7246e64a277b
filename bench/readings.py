"""Arithmetic the metric readers share, over a run's record (see
``harness.run_cell``): ``setup_s``, ``window`` (queries, rows, wall
seconds from the first query's start to the last one's end),
``counters`` (per device call or per answer), ``setup_compile`` and
``window_compile`` (``CompileClock`` readings) and ``trace`` (the
reduced profiler trace of the traced queries, or ``None``)."""

from __future__ import annotations

from typing import Dict, Optional


def mean(values) -> Optional[float]:
    values = list(values)
    return sum(values) / len(values) if values else None


def rows_per_s(record: Dict) -> Optional[float]:
    """All rows of all the window's queries over its wall time."""
    w = record["window"]
    return w["rows"] / w["wall_s"] if w["rows"] and w["wall_s"] > 0 else None


def seconds_per_query(record: Dict) -> Optional[float]:
    """The window's wall time over its queries."""
    w = record["window"]
    return w["wall_s"] / w["queries"] if w["queries"] else None


def program_s_per_query(record: Dict) -> Optional[float]:
    """Device seconds inside the named device programs per traced
    query, averaged over the devices."""
    t = record["trace"]
    if not t or not t["devices"] or not t["queries"]:
        return None
    s = mean(d["program_s"] for d in t["devices"])
    return s / t["queries"] if s > 0 else None


def idle_pct(record: Dict) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device, averaged over the devices, in per cent."""
    t = record["trace"]
    if not t or not t["devices"] or not t.get("window_s"):
        return None
    busy = mean(d["busy_s"] for d in t["devices"])
    return 100.0 * (1.0 - busy / t["window_s"])
