"""The comparison that decides ``correct``: rows of the program under
test against rows of the plain reference (``bench/reference.py``).

Each number compared is a count or a worst gap over the sampled rows,
and passes when it is at most its limit:

- ``rows_off``: rows whose exact integers differ from the reference's
  (or that are missing): FLB-NUB and EC2 completed jobs, FB, EC2 and
  DCS peak nodes.
- ``float_gap``: the widest relative gap of a float held exact to
  round-off: FLB-NUB and EC2 execution-time mean, FB, EC2 and DCS
  node-hours.
- ``fb_completed_gap``: the widest gap, in jobs, of FB's completed jobs.
  The planner picks the jobs a web-demand rise kills by size class, the
  reference the smallest and latest started, which can move a
  completion or two.
- ``fb_execution_gap``: the widest relative gap of FB's execution-time
  mean, moved by the same kills.
- ``flb_node_hours_gap``, ``flb_peak_gap``: the widest relative gaps of
  FLB-NUB's node-hours and peak, which the planner counts low where the
  batch side holds the web side's pool nodes.

A caller that names fewer ``gaps`` holds the metrics of the others
exact, under ``rows_off`` and ``float_gap``.
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, Optional, Sequence, Tuple

# kind -> the metrics held exact (to round-off for the float ones).
EXACT_COUNTS = {"fb": ("peak_nodes",),
                "flb_nub": ("completed_jobs",),
                "ec2": ("completed_jobs", "peak_nodes"),
                "dcs": ("peak_nodes",)}
EXACT_FLOATS = {"fb": ("node_hours",),
                "flb_nub": ("avg_execution",),
                "ec2": ("avg_execution", "node_hours"),
                "dcs": ("node_hours",)}
# number -> (kind, metric, relative?): metrics held within a gap.
GAPS = {"fb_completed_gap": ("fb", "completed_jobs", False),
        "fb_execution_gap": ("fb", "avg_execution", True),
        "flb_node_hours_gap": ("flb_nub", "node_hours", True),
        "flb_peak_gap": ("flb_nub", "peak_nodes", True)}

# Limits of the numbers compared; PERF.md gives the readings each limit
# was set from.
LIMITS = {"rows_off": 0, "float_gap": 3e-4, "fb_completed_gap": 6,
          "fb_execution_gap": 1e-3, "flb_node_hours_gap": 0.05,
          "flb_peak_gap": 0.05, "answers_off": 0}
NO_NUMBER = 1e300


def rel_gap(a: float, b: float) -> float:
    """``|a - b| / |b|``, with 0 for two zeros and ∞ for a NaN."""
    if math.isnan(a) or math.isnan(b):
        return math.inf
    if a == b:
        return 0.0
    return abs(a - b) / max(abs(b), 1e-300)


def _gap(a: float, b: float, relative: bool) -> float:
    if math.isnan(a) or math.isnan(b):
        return math.inf
    return rel_gap(a, b) if relative else abs(a - b)


def compare_rows(pairs: Iterable[Tuple[str, Optional[Dict], Dict]],
                 gaps: Sequence[str] = tuple(GAPS)) -> Dict[str, float]:
    """``pairs`` of (system kind, program row or None, reference row).
    Returns the numbers of the module docstring over all pairs (of
    ``gaps``, those whose kind some pair has), and how many rows were
    compared."""
    pairs = list(pairs)
    kinds = {kind for kind, _, _ in pairs}
    gaps = {name: GAPS[name] for name in gaps if GAPS[name][0] in kinds}
    counts, floats = dict(EXACT_COUNTS), dict(EXACT_FLOATS)
    for name, (kind, k, relative) in GAPS.items():
        if name not in gaps:
            held = floats if relative else counts
            held[kind] = held[kind] + (k,)
    out = {"rows_off": 0, "float_gap": 0.0}
    out.update({name: 0.0 for name in gaps})
    n = 0
    for kind, got, ref in pairs:
        n += 1
        if got is None:
            out["rows_off"] += 1
            got = {}
        elif any(int(got[k]) != int(ref[k]) for k in counts[kind]):
            out["rows_off"] += 1
        for k in floats[kind]:
            out["float_gap"] = max(out["float_gap"], rel_gap(
                float(got.get(k, math.nan)), float(ref[k])))
        for name, (gap_kind, k, relative) in gaps.items():
            if gap_kind == kind:
                out[name] = max(out[name], _gap(
                    float(got.get(k, math.nan)), float(ref[k]), relative))
    # A missing row or a NaN reads as the largest float, so the result
    # line stays plain JSON.
    out = {k: min(v, NO_NUMBER) for k, v in out.items()}
    out["rows_compared"] = n
    return out


def verdict(numbers: Dict[str, float]) -> Tuple[bool, Dict[str, Dict]]:
    """Every limited number against its limit. Returns (all within,
    ``{name: {"value": v, "limit": l}}``)."""
    checks = {k: {"value": numbers[k], "limit": LIMITS[k]}
              for k in LIMITS if k in numbers}
    ok = bool(checks) and all(c["value"] <= c["limit"]
                              for c in checks.values())
    return ok, checks
