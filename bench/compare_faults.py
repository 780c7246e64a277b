"""The comparison that decides ``correct`` in the fault cell: FB rows of
the program under failures against rows of ``bench/reference_faults.py``.

Each sampled row is held to a limit on each of these gaps from its
reference row, and the widest of each over the sample is reported:

- ``fault_node_hours_gap``, ``fault_peak_gap``: the relative gaps of
  node-hours and peak. Both count nodes, not jobs, so which job a
  failure kills does not move them.
- ``fault_completed_gap``: the relative gap of completed jobs. The
  planner picks the running jobs a failure or a web-demand rise kills
  by power-of-two size class and arrival, the reference the smallest
  and the latest started, which moves which jobs finish inside the
  horizon; kills are frequent under failures.
- ``fault_execution_gap``: the relative gap of the execution-time mean,
  moved by the same kills.

``rows_off`` counts the rows missing, with a number that is not one, or
with a gap beyond its limit; the harness holds it to 0
(``bench.compare.LIMITS``).
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, Optional, Tuple

from bench import compare

# number -> the metric whose relative gap it is
GAPS = {"fault_node_hours_gap": "node_hours",
        "fault_peak_gap": "peak_nodes",
        "fault_completed_gap": "completed_jobs",
        "fault_execution_gap": "avg_execution"}

# Limit of each gap on every row; PERF.md gives the readings each limit
# was set from.
LIMITS = {"fault_node_hours_gap": 3e-4, "fault_peak_gap": 0.0,
          "fault_completed_gap": 0.02, "fault_execution_gap": 0.03}


def compare_rows(pairs: Iterable[Tuple[Optional[Dict], Dict]]
                 ) -> Dict[str, float]:
    """``pairs`` of (program row or None, reference row). Returns
    ``rows_off``, the widest of each gap over all pairs, and how many
    rows were compared."""
    out = {"rows_off": 0}
    out.update({name: 0.0 for name in GAPS})
    n = 0
    for got, ref in pairs:
        n += 1
        gaps = {name: compare.rel_gap(
                    math.nan if got is None else float(got.get(k, math.nan)),
                    float(ref[k]))
                for name, k in GAPS.items()}
        if any(not g <= LIMITS[name] for name, g in gaps.items()):
            out["rows_off"] += 1
        for name, g in gaps.items():
            out[name] = max(out[name], g)
    # A missing row or a NaN reads as the largest float, so the result
    # line stays plain JSON.
    out = {k: min(v, compare.NO_NUMBER) for k, v in out.items()}
    out["rows_compared"] = n
    return out
