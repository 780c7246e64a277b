"""Paper-grid queries: the FB / FLB-NUB / DCS / EC2 grid of the
PhoenixCloud paper's Figs. 13, 14 and 18 over a site's workloads, one
``run_sweep_workloads`` call per query.

Traffic keys (``bench/traffic/<name>.json``):

- ``fb_capacity``: ``{"count", "low", "high"}`` — each query takes
  ``count`` FB capacities C in ``[low, high]``, one from each of
  ``count`` equal strata;
- ``flb_pool``: ``{"count", "low", "high", "lb_ws_max"}`` — FLB-NUB pool
  sizes B, drawn alike, split as ``lb_ws = min(lb_ws_max, B - 1)``;
- ``fixed_points``: points every query carries as published;
- ``check_rows``: ``{kind: n}`` — rows of each system kind, sampled from
  all the window's queries, that the reference re-runs.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench import compare, reference
from bench.generators import common, site_traces

PROGRAMS = ("_rounds_grids_single",)


class PaperSweep:
    def __init__(self, config: Dict, traffic: Dict, seed: int):
        from repro.sim.sweep import ScanOptions
        self.config, self.traffic, self.seed = config, traffic, seed
        self.horizon = float(config["horizon_s"])
        self.workloads = [site_traces.workload(w).cut(self.horizon)
                          for w in config["workloads"]]
        self.program_workloads = [
            (common.program_jobs(w.submit, w.size, w.runtime), list(w.ws))
            for w in self.workloads]
        self.options = ScanOptions(window=config["job_window"],
                                   dtype=common.dtype(config))

    def query(self, k: int) -> List[Dict]:
        """Query ``k``'s points: fresh FB capacities and FLB-NUB pool
        sizes, then the fixed points."""
        t = self.traffic
        fb, flb = t["fb_capacity"], t["flb_pool"]
        points = [{"system": "fb", "capacity": c}
                  for c in self._stratified(fb, k, 0)]
        for b in self._stratified(flb, k, 1):
            w = min(flb["lb_ws_max"], b - 1)
            points.append({"system": "flb_nub", "lb_pbj": b - w,
                           "lb_ws": w})
        return points + [dict(p) for p in t["fixed_points"]]

    def _stratified(self, knob: Dict, k: int, which: int) -> List[int]:
        """``knob["count"]`` values, one from each of as many equal
        strata of ``[low, high]``. Each stratum is dealt out in an order
        drawn from the seed, a fresh order every pass, so every run asks
        about the same set of values in another order and no two
        queries of a run repeat a set."""
        strata = np.array_split(np.arange(knob["low"], knob["high"] + 1),
                                knob["count"])
        n = min(len(x) for x in strata)
        cycle, i = divmod(k, n)
        return [int(common.rng(self.seed, cycle, which, j).permutation(x)[i])
                for j, x in enumerate(strata)]

    def run(self, points: List[Dict]):
        from repro.sim.sweep import run_sweep_workloads
        return run_sweep_workloads(
            [common.sweep_point(p) for p in points], self.program_workloads,
            self.horizon, mode="rounds", scan_options=self.options)

    def summary(self, points, rows) -> Dict:
        flat = [r for rs in rows for r in rs]
        per_call = [max(r["rounds"] for r in rs if r["engine"] == "rounds")
                    for rs in rows]
        return {"rows": len(flat),
                "failed": any(common.bad_row(r) for r in flat),
                "rounds_max": per_call}

    def check(self, done, r: np.random.Generator) -> Dict:
        """Re-run a seeded sample of the window's rows on the reference."""
        by_kind: Dict[str, list] = {}
        for k, points, rows in done:
            if rows is None:
                continue
            for w in range(len(self.workloads)):
                for i, p in enumerate(points):
                    by_kind.setdefault(p["system"], []).append((k, w, i))
        pairs, t0 = [], time.perf_counter()
        queries = {k: (points, rows) for k, points, rows in done}
        for kind, n in self.traffic["check_rows"].items():
            for k, w, i in common.sample(r, by_kind.get(kind, []), n):
                points, rows = queries[k]
                wl = self.workloads[w]
                ref = reference.row(points[i], wl.submit, wl.size,
                                    wl.runtime, wl.ws, self.horizon)
                pairs.append((kind, rows[w][i], ref))
        out = compare.compare_rows(pairs)
        out["reference_s"] = time.perf_counter() - t0
        return out


def make(config: Dict, traffic: Dict, seed: int) -> PaperSweep:
    return PaperSweep(config, traffic, seed)
