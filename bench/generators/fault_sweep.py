"""Fault-sweep queries: the FB system of the PhoenixCloud paper over a
site's workloads while its nodes fail, one ``run_sweep_workloads`` call
per query with one failure schedule per (workload, draw).

Traffic keys (``bench/traffic/<name>.json``):

- ``fb_capacity``: ``{"strata", "low", "high"}`` — each query takes one
  FB capacity C in ``[low, high]``: the strata are dealt out in an order
  drawn from the seed, a fresh order every pass, and each stratum gives
  a value drawn from the seed;
- ``leases``: the FB lease units L; the query's points are FB(C, L);
- ``rate_multipliers``, ``draws_per_rate``: each workload runs under
  ``draws_per_rate`` schedules at each multiple of the configuration's
  failure rates;
- ``fault_stops``: the most fault instants one schedule may hold inside
  the horizon, so that every query runs the program the warm-up
  compiled;
- ``check_rows``: ``{"fb": n}`` — rows, sampled from all the window's
  queries, that the reference re-runs.

The configuration's ``failures`` block gives the failure model: per
node a Weibull renewal process with lognormal repairs, and racks whose
outages take all their nodes at once. A query draws its schedules on
the host, from the seed and the query index, as part of its answer.
"""

from __future__ import annotations

import math
import time
from typing import Dict, List

import numpy as np

from bench import compare_faults, reference_faults
from bench.generators import common, site_traces

PROGRAMS = ("_rounds_grids_single",)
DAY_S, HOUR_S = 86400.0, 3600.0


class FaultSweep:
    def __init__(self, config: Dict, traffic: Dict, seed: int):
        from repro.sim.sweep import ScanOptions
        self.config, self.traffic, self.seed = config, traffic, seed
        self.horizon = float(config["horizon_s"])
        self.failures = config["failures"]
        self.workloads = [site_traces.workload(w).cut(self.horizon)
                          for w in config["workloads"]]
        self.program_workloads = [
            (common.program_jobs(w.submit, w.size, w.runtime), list(w.ws))
            for w in self.workloads]
        self.options = ScanOptions(window=config["job_window"],
                                   dtype=common.dtype(config),
                                   fault_stops=traffic["fault_stops"])
        # (multiplier, draw) of each schedule a workload runs under.
        self.draws = [(m, d) for m in traffic["rate_multipliers"]
                      for d in range(traffic["draws_per_rate"])]

    def query(self, k: int) -> Dict:
        """Query ``k``: its index and its FB capacity."""
        knob = self.traffic["fb_capacity"]
        strata = np.array_split(np.arange(knob["low"], knob["high"] + 1),
                                knob["strata"])
        cycle, i = divmod(k, len(strata))
        j = int(common.rng(self.seed, cycle, 0).permutation(len(strata))[i])
        c = int(common.rng(self.seed, cycle, 1, j).choice(strata[j]))
        return {"k": k, "capacity": c}

    def points(self, q: Dict) -> List[Dict]:
        return [{"system": "fb", "capacity": q["capacity"],
                 "lease_seconds": float(L)} for L in self.traffic["leases"]]

    def schedule(self, q: Dict, w: int, d: int):
        """The failure schedule of workload ``w``'s draw ``d`` in query
        ``q``: node failures and rack outages over its C nodes."""
        from repro.sim import faults
        f, c = self.failures, q["capacity"]
        mult, _ = self.draws[d]
        r = common.rng(self.seed, q["k"], w, d)
        node, repair = f["node_time_between_failures"], f["node_repair"]
        rack = f["rack"]
        parts = [faults.weibull_schedule(
            int(r.integers(1 << 62)), c,
            node["mean_days"] * DAY_S / mult, repair["mean_hours"] * HOUR_S,
            self.horizon, shape=node["shape"], repair="lognormal",
            repair_sigma=repair["log_sigma"])]
        for j in range(math.ceil(c / rack["nodes"])):
            parts.append(faults.burst_schedule(
                int(r.integers(1 << 62)),
                min(rack["nodes"], c - j * rack["nodes"]),
                365.0 * DAY_S / (rack["outages_per_year"] * mult),
                rack["outage_mean_hours"] * HOUR_S, self.horizon,
                repair="lognormal", repair_sigma=rack["log_sigma"]))
        return faults.merge_schedules(*parts)

    def run(self, q: Dict):
        """The query's rows, ``rows[w * draws + d][i]``, and the
        schedules they ran under."""
        from repro.sim.sweep import run_sweep_workloads
        n = len(self.draws)
        schedules = [self.schedule(q, w, d)
                     for w in range(len(self.workloads)) for d in range(n)]
        workloads = [wl for wl in self.program_workloads for _ in range(n)]
        rows = run_sweep_workloads(
            [common.sweep_point(p) for p in self.points(q)], workloads,
            self.horizon, mode="rounds", scan_options=self.options,
            faults=schedules)
        return {"rows": rows, "faults": schedules}

    def summary(self, q, answer) -> Dict:
        rows = answer["rows"]
        n = len(self.draws)
        flat = [r for rs in rows for r in rs]
        per_workload = [max(r["rounds"] for rs in rows[w * n:(w + 1) * n]
                            for r in rs)
                        for w in range(len(self.workloads))]
        return {"rows": len(flat),
                "failed": any(common.bad_row(r) for r in flat),
                "rounds_max": per_workload}

    def check(self, done, r: np.random.Generator) -> Dict:
        """Re-run a seeded sample of the window's rows on the reference."""
        cells = [(k, s, i) for k, q, a in done if a is not None
                 for s in range(len(a["rows"]))
                 for i in range(len(self.traffic["leases"]))]
        answers = {k: (q, a) for k, q, a in done}
        pairs, t0 = [], time.perf_counter()
        for k, s, i in common.sample(r, cells, self.traffic["check_rows"]["fb"]):
            q, a = answers[k]
            wl = self.workloads[s // len(self.draws)]
            ref = reference_faults.row(
                self.points(q)[i], wl.submit, wl.size, wl.runtime, wl.ws,
                list(a["faults"][s].events()), self.horizon)
            pairs.append((a["rows"][s][i], ref))
        out = compare_faults.compare_rows(pairs)
        out["reference_s"] = time.perf_counter() - t0
        return out


def make(config: Dict, traffic: Dict, seed: int) -> FaultSweep:
    """The cell's queries. A planner whose ``run_sweep_workloads`` takes
    no fault schedules cannot run them: refused here, at set-up, where
    otherwise every query of the window would fail alike."""
    import inspect
    from repro.sim.sweep import run_sweep_workloads
    if "faults" not in inspect.signature(run_sweep_workloads).parameters:
        raise TypeError("this planner's run_sweep_workloads takes no "
                        "fault schedules")
    return FaultSweep(config, traffic, seed)
